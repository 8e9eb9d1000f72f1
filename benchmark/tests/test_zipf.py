"""``sbom-zipf-2m`` at test size on the CPU, in process
(``require_chip=False``). ``--rehearsal`` cannot run this cell:
``run.py`` takes a mode's test sizes from ``tests/tiny.json``, which
has no entry for ``batch_pass_zipf`` and is not this PR's to edit, so
the sizes and the traffic are overridden here, as
``test_shared_base.py`` does.

* the cell gives a well-formed result, traced and not, with every
  per-layer metric that a CPU run can read;
* ``gen_zipf`` writes what ``gen`` would for the same draws, draws by
  popularity, and keeps the same packages popular on every seed;
* the program is correct and ``reference.py``'s control in its place
  is not, on three seeds;
* two faults planted in the program underneath a whole run come out
  not correct: an interval hit lost for one of two documents that
  share a purl, and a purl memo that answers with its neighbour's
  parse.
"""

import json
import os
import re

import numpy as np
import pytest

import run as bench_run
from conftest import ROOT

SEEDS = (2147483777, 11, 4096)
SIZES = {"os_universe": 600, "ghsa_pkgs": 800, "name_universe": 8000,
         "comps": 20}
TRAFFIC = {"batch": 100, "pool": 300, "warmup": 100,
           "check_per_pass": 100}
CELL = "sbom-zipf-2m"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# what only a device trace gives
TRACE_ONLY = {"interval.device_ms_per_kdoc.2m", "interval_roofline.2m",
              "device_idle_share.2m"}


def one_run(seed, control=False, trace=False, seconds=1.0):
    cell = bench_run.load_cell(CELL)
    cell["config"]["sizes"].update(SIZES)
    cell["traffic"].update(TRAFFIC)
    lines = []
    run = bench_run.Cell(cell, seed, seconds, trace=trace,
                         require_chip=False, control=control)
    run.say = lambda *parts: lines.append(" ".join(map(str, parts)))
    out = run.run()
    out["lines"] = lines
    return out


def repeats(line) -> list:
    """The three numbers of the mode's ``repeats:`` line."""
    said = [ln for ln in line["lines"] if ln.startswith("repeats:")]
    assert len(said) == 1
    return [int(n) for n in re.findall(r"\d+", said[0])[:3]]


@pytest.mark.parametrize("trace", [False, True])
def test_cell_gives_a_well_formed_result(trace):
    line = one_run(SEEDS[0], trace=trace)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    listed = {m["name"]: m for m in
              BENCH["per_layer" if trace else "end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    for name, m in line["metrics"].items():
        assert m["unit"] == listed[name]["unit"]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(line["metrics"]) == {"sboms_per_s", "setup_s"}
        return
    moved = {n for n, m in listed.items()
             if m["moves"] == "sboms_per_s"}
    assert set(line["metrics"]) >= moved - TRACE_ONLY
    values = {n: m["value"] for n, m in line["metrics"].items()}
    # on the CPU nothing is warmed beyond the warm-up pass, and the
    # small memo of a test never turns
    assert values.pop("compile.fresh_in_window.2m") >= 0.0
    assert values.pop("sbom.purl_memo_turns_per_kdoc.2m") == 0.0
    assert all(v > 0 for v in values.values()), values
    # popular packages come back: uniform draws read under 1%
    assert values["sbom.purl_memo_hit_share.2m"] > 20.0
    assert values["interval.unique_job_share.2m"] < 90.0


def test_the_compared_documents_share_packages():
    line = one_run(SEEDS[0])
    shared, compared, differently = repeats(line)
    assert compared == line["compared"]["reports_compared"][0]
    assert shared > 50 and differently == 0


def test_generator_writes_what_gen_would_and_draws_by_popularity():
    import gen
    import gen_zipf
    sz = dict(bench_run.load_cell(CELL)["config"]["sizes"], **SIZES)
    docs, facts = gen_zipf.build_sboms(sz, 400, SEEDS[0], "bom")
    again, _ = gen_zipf.build_sboms(sz, 400, SEEDS[0], "bom")
    assert docs == again
    for n in (0, 7, 399):
        doc = json.loads(docs[n][1])
        comps = gen.sbom_components(facts, n)
        assert [(c["name"], c["version"]) for c in doc["components"]] \
            == [(f"{gen.ECOSYSTEMS[e][0]}-lib-{i}", v)
                for e, i, v in comps]
        assert all(c["purl"] == c["bom-ref"].rsplit("-", 2)[0]
                   for c in doc["components"])
        assert len({c["bom-ref"] for c in doc["components"]}) == \
            len(doc["components"]) == sz["comps"]
        assert doc["serialNumber"] == f"urn:uuid:bom-{n}"
    # the head of the curve: rank 1 takes about 1 draw in
    # ln(8000) + 0.58 = 9.6 of its ecosystem, uniform draws 1 in 8000
    order = gen_zipf.popularity_order(sz["name_universe"],
                                      sz["db_seed"])
    top = [(facts["idx"][facts["eco"] == e] == order[e, 0]).mean()
           for e in range(4)]
    assert all(0.07 < share < 0.14 for share in top), top
    # the same packages are popular on another seed and another tag
    _, other = gen_zipf.build_sboms(sz, 400, SEEDS[1], "warm")
    assert not np.array_equal(other["idx"], facts["idx"])
    assert all((other["idx"][other["eco"] == e] == order[e, 0]).mean()
               > 0.07 for e in range(4))
    # a tenth of the names bear advisories, at the head as in the
    # tail: the cut is by index, the popularity by permutation
    bearing = (order < sz["ghsa_pkgs"])
    assert abs(bearing.mean() - 0.1) < 1e-9
    assert 0.03 < bearing[:, :200].mean() < 0.2
    # repeats inside one document stay, under their own bom-ref
    assert any(len({c["purl"] for c in
                    json.loads(d)["components"]}) < sz["comps"]
               for _, d in docs)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct_and_control_is_not(seed):
    good = one_run(seed)
    assert good["correct"] is True, good["compared"]
    assert good["compared"]["reports_mismatched"] == [0, 0]
    assert good["compared"]["host_fallback_pairs"] == [0, 0]
    bad = one_run(seed, control=True)
    assert bad["correct"] is False
    assert bad["compared"]["reports_mismatched"][0] > 0


def test_fault_hit_lost_for_one_of_two_documents(monkeypatch):
    """The fan-out of one row's hit to every document that asked is
    broken for one of them: of two documents that hold the same
    vulnerable purl, one keeps the finding and one loses it."""
    from trivy_tpu.runtime import batch
    real = batch.dispatch_jobs
    calls = []

    def broken(jobs, **kw):
        hits = real(jobs, **kw)
        calls.append(len(hits))
        if len(calls) == 1:         # the warm-up pass is left whole
            return hits
        first: dict = {}
        for k, (doc, (_kind, _key, v)) in enumerate(hits):
            key = (v.pkg_name, v.installed_version,
                   v.vulnerability_id)
            if first.setdefault(key, doc) != doc:
                return hits[:k] + hits[k + 1:]
        raise AssertionError("no purl shared by two documents")

    monkeypatch.setattr(batch, "dispatch_jobs", broken)
    out = one_run(SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["reports_mismatched"][0] > 0
    assert out["compared"]["slots_not_ok"] == [0, 0]
    assert repeats(out)[2] >= 1


def test_fault_memo_answers_with_its_neighbours_parse(monkeypatch):
    """A purl memo whose every seventh hit comes back as the parse
    of the key asked before it: documents report packages they do
    not hold."""
    from trivy_tpu import purl
    from trivy_tpu.detect.ccache import KeyedMemo

    class Neighbourly(KeyedMemo):
        hits, last = 0, None

        def lookup(self, key, factory):
            value = super().lookup(key, factory)
            if self.last is not None and key in self._gens[0]:
                self.hits += 1
                if self.hits % 7 == 0:
                    value = self.last
            self.last = value
            return value

    monkeypatch.setattr(purl, "_parse_memo", Neighbourly(
        65536, "purl_cache_hits", "purl_cache_misses"))
    out = one_run(SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["reports_mismatched"][0] > 0
