"""``secret-tree`` at test size on the CPU, in process
(``require_chip=False``). ``--rehearsal`` cannot run this cell:
``tests/tiny.json`` has no entry for ``closed_loop_tree`` and is not
this PR's to edit, so the sizes and the traffic are overridden here,
as ``test_shared_base.py`` does. The program's part is cut to 64 rows
(``PART_ROWS``) and the generator's with it, so a tree of a few
hundred files is a dozen parts and one file that rides alone.

* the cell gives a well-formed result, traced and not, with every
  per-layer metric that a CPU run can read;
* what ``gen_tree`` writes holds what its facts say, by the exact
  host engine: the planted secrets and nothing else;
* its control (the traps reported, the secrets at the parts' cuts
  lost) in the program's place comes out not correct, on three seeds;
* two faults planted in the program underneath a whole run come out
  not correct: a part's findings dropped at ``collect``, and the
  file that closes a part never scanned.
"""

import json
import os

import pytest

import run as bench_run
from conftest import ROOT

SEEDS = (2147483777, 11, 4096)
SIZES = {"files": 240, "services": 4, "pip_pkgs": 8,
         "os_universe": 60, "ghsa_pkgs": 80, "part_rows": 64,
         "planted": 24, "planted_segment_end": 4,
         "planted_boundary": 3, "skipped_file_bytes": 256,
         "file_size_quantiles": [[0.0, 10], [0.5, 1500],
                                 [0.9, 9000], [0.99, 40000],
                                 [0.995, 150000], [1.0, 400000]]}
TRAFFIC = {"pool": 2, "warmup": 1, "warmup_bytes": 600000}
CELL = "secret-tree"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# what only a device trace gives
TRACE_ONLY = {"sieve.device_ms_per_gb", "sieve.full_device_ms_per_gb",
              "sieve_roofline.tree", "device_idle_share.fleet"}


@pytest.fixture(autouse=True)
def small_parts(monkeypatch):
    from trivy_tpu.secret import batch
    monkeypatch.setattr(batch, "PART_ROWS", SIZES["part_rows"])


def one_run(seed, control=False, trace=False, seconds=1.0):
    cell = bench_run.load_cell(CELL)
    cell["config"]["sizes"].update(SIZES)
    cell["traffic"].update(TRAFFIC)
    return bench_run.Cell(cell, seed, seconds, trace=trace,
                          require_chip=False, control=control).run()


@pytest.mark.parametrize("trace", [False, True])
def test_cell_gives_a_well_formed_result(trace):
    line = one_run(SEEDS[0], trace=trace)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    listed = {m["name"]: m for m in
              BENCH["per_layer" if trace else "end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    for name, m in line["metrics"].items():
        assert m["unit"] == listed[name]["unit"]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
        return
    moved = {n for n, m in listed.items()
             if m["moves"] == "images_per_s"}
    assert set(line["metrics"]) >= moved - TRACE_ONLY
    values = {n: m["value"] for n, m in line["metrics"].items()}
    assert values.pop("compile.fresh_in_window.tree") >= 0.0
    assert values.pop("sieve.full_fetch_share.tree") >= 0.0
    assert all(v > 0 for v in values.values()), values
    assert values["sched.images_per_batch"] == 1.0
    assert 0.0 < values["tree.gated_file_share"] < 100.0


@pytest.mark.parametrize("seed", SEEDS)
def test_a_tree_holds_what_its_facts_say(seed, tmp_path):
    """The generator against the exact host engine, a file at a
    time: the planted secrets, on their lines, and nothing else; no
    decoy is a finding; the traps are tokens the engine would find."""
    import gen_tree
    from trivy_tpu.secret.scanner import new_scanner
    sz = dict(bench_run.load_cell(CELL)["config"]["sizes"], **SIZES)
    (facts,) = gen_tree.build_trees(sz, [0], str(tmp_path), seed)
    engine = new_scanner()
    got = set()
    cands = gen_tree.candidates(facts["path"])
    for rel, _size in cands:
        with open(os.path.join(facts["path"], rel), "rb") as f:
            for finding in engine.scan(rel, f.read()).findings:
                got.add((rel, finding.rule_id, finding.start_line))
    assert got == {tuple(s) for s in facts["secrets"]}
    assert len(got) == sz["planted"]
    assert len(facts["boundary"]) >= sz["planted_boundary"]
    assert facts["parts"] > 8 and facts["oversize"] == 1
    assert facts["candidate_files"] == len(cands)
    for rel, rule, line in facts["traps"]:
        assert rel not in {c[0] for c in cands}
        with open(os.path.join(facts["path"], rel), "rb") as f:
            hits = engine.scan(rel, f.read().replace(b"\x00", b" "))
        assert (rule, line) in {(x.rule_id, x.start_line)
                                for x in hits.findings}


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct_and_control_is_not(seed):
    good = one_run(seed)
    assert good["correct"] is True, good["compared"]
    assert good["compared"]["reports_mismatched"] == [0, 0]
    assert good["compared"]["off_device_events"] == [0, 0]
    bad = one_run(seed, control=True)
    assert bad["correct"] is False
    c = bad["compared"]
    assert c["reports_mismatched"][0] == c["reports_compared"][0]


def test_fault_a_parts_findings_dropped_at_collect(monkeypatch):
    from trivy_tpu.secret.batch import BatchSecretScanner
    real = BatchSecretScanner.collect
    calls = []

    def collect(self, handle):
        calls.append(1)
        found = real(self, handle)
        return [] if len(calls) % 5 == 0 else found

    monkeypatch.setattr(BatchSecretScanner, "collect", collect)
    out = one_run(SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["reports_mismatched"][0] > 0
    assert out["compared"]["slots_not_ok"] == [0, 0]


def test_fault_the_file_that_closes_a_part_is_never_scanned(
        monkeypatch):
    from trivy_tpu.secret.batch import PartCutter
    real = PartCutter.add

    def add(self, path, content):
        closed = real(self, path, content)
        return closed[:-1] if closed and len(closed) > 1 else closed

    monkeypatch.setattr(PartCutter, "add", add)
    out = one_run(SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["reports_mismatched"][0] > 0
    assert out["compared"]["slots_not_ok"] == [0, 0]
