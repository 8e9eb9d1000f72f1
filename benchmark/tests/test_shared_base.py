"""``fleet-shared-base`` at test size on the CPU, in process
(``require_chip=False``). ``--rehearsal`` cannot run this cell:
``run.py`` takes a mode's test sizes from ``tests/tiny.json``, which
has no entry for ``closed_loop_shared`` and is not this PR's to edit,
so the sizes and the traffic are overridden here, as
``test_correct.py`` does.

* the cell gives a well-formed result, traced and not, with every
  per-layer metric that a CPU run can read;
* its control (``reference_shared``: the base's planted secrets
  reported, a tag's own layer answered from the first tag seen on its
  base) in the program's place comes out not correct, on three seeds;
* two faults planted in the program underneath a whole run come out
  not correct: a blob served under another tag's key, and the
  base-layer rule switched off.
"""

import json
import os

import pytest

import run as bench_run
from conftest import ROOT

SEEDS = (2147483777, 11, 4096)
SIZES = {"files": 24, "os_pkgs": 240, "pip_pkgs": 80,
         "os_universe": 600, "ghsa_pkgs": 800}
TRAFFIC = {"in_flight": 3, "tags": 8, "bases": 3, "schedule": 60,
           "warmup": 2}
CELL = "fleet-shared-base"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# what only a device trace gives
TRACE_ONLY = {"sieve.device_ms_per_image.shared",
              "sieve_roofline.shared", "device_idle_share.fleet"}


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
    assert line["device"]["platform"] == "cpu"
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
    # on the CPU nothing is warmed (runtime.aot.on_accelerator), so
    # a rung met for the first time in the window compiles there
    assert values.pop("compile.fresh_in_window.shared") >= 0.0
    assert all(v > 0 for v in values.values()), values
    assert values["cache.layer_hit_share"] > 50.0
    assert values["memo.query_hit_share"] > 50.0
    assert values["secret.base_layers_skipped_per_image"] >= 2.0


def test_configuration_and_traffic_say_the_same():
    cell = bench_run.load_cell(CELL)
    skew = cell["config"]["skew"]
    for key in ("tags", "bases", "tag_zipf_s", "base_zipf_s",
                "schedule"):
        assert cell["traffic"][key] == skew[key], key
    import gen_shared
    plan = gen_shared.plan(cell["traffic"])
    on_a_base = [plan["base_of"].count(b)
                 for b in range(skew["bases"])]
    assert on_a_base == skew["tags_on_a_base"]
    first = plan["classes"][:600]
    assert (first.count("base_cold"), first.count("tag_new")) == \
        (12, 108)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct_and_control_is_not(seed):
    good = one_run(seed)
    assert good["correct"] is True, good["compared"]
    assert good["compared"]["reports_mismatched"] == [0, 0]
    bad = one_run(seed, control=True)
    assert bad["correct"] is False
    # every report differs: each shows its base's two secrets
    c = bad["compared"]
    assert c["reports_mismatched"][0] == c["reports_compared"][0]


def test_fault_blob_served_under_another_tags_key(monkeypatch):
    """A cache key that is too coarse: a tag's own layer keyed by
    its base's layers alone, so the second tag on a base is served
    the first one's blob."""
    from trivy_tpu.artifact.artifact import ImageArtifact
    real = ImageArtifact.cache_keys

    def coarse(self):
        artifact_id, blob_ids, base = real(self)
        return (artifact_id,
                blob_ids[:-1] + [blob_ids[-2] + "-app"], base)

    monkeypatch.setattr(ImageArtifact, "cache_keys", coarse)
    out = one_run(SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["reports_mismatched"][0] > 0
    assert out["compared"]["slots_not_ok"] == [0, 0]


def test_fault_base_rule_switched_off(monkeypatch):
    """No layer is taken for the base's: everything is sieved and
    the base's planted secrets are reported."""
    from trivy_tpu.artifact import artifact
    monkeypatch.setattr(artifact, "guess_base_layers",
                        lambda diff_ids, config: [])
    out = one_run(SEEDS[0])
    assert out["correct"] is False
    c = out["compared"]
    assert c["reports_mismatched"][0] == c["reports_compared"][0]
