"""``k8s-sweep`` at test size on the CPU, in process
(``require_chip=False``): a cluster of 3 namespaces (10 workloads, 17
image refs, 6 images) swept by the runner ``trivy_tpu.k8s.build_runner``
builds, on a mesh of four virtual devices where this process can still
ask for them (run alone, it can), else on one. ``--rehearsal`` cannot
run this cell (``tests/tiny.json`` has no entry for ``k8s_sweep``), so
the sizes, the cluster and the cell's chips are overridden here, as
``test_served.py`` does.

* the cell gives a well-formed, correct result against
  ``reference_k8s.py``, traced and not, on two seeds;
* the control in the program's place is not correct;
* two faults planted in the sweep's report come out not correct: an
  image's entry answered with another image's findings, and a
  workload whose sidecar ref is dropped from the report;
* the cluster's plan is the configuration's: 484 workloads, 844 image
  refs, 160 distinct images.
"""

import json
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

import run as bench_run  # noqa: E402
from conftest import BENCH, ROOT  # noqa: E402

SEEDS = (2147483777, 11)
SIZES = {"files": 24, "os_pkgs": 16, "pip_pkgs": 8,
         "os_universe": 60, "ghsa_pkgs": 80}
CLUSTER = {"namespaces": 3, "kinds": [["Deployment", 2], ["CronJob", 1]],
           "daemonsets": ["node-exporter"], "app_images": 3,
           "sidecar_every": 2, "init_every": 4, "init_images": 1,
           "zipf_s": 1.0}
CELL = "k8s-sweep"
TRACE_ONLY = {"sieve.device_ms_per_image.k8s",
              "interval.device_ms_per_image.k8s", "sieve_roofline.k8s",
              "interval_roofline.k8s", "device_idle_share.fleet"}


def one_run(seed, control=False, trace=False, seconds=1.0):
    import jax
    cell = bench_run.load_cell(CELL)
    cell["config"]["sizes"].update(SIZES)
    cell["config"]["cluster"] = dict(CLUSTER)
    cell["traffic"]["warmup"] = 2
    cell["chips"] = min(4, len(jax.devices()))
    run = bench_run.Cell(cell, seed, seconds, trace=trace,
                         require_chip=False, control=control)
    lines = []
    run.say = lambda *parts: lines.append(" ".join(map(str, parts)))
    # run.py's own lines (what differed) go out through its ``say``
    said, bench_run.say = bench_run.say, run.say
    try:
        out = run.run()
    finally:
        bench_run.say = said
    out["lines"], out["stats"] = lines, run.stats
    return out


@pytest.fixture
def plant(monkeypatch):
    from modes import k8s_sweep

    def set_(fn):
        monkeypatch.setattr(k8s_sweep, "PLANT", fn)
    return set_


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_gives_a_well_formed_result(trace, seed):
    line = one_run(seed, trace=trace)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 6
    assert line["attempted"] % 6 == 0          # whole sweeps
    # the last sweep's 10 resources rendered and compared
    assert line["compared"]["reports_compared"][0] == 10
    assert line["compared"]["off_device_events"] == [0, 0]
    if not trace:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
        return
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])
            and m["moves"] == "images_per_s"} - TRACE_ONLY
    assert want <= set(line["metrics"]), \
        sorted(want - set(line["metrics"]))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["k8s.refs_per_image"] == 17 / 6
    # (read here; 0 is for a chip, where the runner warms the
    # ladders before the first sweep and the CPU's does not)
    assert m["compile.fresh_in_window.k8s"] >= 0
    assert m["k8s.enumerate_ms_per_image"] > 0
    assert m["k8s.report_ms_per_image"] > 0
    import jax
    if len(jax.devices()) >= 4:
        assert 1.0 <= m["mesh.shard_balance.k8s"] < 4.0


def test_control_is_not_correct():
    line = one_run(SEEDS[0], control=True)
    assert line["correct"] is False
    assert line["compared"]["reports_mismatched"][0] > 0


def test_an_answer_from_another_image_is_not_correct(plant):
    def swap(report, data):
        # the first entry given the findings of another image's
        ents = report.vulnerabilities
        other = next(r for r in ents
                     if r.results is not ents[0].results)
        ents[0].results = other.results
    plant(swap)
    line = one_run(SEEDS[0])
    assert line["correct"] is False
    assert line["compared"]["reports_mismatched"][0] >= 1


def test_a_dropped_sidecar_ref_is_not_correct(plant):
    from gen_k8s import REGISTRY
    from trivy_tpu.k8s import ManifestClient

    def drop(report, data):
        # the report's entries are the workloads' image refs in the
        # order the manifests name them: drop the first sidecar's
        refs = [ref for a in ManifestClient(data["cluster"]).artifacts()
                for ref in a.images]
        at = refs.index(f"{REGISTRY}/mesh/proxy:1.27")
        del report.vulnerabilities[at]
    plant(drop)
    line = one_run(SEEDS[0])
    assert line["correct"] is False
    assert line["compared"]["slots_not_ok"][0] >= 1
    assert any("image entries" in ln for ln in line["lines"])


def test_the_plan_is_the_configurations():
    import gen_k8s
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "k8s-cluster-4chip.json")))
    p = gen_k8s.plan(cfg["cluster"])
    refs = [r for w in p["workloads"]
            for r in w["init"] + w["containers"]]
    assert len(p["workloads"]) == 484 and len(refs) == 844
    assert set(refs) == set(p["refs"]) and len(p["refs"]) == 160
    apps = [w["containers"][0] for w in p["workloads"]
            if w["namespace"] != "kube-system"]
    assert len(set(apps)) == 153
    # a Zipf head: the most deployed app image runs in many tenants
    top = max(set(apps), key=apps.count)
    assert len({w["namespace"] for w in p["workloads"]
                if w["containers"][0] == top}) >= 12
