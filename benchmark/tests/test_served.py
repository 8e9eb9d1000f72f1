"""``served-over-knee`` at test size on the CPU, in process
(``require_chip=False``): a server built by the program's
``build_server``, two client processes, an open loop of 20 builds a
second. ``--rehearsal`` cannot run this cell (``tests/tiny.json`` has
no entry for ``open_loop_served`` and is not this PR's to edit), so
the sizes and the traffic are overridden here, as ``test_zipf.py``
does.

* the cell gives a well-formed result, traced and not, on two seeds,
  with every per-layer metric that a CPU run can read;
* the control in the program's place is not correct;
* two faults planted in the server underneath a whole run come out
  not correct: a blob dropped between ``PutBlob`` and ``Scan``, and a
  shed request answered with an empty report in a 503's place;
* the schedule never waits for a completion: with every Scan stalled
  for a second the arrivals still leave on time;
* ``gen_served`` gives every build of a base the same base layers
  and arrival times of the stated mean and burstiness.
"""

import json
import os
import re
import time

import numpy as np
import pytest

import run as bench_run
from conftest import ROOT

SEEDS = (2147483777, 11)
SIZES = {"os_universe": 600, "ghsa_pkgs": 800, "os_pkgs": 16,
         "pip_pkgs": 8}
TRAFFIC = {"rate_per_s": 20.0, "clients": 2, "warmup": 2, "bases": 3,
           "schedule_factor": 3.0}
CELL = "served-over-knee"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# what only a device trace gives
TRACE_ONLY = {"interval.device_ms_per_image.served",
              "interval_roofline.served", "device_idle_share.fleet"}


def one_run(seed, control=False, trace=False, seconds=1.5):
    cell = bench_run.load_cell(CELL)
    cell["config"]["sizes"].update(SIZES)
    cell["traffic"].update(TRAFFIC)
    lines = []
    run = bench_run.Cell(cell, seed, seconds, trace=trace,
                         require_chip=False, control=control)
    run.say = lambda *parts: lines.append(" ".join(map(str, parts)))
    # the record's ``lines`` go out through run.py's own ``say``
    said_, bench_run.say = bench_run.say, run.say
    try:
        out = run.run()
    finally:
        bench_run.say = said_
    out["lines"], out["stats"] = lines, run.stats
    return out


def said(line, start) -> str:
    got = [ln for ln in line["lines"] if ln.startswith(start)]
    assert len(got) == 1, (start, line["lines"])
    return got[0]


def numbers(text: str) -> list:
    """The numbers that stand alone in a line of the mode's."""
    return [float(x) for x in text.split(":", 1)[1].split()
            if re.fullmatch(r"[\d.]+", x)]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_gives_a_well_formed_result(trace, seed):
    line = one_run(seed, trace=trace)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 10 and line["failed"] == 0
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
    assert values["compile.fresh_in_window.served"] == 0.0
    assert values["rpc.shed_share"] == 0.0
    assert values["rpc.kb_out_per_image"] > values[
        "rpc.kb_in_per_image"] > 0.5
    # three bases' layers are missing once (or twice, where two
    # builds met a base together), every build's own always
    assert 50.0 < values["cache.layer_hit_share.served"] < 66.7
    assert values["memo.query_hit_share.served"] > 40.0
    assert values["rpc.scan_wait_ms_per_image"] > values[
        "sched.queue_wait_ms_per_image.served"]
    for name in ("rpc.decode_ms_per_image",
                 "rpc.put_blob_ms_per_image",
                 "rpc.encode_ms_per_image",
                 "interval.pack_ms_per_image.served",
                 "interval.rows_per_wave.served",
                 "sched.images_per_batch"):
        assert values[name] > 0.0, name


def test_the_window_is_an_open_loop_over_the_wire():
    line = one_run(SEEDS[0])
    rpc, units = line["stats"]["rpc"], line["stats"]["harness"]["units"]
    # nothing reaches the scheduler except through the RPC
    assert line["stats"]["counters"]["submitted"] \
        == rpc["requests"]["Scan"] >= units
    assert rpc["requests"]["MissingBlobs"] == rpc["requests"]["Scan"]
    sent, window = re.match(r"arrivals: (\d+) sent in ([\d.]+) s",
                            said(line, "arrivals:")).groups()
    assert int(sent) >= units
    late = numbers(said(line, "generator lateness"))
    assert len(late) == 3 and late[1] < 50.0      # p99, in ms
    assert "p50" in said(line, "client latency")


def test_control_is_not_correct():
    line = one_run(SEEDS[1], control=True)
    assert line["correct"] is False
    n, limit = line["compared"]["reports_mismatched"]
    # every build but the first of each base carries another's pins
    assert n >= line["compared"]["reports_compared"][0] \
        - TRAFFIC["bases"] and limit == 0


def test_fault_blob_dropped_between_put_and_scan(monkeypatch):
    """Every fifth ``PutBlob`` is acknowledged and not kept."""
    from trivy_tpu.rpc.server import ScanServer
    put, calls = ScanServer.put_blob, [0]

    def lossy(self, body):
        calls[0] += 1
        return {} if calls[0] % 5 == 0 else put(self, body)

    monkeypatch.setattr(ScanServer, "put_blob", lossy)
    monkeypatch.setitem(ScanServer.ROUTES, next(
        k for k in ScanServer.ROUTES if k.endswith("PutBlob")),
        lossy)
    line = one_run(SEEDS[0])
    assert calls[0] >= 10
    assert line["correct"] is False
    assert line["compared"]["reports_mismatched"][0] \
        + line["compared"]["slots_not_ok"][0] > 0


def test_fault_shed_answered_as_an_empty_report(monkeypatch):
    """Every seventh Scan is shed, and answered 200 with no result
    where a 503 belongs."""
    from trivy_tpu.rpc.server import ScanServer
    scan, calls = ScanServer._scan_scheduled, [0]

    def empty(self, target, options, body):
        calls[0] += 1
        if calls[0] % 7 == 0:
            return {"os": None, "results": []}
        return scan(self, target, options, body)

    monkeypatch.setattr(ScanServer, "_scan_scheduled", empty)
    line = one_run(SEEDS[0])
    assert calls[0] >= 14
    assert line["correct"] is False
    assert line["compared"]["reports_mismatched"][0] >= 1
    # the answers' own count of findings picked them out
    assert "of another count" in said(line, "reports:")


def test_arrivals_do_not_wait_for_completions(monkeypatch):
    """Every Scan stalls for a second, so no build is answered
    before the window's last arrival: a loop that waited would send
    its first few builds and stop."""
    import gen_served
    from trivy_tpu.rpc.server import ScanServer
    scan = ScanServer._scan_scheduled

    def stalled(self, target, options, body):
        time.sleep(1.0)
        return scan(self, target, options, body)

    monkeypatch.setattr(ScanServer, "_scan_scheduled", stalled)
    line = one_run(SEEDS[0], seconds=1.0)
    assert line["correct"] is True, line["compared"]
    sent, window = re.match(r"arrivals: (\d+) sent in ([\d.]+) s",
                            said(line, "arrivals:")).groups()
    cell = bench_run.load_cell(CELL)
    cell["traffic"].update(TRAFFIC)
    plan = gen_served.plan(cell["traffic"], 200, SEEDS[0])
    due = sum(at <= float(window) for at in plan["at"])
    assert due >= 15 and abs(int(sent) - due) <= 1
    late = numbers(said(line, "generator lateness"))
    assert late[2] < 100.0                      # the worst, in ms
    # answered a second and more after they were sent
    took = numbers(said(line, "client latency"))
    assert took[0] >= 1000.0


def test_gen_served_shares_bases_and_keeps_the_stated_arrivals():
    import gen_served
    traffic = {"bases": 12, "base_zipf_s": 1.0, "rate_per_s": 40.0,
               "arrival_gamma_shape": 0.5}
    plan = gen_served.plan(traffic, 4000, SEEDS[0])
    again = gen_served.plan(traffic, 4000, SEEDS[1])
    assert plan["base_of"] == again["base_of"]      # no draw
    assert plan["at"] != again["at"]                # the seed's
    assert sum(plan["first"]) == 12
    share = plan["base_of"].count(0) / 4000
    assert abs(share - 1 / sum(1 / k for k in range(1, 13))) < 0.01
    gaps = np.diff([0.0] + plan["at"])
    assert abs(gaps.mean() * 40.0 - 1.0) < 0.1
    assert abs(gaps.std() / gaps.mean() - 2 ** 0.5) < 0.15
    sizes = dict(SIZES, base_layers=2)
    a = gen_served.build_base(sizes, 1, SEEDS[0])
    b = gen_served.build_base(sizes, 1, SEEDS[0])
    assert a["diff_ids"] == b["diff_ids"] and len(a["blobs"]) == 2
    assert gen_served.build_base(sizes, 2, SEEDS[0])["diff_ids"] \
        != a["diff_ids"]
    assert len(a["os_pkgs"]) == SIZES["os_pkgs"] // 2
