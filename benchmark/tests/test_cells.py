"""The three cells at tiny sizes on the CPU, through the command as
the driver gives it (plus ``--rehearsal``): a well-formed last line.
And the command with no chip: another exit code than 0, no result."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

RUN = [sys.executable, os.path.join("benchmark", "run.py")]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(*args):
    return subprocess.run(RUN + list(args), cwd=ROOT, env=ENV,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_a_well_formed_last_line(cell, trace):
    p = run("--workload", cell, "--seed", "2147483777", "--seconds",
            "2", "--trace", str(trace), "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for k in ("kind", "count", "memory_peak_bytes"):
        assert k in line["device"]
    names = {m["name"]: m for m in
             BENCH["per_layer" if trace else "end_to_end"]}
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert m["unit"] == names[name]["unit"]
        assert isinstance(m["value"], float) and m["value"] > 0
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        # no TPU plane in a CPU trace: a trace reader reads nothing
        assert not any("roofline" in n or "idle" in n
                       for n in line["metrics"])
    else:
        assert "setup_s" in line["metrics"]
    for name, (value, limit) in line["compared"].items():
        assert f"compared {name}: {value} limit {limit}" in p.stderr


def test_no_chip_no_result():
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == "" or \
        not p.stdout.strip().splitlines()[-1].startswith("{")


def test_manifest_checks():
    p = run("--check-manifest")
    assert p.returncode == 0, p.stderr
