"""``correct`` has been shown to fail. In process, with the harness's
look for a chip skipped (``require_chip=False``) and sizes a test run
can hold:

* the control (``reference`` with versions compared without their
  last field and no overlap between sieve segments) in the program's
  place comes out not correct, in every cell, on three seeds;
* the rest of a run with the timed path broken underneath comes out
  not correct, once for each fault these cells can have: an answer
  altered where it is produced (the interval kernel's hits, the sieve's
  findings), a slot that did not come back ok, and work finished on
  the host under the device's name (half or all of the table's rows on
  the host path; a device dispatch that fails and is bisected).

On the chip at the cells' own sizes the control reads far higher
(PERF.md section 2); the seeds here are ones on which these small
sizes show it too.
"""

import pytest

import run as bench_run

SEEDS = (2147483777, 11, 4096)
# between tiny and full: enough packages an image and documents a
# pass for the control's narrower versions to change an answer
SIZES = {"files": 24, "os_pkgs": 240,
         "pip_pkgs": 80, "os_universe": 600, "ghsa_pkgs": 800,
         "comps": 20}
TRAFFIC = {"closed_loop": {"in_flight": 3, "pool": 6, "warmup": 2},
           "batch_pass": {"batch": 100, "pool": 300, "warmup": 100,
                          "check_per_pass": 50}}
CELLS = ("fleet-secrets", "sbom-batch")


def one_run(name, seed, control=False, seconds=1.0):
    cell = bench_run.load_cell(name)
    cell["config"]["sizes"].update(SIZES)
    cell["traffic"].update(TRAFFIC[cell["traffic"]["mode"]])
    return bench_run.Cell(cell, seed, seconds, trace=False,
                          require_chip=False, control=control).run()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct_and_control_is_not(name, seed):
    good = one_run(name, seed)
    assert good["correct"] is True, good["compared"]
    assert good["compared"]["reports_mismatched"] == [0, 0]
    bad = one_run(name, seed, control=True)
    assert bad["correct"] is False
    assert bad["compared"]["reports_mismatched"][0] > 0


def test_fault_interval_hits_altered(monkeypatch):
    """An answer altered where it is produced: the resident interval
    kernel's first hit of every wave is flipped."""
    import numpy as np
    from trivy_tpu.ops import intervals
    real = intervals.interval_hits_resident_donated

    def broken(*args):
        hits = np.array(real(*args))
        hits[0] = ~hits[0]
        return hits

    monkeypatch.setattr(intervals, "interval_hits_resident_donated",
                        broken)
    for name in CELLS:
        out = one_run(name, SEEDS[0])
        assert out["correct"] is False, name
        assert out["compared"]["reports_mismatched"][0] > 0


def test_fault_secret_dropped(monkeypatch):
    """A finding dropped where it is produced: the batch secret
    scanner loses the last file's findings."""
    from trivy_tpu.secret.batch import BatchSecretScanner
    real = BatchSecretScanner.collect

    def broken(self, handle):
        found = list(real(self, handle))
        return found[:-1]

    monkeypatch.setattr(BatchSecretScanner, "collect", broken)
    out = one_run("fleet-secrets", SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["reports_mismatched"][0] > 0


def test_fault_slot_not_ok(monkeypatch):
    """A slot that comes back degraded is not ok, whatever it found."""
    from trivy_tpu.runtime.batch import BatchScanRunner
    real = BatchScanRunner.scan_boms
    calls = []

    def broken(self, boms, options=None):
        out = real(self, boms, options)
        calls.append(len(boms))
        if len(calls) > 1:          # the warm-up pass is left whole
            out[-1].status = "degraded"
        return out

    monkeypatch.setattr(BatchScanRunner, "scan_boms", broken)
    out = one_run("sbom-batch", SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["slots_not_ok"][0] > 0
    assert out["failed"] > 0


@pytest.mark.parametrize("every", [2, 1])
@pytest.mark.parametrize("name", CELLS)
def test_fault_rows_on_the_host_path(monkeypatch, name, every):
    """Half (or all) of the table's rows flagged for the host path:
    the program's exact host evaluation gives the right findings, but
    the pairs were not the device's, and the window's device rows
    fall under what the reference expects."""
    from trivy_tpu.db import CompiledDB
    from trivy_tpu.db.compiled import F_HOST
    real = CompiledDB.load

    def flagged(path, *a, **kw):
        cdb = real(path, *a, **kw)
        cdb.flags = cdb.flags.copy()
        cdb.flags[::every] |= F_HOST
        return cdb

    monkeypatch.setattr(CompiledDB, "load", staticmethod(flagged))
    out = one_run(name, SEEDS[0])
    assert out["correct"] is False
    c = out["compared"]
    assert c["reports_mismatched"] == [0, 0]
    assert c["host_fallback_pairs"][0] > 0
    # the closed loop's floor counts a job once however often this
    # small pool wraps, so half the rows can still clear it
    if every == 1 or name == "sbom-batch":
        assert c["device_rows"][0] < c["device_rows"][1]


def test_fault_dispatch_fails_and_is_bisected(monkeypatch):
    """A device dispatch that fails: the scheduler bisects, quarantines
    and finishes the slot on the host, ok and with the right findings;
    ``off_device_events`` shows it."""
    from trivy_tpu.faults import FaultInjector, parse_fault_spec
    from trivy_tpu.runtime.batch import BatchScanRunner
    real = BatchScanRunner.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        self.fault_injector = FaultInjector(
            parse_fault_spec("device_fail_rate=0.5,seed=3"))

    monkeypatch.setattr(BatchScanRunner, "__init__", init)
    out = one_run("fleet-secrets", SEEDS[0])
    assert out["correct"] is False
    assert out["compared"]["off_device_events"][0] > 0
