"""Runtime lock-order witness tests (``pytest -m lint``,
docs/static-analysis.md "Witness").

Covers: the pure cycle detector property-tested on seeded random
lock-acquisition schedules (cycle planted ⇒ always raised, DAG
schedules ⇒ never raised), the instrumented-lock wrapper (opposite-
order nesting raises, reentrant RLocks book once, Condition wait/
notify works through the wrapper), host-pool self-join detection
(the PR-5 class raises instead of deadlocking), install/uninstall
hygiene, and the profiler exclude-list — the ~49Hz tick path pays
zero witness bookkeeping.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from trivy_tpu.analysis.witness import (LockOrderViolation,
                                        LockWitness, OrderGraph,
                                        PoolSelfJoinError,
                                        _WitnessLock,
                                        active_witness,
                                        install_witness,
                                        uninstall_witness)

pytestmark = pytest.mark.lint


# ---------------------------------------------------------------
# the pure cycle detector
# ---------------------------------------------------------------

class TestOrderGraphProperties:
    def test_dag_schedules_never_raise(self):
        """Seeded random schedules that always acquire locks in
        ascending global order form a DAG — the detector must
        never report a cycle."""
        rng = np.random.default_rng(20260804)
        for _ in range(100):
            g = OrderGraph()
            n_locks = int(rng.integers(3, 12))
            for _step in range(int(rng.integers(5, 40))):
                depth = int(rng.integers(2, min(5, n_locks) + 1))
                picks = sorted(rng.choice(n_locks, size=depth,
                                          replace=False))
                held: list = []
                for lk in picks:
                    for h in held:
                        assert g.add_edge(f"L{h}", f"L{lk}") \
                            is None
                    held.append(lk)

    def test_planted_cycle_always_raised(self):
        """Build a random DAG, then reverse one reachable pair:
        the closing edge must be reported, every time."""
        rng = np.random.default_rng(7)
        found = 0
        for _ in range(100):
            g = OrderGraph()
            n = int(rng.integers(4, 10))
            edges = set()
            for _e in range(int(rng.integers(n, 3 * n))):
                a, b = rng.integers(0, n, 2)
                if a < b:
                    g.add_edge(f"L{a}", f"L{b}")
                    edges.add((int(a), int(b)))
            if not edges:
                continue
            a, b = sorted(edges)[int(rng.integers(0, len(edges)))]
            cycle = g.add_edge(f"L{b}", f"L{a}")
            assert cycle is not None
            assert cycle[0] == f"L{b}"
            found += 1
        assert found > 50      # the property actually exercised

    def test_repeated_inversion_keeps_reporting(self):
        """A cycle-closing edge is not recorded: the same
        inversion re-detected later must report again (a first
        raise swallowed by a broad except seam must not silence
        the witness for the rest of the process)."""
        g = OrderGraph()
        assert g.add_edge("A", "B") is None
        assert g.add_edge("B", "A") is not None
        assert g.add_edge("B", "A") is not None

    def test_duplicate_edges_are_free(self):
        g = OrderGraph()
        assert g.add_edge("A", "B") is None
        assert g.add_edge("A", "B") is None
        assert g.edges() == [("A", "B")]

    def test_self_edge_ignored(self):
        g = OrderGraph()
        assert g.add_edge("A", "A") is None
        assert g.edges() == []

    def test_long_cycle_detected(self):
        g = OrderGraph()
        for i in range(6):
            assert g.add_edge(f"L{i}", f"L{i + 1}") is None
        cycle = g.add_edge("L6", "L0")
        assert cycle is not None and len(cycle) == 8


# ---------------------------------------------------------------
# the instrumented lock
# ---------------------------------------------------------------

def _wlock(witness, name):
    return _WitnessLock(threading.Lock(), name, witness)


class TestWitnessLock:
    def setup_method(self):
        self.w = install_witness()

    def teardown_method(self):
        uninstall_witness()

    def test_opposite_order_raises(self):
        a, b = _wlock(self.w, "site:A"), _wlock(self.w, "site:B")
        with a:
            with b:
                pass
        with b:
            with pytest.raises(LockOrderViolation) as ei:
                with a:
                    pass
        assert "site:A" in str(ei.value)
        assert "site:B" in str(ei.value)
        # the failed acquire must not leave the lock held
        assert not a._inner.locked()

    def test_consistent_order_never_raises(self):
        a, b = _wlock(self.w, "site:A"), _wlock(self.w, "site:B")
        for _ in range(10):
            with a:
                with b:
                    pass
        assert self.w.stats()["violations"] == 0

    def test_same_site_instances_do_not_self_cycle(self):
        """Two locks from the same creation site (two instances of
        one class) may nest — lockdep's class-level self edge is
        deliberately not an error here."""
        a1 = _wlock(self.w, "site:same")
        a2 = _wlock(self.w, "site:same")
        with a1:
            with a2:
                pass
        assert self.w.stats()["violations"] == 0

    def test_reentrant_rlock_books_once(self):
        r = _WitnessLock(threading.RLock(), "site:R", self.w)
        g = _wlock(self.w, "site:G")
        with g:
            with r:
                with r:      # re-entry: no second edge/acquisition
                    pass
        assert ("site:G", "site:R") in self.w.graph.edge_set
        assert ("site:R", "site:R") not in self.w.graph.edge_set

    def test_condition_wait_notify_through_wrapper(self):
        """threading.Condition accepts the wrapper (the Condition
        protocol is delegated); wait releases the witnessed lock
        and reacquires it."""
        cv = threading.Condition(
            _WitnessLock(threading.RLock(), "site:CV", self.w))
        hits = []

        def waiter():
            with cv:
                cv.wait(timeout=5)
                hits.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            cv.notify_all()
        t.join(5)
        assert hits == ["woke"]

    def test_uninstalled_wrappers_go_inert(self):
        a, b = _wlock(self.w, "site:A"), _wlock(self.w, "site:B")
        with a:
            with b:
                pass
        uninstall_witness()
        # opposite order now: a dead witness must not raise
        with b:
            with a:
                pass
        # re-install for teardown symmetry
        install_witness()


class TestInstallUninstall:
    def test_factories_restored(self):
        real_lock = threading.Lock
        install_witness()
        try:
            assert threading.Lock is not real_lock
        finally:
            uninstall_witness()
        assert threading.Lock is real_lock
        assert active_witness() is None

    def test_install_is_idempotent(self):
        w1 = install_witness()
        try:
            assert install_witness() is w1
        finally:
            uninstall_witness()

    def test_only_trivy_tpu_constructions_wrapped(self):
        install_witness()
        try:
            # this module is not under the trivy_tpu prefix: its
            # Lock() calls get the real thing
            lk = threading.Lock()
            assert not isinstance(lk, _WitnessLock)
            # a trivy_tpu module constructing a lock gets wrapped
            from trivy_tpu.runtime.ring import RingMetrics
            rm = RingMetrics()
            assert isinstance(rm._lock, _WitnessLock)
        finally:
            uninstall_witness()


# ---------------------------------------------------------------
# host-pool self-join detection (the PR-5 class, dynamically)
# ---------------------------------------------------------------

class TestPoolSelfJoin:
    @pytest.fixture
    def fresh_pool(self, monkeypatch):
        from trivy_tpu.runtime import hostpool
        monkeypatch.setenv("TRIVY_TPU_HOST_POOL", "2")
        old = hostpool._POOL
        hostpool._POOL = None
        yield hostpool
        pool = hostpool._POOL
        hostpool._POOL = old
        if pool is not None:
            pool.shutdown(wait=True)

    def test_self_join_raises_instead_of_deadlocking(
            self, fresh_pool, lock_witness):
        pool = fresh_pool.get_host_pool()
        assert pool is not None

        def task():
            # the PR-5 shape: a pool task joining its own pool
            return pool.submit(str, 1).result()

        fut = pool.submit(task)
        with pytest.raises(PoolSelfJoinError):
            fut.result(timeout=10)
        assert lock_witness.stats()["pool_joins_checked"] >= 1

    def test_main_thread_joins_freely(self, fresh_pool,
                                      lock_witness):
        pool = fresh_pool.get_host_pool()
        assert pool.submit(str, 7).result(timeout=10) == "7"

    def test_thread_name_guard_still_safe(self, fresh_pool,
                                          lock_witness):
        """A task that runs its inner work inline on a pool thread
        (the PR-5 fix, the guard ``hostpool-blocking`` looks for)
        and joins the pool only from elsewhere: the witness must not
        misfire on it."""
        pool = fresh_pool.get_host_pool()
        digits = list("123456789" * 2)

        def task(_):
            if threading.current_thread().name.startswith(
                    "trivy-hostpool"):
                return sum(int(c) for c in digits)
            return sum(f.result(timeout=10) for f in
                       [pool.submit(int, c) for c in digits])

        futs = [pool.submit(task, i) for i in range(12)]
        out = [f.result(timeout=10) for f in futs] + [task(12)]
        assert out == [sum(int(c) for c in digits)] * 13


# ---------------------------------------------------------------
# profiler exclusion: the ~49Hz tick path pays nothing
# ---------------------------------------------------------------

class TestProfilerExclusion:
    def test_profiler_lock_not_wrapped(self, lock_witness):
        from trivy_tpu.obs.profiler import HostProfiler
        prof = HostProfiler()
        assert not isinstance(prof._lock, _WitnessLock)

    def test_tick_path_books_zero_witness_work(self, lock_witness):
        """Drive the sampler directly under an installed witness:
        the witness acquisition counter must not move — the tick
        path is exclude-listed by module."""
        from trivy_tpu.obs.profiler import HostProfiler
        prof = HostProfiler()
        before = lock_witness.stats()["acquisitions"]
        for _ in range(50):
            prof.sample_once()
        assert lock_witness.stats()["acquisitions"] == before
        assert prof.ticks == 50

    def test_sampler_cadence_unchanged_under_env_witness(
            self, lock_witness):
        """Live cadence proof: the sampler keeps its tick rate
        with the witness installed (coarse floor — the point is
        no per-tick witness stall, not exact Hz)."""
        from trivy_tpu.obs.profiler import HostProfiler
        prof = HostProfiler(hz=49.0, ring_seconds=30)
        prof.start()
        try:
            time.sleep(0.5)
        finally:
            prof.stop()
        # 49 Hz over 0.5s ≈ 24 ticks; a witness-stalled sampler
        # (or a wrapped tick lock) lands far below the floor
        assert prof.ticks >= 10
        assert prof.stats()["overhead_s"] < 0.25


# ---------------------------------------------------------------
# end-to-end: a seeded storm books real edges, no violations
# ---------------------------------------------------------------

class TestWitnessStorm:
    def test_scheduler_storm_clean_under_witness(self,
                                                 lock_witness):
        """A concurrent submit storm against a fresh scheduler:
        locks get wrapped, acquisitions book, and no cycle or
        self-join fires (the acceptance wiring the three race
        suites also run under)."""
        from trivy_tpu.sched import SchedConfig
        from trivy_tpu.sched.queue import (AnalyzedWork,
                                           ScanRequest)
        from trivy_tpu.sched.scheduler import ScanScheduler

        sched = ScanScheduler(config=SchedConfig(
            workers=2, flush_timeout_s=0.005, max_queue=64))
        errors: list = []

        def one(i):
            try:
                req = sched.submit(ScanRequest(
                    f"r{i}", lambda req: AnalyzedWork(
                        finish=lambda f, d: "x")))
                req.result(timeout=20)
            except Exception as e:  # noqa: BLE001 — collected
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        sched.close()
        assert errors == []
        st = lock_witness.stats()
        assert st["wrapped_locks"] > 0
        assert st["acquisitions"] > 0
        assert st["violations"] == 0
