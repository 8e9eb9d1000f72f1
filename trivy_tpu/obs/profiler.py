"""Always-on sampling host profiler (docs/observability.md "Host
profiler").

A daemon thread walks ``sys._current_frames()`` at ~49 Hz (a prime
tick, so it cannot phase-lock with 50/100 Hz periodic work) and
folds every thread's stack into a collapsed-stack counter — the
`folded` format flamegraph.pl / speedscope / inferno consume
directly. Samples land in per-second ring buckets, so
``GET /debug/profile?seconds=N`` (token-protected, mirroring
``/trace/<id>``) answers "what was the host doing for the last N
seconds" from a server that never had profiling "switched on".

Overhead is bounded three ways and *measured*: the sampler skips its
own thread, distinct-stack cardinality folds into ``<overflow>``
past ``max_stacks`` per bucket, and the cumulative sampling CPU time
is tracked in ``stats()["overhead_s"]``, so a run can attribute
the profiler's share of its own wall (``pytest -m obs``,
tests/test_obs_profiler.py).

The optional **device** trace rides :func:`device_trace`: an opt-in
``jax.profiler`` hook behind ``--profile-out DIR`` (the host
profiler's folded stacks are dumped next to it as
``host_profile.folded``). Import of jax is deferred; a CPU
platform whose trace cannot start still gets the host profile, a
TPU platform fails instead — the device timeline is the point.

Clock discipline: bucket keys and sample timing are
``time.monotonic``; wall time appears nowhere in the math (lint-
enforced across ``obs/``).
"""

from __future__ import annotations

import os
import sys
import threading
import time

from ..utils import get_logger

log = get_logger("obs.profiler")

DEFAULT_HZ = 49.0
# per-second buckets retained — 15 minutes of history
RING_SECONDS = 900
# distinct folded stacks per bucket before folding to <overflow>
MAX_STACKS = 4096
# frames folded per stack before truncating (deep recursion guard)
MAX_DEPTH = 64


def _fold(frame) -> str:
    """One thread's stack, outermost-first, semicolon-joined:
    ``module.func;module.func;...`` (the collapsed-stack frame
    vocabulary)."""
    parts: list = []
    while frame is not None and len(parts) < MAX_DEPTH:
        code = frame.f_code
        mod = frame.f_globals.get("__name__", "") or \
            os.path.basename(code.co_filename)
        parts.append(f"{mod}.{code.co_name}")
        frame = frame.f_back
    parts.reverse()
    return ";".join(parts)


class HostProfiler:
    """The sampling thread + the per-second folded-stack ring."""

    def __init__(self, hz: float = DEFAULT_HZ,
                 ring_seconds: int = RING_SECONDS,
                 max_stacks: int = MAX_STACKS):
        self.hz = max(1.0, float(hz))
        self.ring_seconds = max(1, int(ring_seconds))
        self.max_stacks = max(16, int(max_stacks))
        self._lock = threading.Lock()
        # bucket second (int monotonic) -> {folded stack: count}
        self._ring: dict = {}
        self._stop = threading.Event()
        self._thread = None
        self.samples = 0
        self.ticks = 0
        self.overhead_s = 0.0      # cumulative sampling CPU time

    # --- lifecycle ---

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> "HostProfiler":
        with self._lock:
            if self.running:
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="trivy-obs-profiler",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 1.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        self._thread = None

    # --- sampling ---

    @staticmethod
    def _next_tick(nxt: float, period: float, now: float) -> float:
        """Fixed-rate schedule (not fixed-sleep): a slow tick doesn't
        compound into a slower sampling rate — but missed ticks are
        DROPPED, never replayed: after a long GIL hold or blocking C
        call the sampler must not fire a zero-wait catch-up burst
        that overweights whatever runs right after the stall."""
        return max(nxt + period, now)

    def _loop(self) -> None:
        period = 1.0 / self.hz
        me = threading.get_ident()
        nxt = time.monotonic()
        while not self._stop.wait(max(0.0, nxt - time.monotonic())):
            nxt = self._next_tick(nxt, period, time.monotonic())
            t0 = time.process_time()
            try:
                self.sample_once(skip_thread=me)
            # lint: disable=bare-except-at-seam -- the ~49Hz tick
            # must never take the host down or pay logging on the
            # hot path; a failed tick self-heals next period
            except Exception:       # noqa: BLE001 — the profiler
                pass                # must never take the host down
            self.overhead_s += time.process_time() - t0

    def sample_once(self, skip_thread=None) -> int:
        """One walk over every live thread's stack; returns the
        number of stacks recorded (tests drive this directly)."""
        frames = sys._current_frames()
        sec = int(time.monotonic())
        n = 0
        with self._lock:
            bucket = self._ring.get(sec)
            if bucket is None:
                bucket = self._ring[sec] = {}
                while len(self._ring) > self.ring_seconds:
                    self._ring.pop(next(iter(self._ring)))
            for tid, frame in frames.items():
                if tid == skip_thread:
                    continue
                stack = _fold(frame)
                if stack not in bucket and \
                        len(bucket) >= self.max_stacks:
                    stack = "<overflow>"
                bucket[stack] = bucket.get(stack, 0) + 1
                n += 1
            self.ticks += 1
            self.samples += n
        return n

    # --- export ---

    def folded(self, seconds=None) -> dict:
        """{folded stack: count} over the last ``seconds`` (whole
        ring when None)."""
        with self._lock:
            if seconds is None:
                keys = list(self._ring)
            else:
                horizon = int(time.monotonic()) - max(
                    0, int(seconds)) + 1
                keys = [k for k in self._ring if k >= horizon]
            out: dict = {}
            for k in keys:
                for stack, c in self._ring[k].items():
                    out[stack] = out.get(stack, 0) + c
            return out

    def collapsed(self, seconds=None) -> str:
        """Collapsed-stack text (``stack count`` per line), heaviest
        first — feed to flamegraph.pl / speedscope as-is."""
        folded = self.folded(seconds)
        lines = [f"{stack} {count}" for stack, count in
                 sorted(folded.items(),
                        key=lambda kv: (-kv[1], kv[0]))]
        return "\n".join(lines) + ("\n" if lines else "")

    def dump(self, path: str, seconds=None) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            f.write(self.collapsed(seconds))
        return path

    def stats(self) -> dict:
        with self._lock:
            return {"running": self.running, "hz": self.hz,
                    "ticks": self.ticks, "samples": self.samples,
                    "buckets": len(self._ring),
                    "overhead_s": round(self.overhead_s, 6)}


_PROFILER = None
_LOCK = threading.Lock()


def get_profiler(start: bool = True) -> HostProfiler:
    """The process-wide profiler (created on first use; started
    unless ``start=False`` or ``TRIVY_TPU_PROFILE=off``)."""
    global _PROFILER
    if _PROFILER is None:
        with _LOCK:
            if _PROFILER is None:
                _PROFILER = HostProfiler()
    if start and os.environ.get("TRIVY_TPU_PROFILE", "") != "off":
        _PROFILER.start()
    return _PROFILER


class _DeviceTraceCtx:
    """Context manager behind :func:`device_trace`: jax.profiler
    around the body, host folded stacks dumped either way.
    ``device=False`` (a process that owns no device) skips the jax
    trace — starting it would initialise a backend. On a TPU
    platform a trace that cannot start is an error, not a quiet
    host-only profile: the device timeline is what the flag was
    given for. ``max_seconds > 0`` bounds the capture: a daemon
    timer closes the trace and writes the artifacts after the
    window, so a long-lived body (the server's ``serve_forever``)
    cannot accumulate an unbounded device trace that only flushes
    at process exit."""

    def __init__(self, out_dir: str, max_seconds: float = 0.0,
                 device: bool = True):
        self.out_dir = out_dir
        self.max_seconds = max_seconds
        self.device = device
        self._jax_trace = None
        self._timer = None
        self._done = threading.Lock()
        self._finished = False

    def __enter__(self):
        if not self.out_dir:
            return self
        os.makedirs(self.out_dir, exist_ok=True)
        get_profiler()
        if self.device:
            self._start_jax_trace()
        if self.max_seconds > 0:
            self._timer = threading.Timer(self.max_seconds,
                                          self._finish)
            self._timer.daemon = True
            self._timer.start()
        return self

    def _start_jax_trace(self) -> None:
        import jax
        try:
            # jax's own Python tracer stays off: it records every
            # Python call (tens of MB for a three-image scan) and
            # stretches the host phases it is there to show; stacks
            # come from the sampling profiler (host_profile.folded),
            # phases from the trivy.* annotations (obs/trace.py)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self._jax_trace = jax.profiler.trace(
                self.out_dir, profiler_options=opts)
            self._jax_trace.__enter__()
        except Exception as e:      # noqa: BLE001 — whatever the
            # profiler plugin raises; classified just below
            self._jax_trace = None
            try:
                platform = jax.default_backend()
            except RuntimeError:
                platform = "tpu"    # the backend itself is broken
            if platform == "tpu":
                raise RuntimeError(
                    f"device trace could not start on the TPU "
                    f"({self.out_dir}): {e!r}") from e
            log.warning("jax device trace unavailable on %s, host "
                        "profile only: %r", platform, e)

    def _finish(self, *exc) -> None:
        with self._done:
            if self._finished:
                return
            self._finished = True
        if self._jax_trace is not None:
            try:
                self._jax_trace.__exit__(*(exc or (None,) * 3))
            # lint: disable=bare-except-at-seam -- no jax or no
            # profiler plugin: the host-only profile is still
            # written below, which is the degraded contract
            except Exception:       # noqa: BLE001
                pass
        try:
            get_profiler(start=False).dump(
                os.path.join(self.out_dir, "host_profile.folded"))
        except OSError:
            pass

    def __exit__(self, *exc):
        if not self.out_dir:
            return
        if self._timer is not None:
            self._timer.cancel()
        self._finish(*exc)


def device_trace(out_dir: str, max_seconds: float = 0.0,
                 device: bool = True) -> _DeviceTraceCtx:
    """``--profile-out DIR``: opt-in jax.profiler device trace (open
    in TensorBoard/Perfetto) + the host profiler's collapsed stacks
    written to ``DIR/host_profile.folded``. A falsy ``out_dir`` is a
    no-op; ``max_seconds`` bounds the capture window (0 = until the
    context exits); ``device=False`` writes the host profile only."""
    return _DeviceTraceCtx(out_dir, max_seconds=max_seconds,
                           device=device)
