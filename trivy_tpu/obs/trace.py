"""Zero-dependency per-request tracing (docs/observability.md).

Dapper-style spans over the scan pipeline: every admitted request
gets a root ``scan`` span whose children bracket the stages it moved
through — ``queue_wait`` → ``analyze`` (host) → ``coalesce`` (with
batch id, padding bucket and occupancy) → ``device`` (one span per
dispatch attempt, so bisect retries and quarantine probes are
visible as siblings) → ``host_fallback`` (quarantine only) →
``report``. Fault injections, guard-budget trips and breaker
degradations land as span EVENTS on whatever span is active.

Identifiers follow the W3C/OTel shape (hex trace/span ids) but the
wire format is the Chrome trace-event JSON Perfetto loads directly
(``to_chrome``): complete spans become ``"ph": "X"`` duration events
keyed by the thread that ran them, span events become ``"ph": "i"``
instants.

A :class:`Tracer` is one tracing domain. The module-level default
(:func:`get_tracer`) is what the scheduler, the batch runner and the
RPC server share unless a test injects its own; disabling a tracer
(``Tracer(enabled=False)``) turns every ``start_span`` into a shared
no-op span: the untraced arm that ``--trace 0`` of the benchmark
and ``pytest -m obs`` compare a traced run with.

Everything here is import-light on purpose: no trivy_tpu imports at
module scope, so the logging layer and the guard/fault seams can
reach :func:`add_event` without cycles.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import gc
import os
import re
import resource
import threading
import time

_ID_RE = re.compile(r"[0-9a-f]{8,64}")

# spans per trace / concurrently open traces are bounded so a request
# source that never completes (or a hostile trace_id storm) cannot
# grow the tracer without limit
MAX_SPANS_PER_TRACE = 4096
MAX_OPEN_TRACES = 1024
# distinct span NAMES tracked as /metrics histograms: each name is a
# label value on trivy_tpu_trace_span_seconds, so a hostile or buggy
# caller minting names must fold into "other" instead of growing the
# exposition without bound (same policy sched/tenant.py applies to
# tenant labels)
MAX_PHASE_NAMES = 64


def new_trace_id() -> str:
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


def _clean_trace_id(trace_id) -> str:
    """Externally supplied trace ids (RPC bodies) are only honored in
    the canonical lowercase-hex shape — anything else gets a fresh id
    (the id is later used as a flight-recorder file name, so this is
    a security boundary, not just hygiene). fullmatch, not match: $
    would admit a trailing newline into the file name."""
    trace_id = (trace_id or "").lower()
    return trace_id if _ID_RE.fullmatch(trace_id) else ""


_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "trivy_tpu_active_span", default=None)


def current_span():
    """The span active on this thread/context, or None."""
    return _ACTIVE.get()


def add_event(name: str, **attrs) -> None:
    """Record an event on the active span; no-op without one. The
    guard budgets, the fault injector and the resilient cache call
    this — they never need a tracer handle."""
    span = _ACTIVE.get()
    if span is not None:
        span.event(name, **attrs)


# ---- the phase clock -------------------------------------------------
#
# One table for the whole process: every ``phase_span`` exit books a
# row here, traced or not, so the numbers /metrics, ``--sched-stats``
# and the benchmark read are the ones measured where the work
# happens. Keys are code-literal call sites (pipeline, phase), a few
# dozen in all; MAX_PHASE_NAMES still folds a runaway caller.

_PHASE_LOCK = threading.Lock()
# (pipeline, phase) -> [n, busy_s, user_s, sys_s, owed user, owed sys]
_PHASE_ROWS: dict = {}

# ``factory(name)`` -> context manager that puts ``name`` on the
# device profiler's clock (jax.profiler.TraceAnnotation), installed
# by runtime/device.py once jax is imported; obs/ stays stdlib-only
_ANNOTATOR = None
_NO_ANNOTATION = contextlib.nullcontext()


def set_annotator(factory) -> None:
    """Install (or with None remove) the profiler-timeline hook."""
    global _ANNOTATOR
    _ANNOTATOR = factory


def annotation(name: str):
    """``with annotation("trivy.compile.fused"):`` — one span on the
    profiler's clock, or nothing when no annotator is installed."""
    ann = _ANNOTATOR
    return ann(name) if ann is not None else _NO_ANNOTATION


def _thread_cpu_reader():
    """``read()[0]``, ``read()[1]``: the calling thread's user and
    system CPU seconds, which the kernel keeps apart
    (``getrusage(RUSAGE_THREAD)``, Linux). The kernel moves these
    books at its timer tick (4 ms on a stock kernel, 10 ms on the
    chip's sandboxed host, whose ``time.thread_time()`` steps the
    same way) and at a context switch, not at the call: a span
    shorter than a tick reads nothing or a whole tick, and
    :func:`book_phase` says what becomes of the tick. Where the
    platform has no ``RUSAGE_THREAD`` all of ``time.thread_time()``
    reads as user seconds and ``sys_s`` stays 0."""
    which = getattr(resource, "RUSAGE_THREAD", None)
    if which is None:
        return lambda: (time.thread_time(), 0.0)
    return functools.partial(resource.getrusage, which)


_thread_cpu = _thread_cpu_reader()


def _row_locked(pipeline: str, phase: str) -> list:
    key = (pipeline, phase)
    row = _PHASE_ROWS.get(key)
    if row is None:
        if len(_PHASE_ROWS) >= MAX_PHASE_NAMES:
            key = (pipeline, "other")
            row = _PHASE_ROWS.get(key)
        if row is None:
            row = _PHASE_ROWS[key] = [0, 0.0, 0.0, 0.0, 0.0, 0.0]
    return row


def ensure_phase(pipeline: str, phase: str) -> None:
    """The row is there, at zero, before it is first booked: a
    reader that takes a missing row for "not measured" (the
    benchmark's) reads 0 for a wait that never happened."""
    with _PHASE_LOCK:
        _row_locked(pipeline, phase)


def book_phase(pipeline: str, phase: str, busy_s: float,
               cpu_s: float = 0.0, *, sys_s: float = 0.0) -> None:
    """One row booked by hand, for a wait that starts on one thread
    and ends on another and so has no ``with`` to stand in (the
    scheduler's ``hit_wait``: all of it ``wait_s``). ``sys_s`` is
    the part of ``cpu_s`` spent in the kernel. Everything else uses
    :func:`phase_span`.

    Every booking is held to its wall, so ``cpu_s <= busy_s`` holds
    for a row and for the difference of any two readings of it. CPU
    seconds come a tick at a time, so a short span may read a whole
    tick: what it reads beyond its wall is owed to the row's next
    bookings, which take it as far as their own wall has room, the
    split keeping its proportions. Over many spans nothing is lost;
    a row's last tick may stay owed."""
    with _PHASE_LOCK:
        row = _row_locked(pipeline, phase)
        user = cpu_s - sys_s + row[4]
        sys_ = sys_s + row[5]
        over = user + sys_ - busy_s
        if over > 0.0:
            keep = busy_s / (user + sys_)
            row[4], row[5] = user * (1.0 - keep), sys_ * (1.0 - keep)
            user, sys_ = user * keep, sys_ * keep
        else:
            row[4] = row[5] = 0.0
        row[0] += 1
        row[1] += busy_s
        row[2] += user
        row[3] += sys_


def _row_dict(n: int, busy: float, user: float = 0.0,
              sys_: float = 0.0) -> dict:
    cpu = min(user + sys_, busy)    # sums of floats: to the last bit
    return {"n": n, "busy_s": busy, "cpu_s": cpu, "user_s": user,
            "sys_s": sys_, "wait_s": busy - cpu}


def phase_table() -> dict:
    """``{pipeline: {phase: {"n", "busy_s", "cpu_s", "user_s",
    "sys_s", "wait_s"}}}``, cumulative since process start.
    ``busy_s`` is wall time inside the phase. The calling thread's
    CPU time inside it is ``cpu_s``, and the kernel splits it:
    ``user_s`` the interpreter (and native code) computing,
    ``sys_s`` the kernel working for the thread's calls. ``wait_s``
    (busy less cpu) is time the thread was off the CPU: waiting
    for the interpreter, a lock, the device or a core. The CPU
    columns are sampled at the kernel's tick, so trust a row that
    holds many ticks."""
    with _PHASE_LOCK:
        rows = [(key, *row[:4]) for key, row in _PHASE_ROWS.items()]
    out: dict = {}
    for (pl, ph), n, busy, user, sys_ in rows:
        out.setdefault(pl, {})[ph] = _row_dict(n, busy, user, sys_)
    if _gc_full[0] is not None:
        out.setdefault("host", {})["gc_full"] = _row_dict(
            _gc_full[0], _gc_full[1])
    return out


def phase_rows(pipeline: str) -> dict:
    """One pipeline's rows of :func:`phase_table`."""
    return phase_table().get(pipeline, {})


# ---- the ``host`` pipeline: what belongs to no thread's phase ----

def process_cpu() -> dict:
    """The whole process's user and system CPU seconds, every
    thread's and the runtime's native ones (``getrusage(
    RUSAGE_SELF)``). Read at snapshot time, never on a hot path:
    over a window, ``user_s`` a second of wall is how many cores'
    worth of computing the process did, and one interpreter gives
    at most one."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": r.ru_utime, "sys_s": r.ru_stime}


def host_snapshot() -> dict:
    """``{"process": {"user_s", "sys_s"}, "phase": {"gc_full"}}``:
    what ``DETECT_METRICS.snapshot()["host"]`` carries, and with it
    ``scheduler.stats()["detect"]["host"]``."""
    return {"process": process_cpu(), "phase": phase_rows("host")}


# n (None until first watched), busy_s, start, open profiler span.
# The collector runs ``_on_gc`` on whichever thread tripped its
# threshold, and that thread may hold any lock of this process,
# ``_PHASE_LOCK`` included: so the row lives here, outside the
# table, and ``_on_gc`` takes no lock. The interpreter never runs
# two collections at a time.
_gc_full = [None, 0.0, 0.0, _NO_ANNOTATION]


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry: an automatic FULL collection
    stops every thread, so it is booked as ``host.gc_full`` and
    bracketed ``trivy.host.gc_full`` on the profiler's clock.
    Young and middle collections return at once."""
    if info["generation"] < 2:
        return
    if phase == "start":
        _gc_full[3] = ann = annotation("trivy.host.gc_full")
        ann.__enter__()
        _gc_full[2] = time.monotonic()
    elif _gc_full[2]:               # installed before its start
        busy = time.monotonic() - _gc_full[2]
        _gc_full[2] = 0.0
        _gc_full[3].__exit__(None, None, None)
        _gc_full[0] += 1
        _gc_full[1] += busy


def watch_full_gc(on: bool) -> None:
    """Install or remove :func:`_on_gc`. ``utils.sparse_full_gc``
    calls this for its first holder and after its last, so the
    entry is there exactly while a scheduler runs; the row is in
    the table, at zero, from the first install on."""
    if on:
        if _gc_full[0] is None:
            _gc_full[0] = 0
        gc.callbacks.append(_on_gc)
    else:
        gc.callbacks.remove(_on_gc)


class _PhaseSpanCtx:
    """Context manager behind :func:`phase_span`, and the handle the
    ``with`` binds: ``set`` forwards to the tracer span,
    ``duration_s``/``cpu_s`` hold the phase's seconds after exit
    (error exits too; ``cpu_s`` no more than ``duration_s``)."""

    __slots__ = ("name", "pipeline", "attrs", "span", "duration_s",
                 "cpu_s", "_token", "_ann", "_t0", "_c0")

    def __init__(self, name: str, pipeline: str, attrs: dict):
        self.name = name
        self.pipeline = pipeline
        self.attrs = attrs
        self.span = NOOP_SPAN
        self.duration_s = 0.0
        self.cpu_s = 0.0
        self._token = None
        self._ann = None

    def set(self, key: str, value) -> None:
        self.span.set(key, value)

    def __enter__(self):
        ann = _ANNOTATOR
        if ann is not None:
            self._ann = ann(f"trivy.{self.pipeline}.{self.name}")
            self._ann.__enter__()
        parent = _ACTIVE.get()
        if parent is not None and not parent.noop:
            self.span = parent.tracer.child(
                parent, self.name, pipeline=self.pipeline,
                **self.attrs)
            self._token = _ACTIVE.set(self.span)
        # one clock: a live tracer span's own start is the phase's
        self._t0 = time.monotonic() if self.span.noop \
            else self.span.start_mono
        self._c0 = _thread_cpu()
        return self

    def __exit__(self, exc_type, *exc):
        c1, c0 = _thread_cpu(), self._c0
        sys_ = c1[1] - c0[1]
        cpu = c1[0] - c0[0] + sys_
        if self._token is not None:
            _ACTIVE.reset(self._token)
        self.span.end("error" if exc_type is not None else None)
        end = time.monotonic() if self.span.noop \
            else self.span.end_mono
        self.duration_s = max(0.0, end - self._t0)
        self.cpu_s = min(cpu, self.duration_s)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        book_phase(self.pipeline, self.name, self.duration_s,
                   cpu, sys_s=sys_)


def phase_span(name: str, *, pipeline: str, **attrs) -> _PhaseSpanCtx:
    """``with phase_span("pack", pipeline="secret") as sp:`` — the
    one boundary marker of a pipeline phase. It always measures: on
    exit the phase's wall seconds and the thread's user and system
    CPU seconds are booked in the
    process-wide phase table (:func:`phase_rows`) and left on
    ``sp.duration_s``/``sp.cpu_s`` for the per-call stats dicts; it
    brackets ``trivy.<pipeline>.<name>`` on the device profiler's
    timeline when an annotator is installed; and when a request
    span is active on this thread it is also a child of it, so deep
    seams (segment packing, H2D uploads, resident-DB staging) show
    up in Perfetto without threading a tracer handle through every
    call chain (docs/observability.md). Grain: a batch, a layer or
    a pool task — never inside a per-file or per-row loop."""
    return _PhaseSpanCtx(name, pipeline, attrs)


def activate_or_null(span):
    """``with activate_or_null(sp):`` — activate ``span`` on this
    thread, or do nothing when there is none. The async slot
    runtime hops threads (hostpool packers, ring drain) and carries
    the launching batch's span along this way."""
    return span.activate() if span is not None \
        else contextlib.nullcontext()


class _SpanContext:
    __slots__ = ("span", "_token")

    def __init__(self, span):
        self.span = span
        self._token = None

    def __enter__(self):
        self._token = _ACTIVE.set(self.span)
        return self.span

    def __exit__(self, *exc):
        _ACTIVE.reset(self._token)


class Span:
    """One timed operation: wall-anchored start, monotonic duration,
    typed attributes, instant events. ``end`` is idempotent and
    hands the finished span to its tracer."""

    noop = False
    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "start_wall", "start_mono", "end_mono", "attrs",
                 "events", "status", "tid", "is_root")

    def __init__(self, tracer, name: str, trace_id: str,
                 parent_id=None, attrs=None):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        # a propagated (remote) parent makes a LOCAL root whose
        # parent_id points into another process's trace: is_root, not
        # parent_id, decides completion bookkeeping from here on
        self.is_root = parent_id is None
        self.name = name
        self.start_wall = time.time()
        self.start_mono = time.monotonic()
        self.end_mono = None
        self.attrs = dict(attrs) if attrs else {}
        self.events = []
        self.status = "ok"
        self.tid = threading.get_ident()

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def event(self, name: str, **attrs) -> None:
        self.events.append((time.monotonic(), name, attrs))

    def activate(self) -> _SpanContext:
        """``with span.activate():`` — publish as the thread's
        current span (log correlation + add_event routing)."""
        return _SpanContext(self)

    @property
    def duration_s(self) -> float:
        if self.end_mono is None:
            return 0.0
        return max(0.0, self.end_mono - self.start_mono)

    def end(self, status=None) -> None:
        if self.end_mono is not None:
            return
        self.end_mono = time.monotonic()
        if status and status != "ok":
            self.status = status
        self.tracer._finish(self)


class _NoopSpan:
    """Shared do-nothing span returned by a disabled tracer."""

    noop = True
    trace_id = ""
    span_id = ""
    parent_id = None
    is_root = False
    name = ""
    attrs: dict = {}
    events: list = []
    status = "ok"
    start_mono = 0.0
    end_mono = 0.0
    duration_s = 0.0

    def set(self, key, value):
        pass

    def event(self, name, **attrs):
        pass

    def end(self, status=None):
        pass

    def activate(self):
        return _NOOP_CTX


class _NoopCtx:
    def __enter__(self):
        return NOOP_SPAN

    def __exit__(self, *exc):
        pass


_NOOP_CTX = _NoopCtx()
NOOP_SPAN = _NoopSpan()


class Tracer:
    """One tracing domain: creates spans, collects completed traces
    into the flight recorder, optionally exports each completed
    trace as Perfetto-loadable JSON, and derives per-span-name
    latency histograms for ``/metrics``."""

    def __init__(self, enabled: bool = True, recorder=None,
                 export_dir: str = "", phase_metrics: bool = True):
        self.enabled = enabled
        self.export_dir = export_dir
        self.epoch_wall = time.time()
        self.epoch_mono = time.monotonic()
        self._lock = threading.Lock()
        self._spans: dict = {}    # open trace_id -> [finished Span]
        # propagated traces can have several concurrently-open LOCAL
        # roots on one trace_id (N scans sharing a fleet trace): the
        # bucket completes when the LAST root ends, and a bad status
        # on any earlier root still forces the dump
        self._open_roots: dict = {}   # trace_id -> open root count
        self._dirty: set = set()      # trace_ids owed a dump
        if recorder is None:
            from .recorder import FlightRecorder
            recorder = FlightRecorder()
        self.recorder = recorder
        # dumps triggered off-tracer (SLO burn-rate trips, operator
        # pokes) must land on the same timebase as _finish's dumps
        recorder.epoch_mono = self.epoch_mono
        self._phase = {} if phase_metrics else None
        self.n_spans = 0
        self.n_traces = 0
        self.n_exported = 0

    # --- span creation ---

    def start_span(self, name: str, trace_id: str = "",
                   parent=None, attrs=None, remote_parent: str = ""):
        if not self.enabled:
            return NOOP_SPAN
        if parent is not None:
            if parent.noop:
                return NOOP_SPAN
            span = Span(self, name, parent.trace_id,
                        parent_id=parent.span_id, attrs=attrs)
            req = parent.attrs.get("request")
            if req is not None and "request" not in span.attrs:
                span.attrs["request"] = req
            return span
        span = Span(self, name,
                    _clean_trace_id(trace_id) or new_trace_id())
        rp = _clean_trace_id(remote_parent)
        if rp:
            # a propagated parent from another process: this span is
            # still a LOCAL root (it owns its bucket's completion)
            # but its parent_id links it into the fleet-wide tree
            span.parent_id = rp
        if attrs:
            span.attrs.update(attrs)
        with self._lock:
            while len(self._spans) >= MAX_OPEN_TRACES:
                # drop the oldest open trace — a root that never ends
                # must not pin its children forever
                dropped = next(iter(self._spans))
                self._spans.pop(dropped)
                self._open_roots.pop(dropped, None)
                self._dirty.discard(dropped)
            self._spans.setdefault(span.trace_id, [])
            self._open_roots[span.trace_id] = \
                self._open_roots.get(span.trace_id, 0) + 1
        return span

    def start_request(self, name: str, trace_id: str = "",
                      parent_span_id: str = ""):
        """Root span for one scan request; a propagated
        ``parent_span_id`` links it under a remote caller's span."""
        root = self.start_span("scan", trace_id=trace_id,
                               remote_parent=parent_span_id)
        root.set("request", name)
        return root

    def child(self, parent, name: str, **attrs):
        if parent is None or parent.noop:
            return NOOP_SPAN
        return self.start_span(name, parent=parent,
                               attrs=attrs or None)

    # --- completion plumbing ---

    def _finish(self, span: Span) -> None:
        if self._phase is not None and not span.is_root:
            self._observe_phase(span.name, span.duration_s,
                                span.trace_id,
                                span.attrs.get("pipeline", ""))
        with self._lock:
            self.n_spans += 1
            if not span.is_root:
                bucket = self._spans.get(span.trace_id)
                if bucket is None:
                    # finished after its root (e.g. a sweep resolved
                    # the request mid-stage): file it with the
                    # completed trace while it is still in the ring
                    self.recorder.append(span.trace_id, span)
                elif len(bucket) < MAX_SPANS_PER_TRACE:
                    bucket.append(span)
                return
            remaining = self._open_roots.get(span.trace_id, 1) - 1
            if remaining > 0:
                # sibling roots on the same propagated trace are
                # still open: file this root like a child and keep
                # the bucket until the last one ends
                self._open_roots[span.trace_id] = remaining
                bucket = self._spans.get(span.trace_id)
                if bucket is not None and \
                        len(bucket) < MAX_SPANS_PER_TRACE:
                    bucket.append(span)
                if span.status in ("degraded", "failed", "error"):
                    self._dirty.add(span.trace_id)
                return
            self._open_roots.pop(span.trace_id, None)
            spans = self._spans.pop(span.trace_id, [])
            spans.append(span)
            self.n_traces += 1
            dirty = span.trace_id in self._dirty
            self._dirty.discard(span.trace_id)
        self._complete(span, spans, dirty=dirty)

    def _observe_phase(self, name: str, dur_s: float,
                       trace_id: str = "",
                       pipeline: str = "") -> None:
        from ..sched.metrics import LatencyHistogram
        # phase_span children carry their pipeline, so secret
        # ``pack`` and interval ``pack`` are two histograms
        key = (name, pipeline)
        with self._lock:
            h = self._phase.get(key)
            if h is None:
                if len(self._phase) >= MAX_PHASE_NAMES:
                    # cardinality cap: overflow names fold into one
                    # shared histogram so /metrics stays bounded
                    key = ("other", "")
                    h = self._phase.get(key)
                if h is None:
                    h = self._phase[key] = LatencyHistogram()
            h.observe(dur_s, exemplar=trace_id)

    def _complete(self, root: Span, spans: list,
                  dirty: bool = False) -> None:
        self.recorder.add(root.trace_id, spans)
        if self.export_dir:
            try:
                self._export(root.trace_id, spans)
            except OSError:
                pass
        if dirty or root.status in ("degraded", "failed", "error"):
            # degraded/failed scans dump the full trace to disk so
            # the evidence outlives the in-memory ring ("rejected"
            # backpressure answers deliberately do NOT — a 503 storm
            # must not become a disk-write storm; the recorder also
            # caps how many dump files it keeps)
            try:
                self.recorder.dump(root.trace_id, spans,
                                   epoch_mono=self.epoch_mono)
            except (OSError, ValueError):
                pass

    def _export(self, trace_id: str, spans: list) -> None:
        self.recorder.write_doc(
            os.path.join(self.export_dir, f"trace-{trace_id}.json"),
            to_chrome(spans, self.epoch_mono, self.epoch_wall))
        self.n_exported += 1

    # --- lookup / reporting ---

    def trace(self, trace_id: str):
        """Chrome trace-event document for one trace (completed, or
        the finished spans of one still in flight), or None."""
        spans = self.recorder.get(trace_id)
        if spans is None:
            with self._lock:
                open_spans = self._spans.get(trace_id)
                spans = list(open_spans) if open_spans else None
        if spans is None:
            return None
        return to_chrome(spans, self.epoch_mono, self.epoch_wall)

    def phase_snapshot(self) -> dict:
        """Raw histograms for Prometheus exposition (with per-bucket
        trace-id exemplars), keyed by span name, or
        ``<pipeline>.<name>`` for a phase_span child; each carries
        its ``labels`` (``span``, and ``pipeline`` where it has
        one)."""
        out = {}
        with self._lock:
            for (name, pipeline), h in (self._phase or {}).items():
                raw = h.raw()
                raw["labels"] = {"span": name}
                if pipeline:
                    raw["labels"]["pipeline"] = pipeline
                out[f"{pipeline}.{name}" if pipeline else name] = raw
        return out

    def stats(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "spans": self.n_spans,
                    "traces": self.n_traces,
                    "open_traces": len(self._spans),
                    "exported": self.n_exported}


def to_chrome(spans: list, epoch_mono: float = 0.0,
              epoch_wall=None) -> dict:
    """Chrome trace-event JSON (Perfetto / chrome://tracing): spans
    as complete ("X") duration events, span events as instants."""
    events = []
    for s in spans:
        end = s.end_mono if s.end_mono is not None else s.start_mono
        args = {"trace_id": s.trace_id, "span_id": s.span_id,
                "status": s.status}
        if s.parent_id:
            args["parent_id"] = s.parent_id
        args.update(s.attrs)
        tid = s.tid & 0xffff
        events.append({
            "ph": "X", "cat": "trivy_tpu", "name": s.name,
            "ts": round((s.start_mono - epoch_mono) * 1e6, 3),
            "dur": round(max(0.0, end - s.start_mono) * 1e6, 3),
            "pid": 1, "tid": tid, "args": args,
        })
        for t, name, attrs in s.events:
            events.append({
                "ph": "i", "cat": "trivy_tpu", "name": name,
                "ts": round((t - epoch_mono) * 1e6, 3),
                "s": "t", "pid": 1, "tid": tid,
                "args": dict(attrs),
            })
    out = {"traceEvents": events, "displayTimeUnit": "ms"}
    if epoch_wall is not None:
        out["otherData"] = {"epoch_unix_s": round(epoch_wall, 6)}
    return out


def summarize(spans: list) -> str:
    """One-line phase breakdown: 'scan 42.1ms: queue_wait 0.2ms,
    analyze 30.0ms, device 8.1ms, report 2.3ms'."""
    root = next((s for s in spans
                 if getattr(s, "is_root", s.parent_id is None)),
                None)
    parts = [f"{s.name} {s.duration_s * 1e3:.1f}ms"
             for s in spans
             if not getattr(s, "is_root", s.parent_id is None)]
    head = (f"{root.name} {root.duration_s * 1e3:.1f}ms"
            if root is not None else "")
    if parts:
        return (head + ": " if head else "") + ", ".join(parts)
    return head


def trace_cause(tracer: Tracer, trace_id: str) -> dict:
    """FailureCause payload a degraded/failed result carries so the
    operator can pull the request's trace (served at /trace/<id>,
    dumped by the flight recorder)."""
    return {"stage": "obs", "kind": "trace",
            "message": f"trace {trace_id} captured (dump: "
                       f"{tracer.recorder.dump_path(trace_id)})"}


_TRACER = None
_TRACER_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    """The process-default tracer (created on first use, with the
    flight recorder's log ring attached to the trivy_tpu logger)."""
    global _TRACER
    if _TRACER is None:
        candidate = Tracer()
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = candidate
                won = candidate
            else:
                won = None
        if won is not None:
            # the handler attach takes the recorder's and logging's
            # locks — outside _TRACER_LOCK (lint: lock-discipline).
            # A racing get_tracer() may briefly see the tracer
            # before its log ring attaches; only the first
            # microseconds of log capture can miss.
            from .recorder import attach_ring_handler
            attach_ring_handler(won.recorder)
    return _TRACER
