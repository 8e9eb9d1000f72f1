"""Batch vulnerability detection: rank-encode versions, compile
constraints to intervals, one TPU dispatch for every (package,
advisory) pair across every ecosystem in the batch.

Parity: results are identical to the host drivers (library.py /
ospkg/drivers.py) — guaranteed because interval compilation is exact
over the finite rank universe, pairs whose constraints exceed
MAX_INTERVALS or fail to parse fall back to the host path, and the
doubled rank space captures bound exclusivity exactly.

Dispatch shape (docs/performance.md): jobs are DEDUPED before any
compilation — fleets repeat (version, constraint) pairs massively
(every SBOM in a batch depends on the same lodash), so the kernel
evaluates each distinct pair once and the hit fans back out to every
duplicate's payload. Row tables are packed with bulk fancy-index
stores into PREALLOCATED buffers padded to a small bucket ladder, so
XLA's compile cache is keyed by a handful of shapes instead of one
per arbitrary batch size.

Two dispatch surfaces share those mechanics:

* :func:`dispatch_jobs` — the synchronous ladder (pack → upload →
  compute → collect on the calling thread); cpu-ref, host fallback
  and the quarantine path stay here.
* :func:`dispatch_jobs_async` / :func:`collect_dispatch` — the
  double-buffered slot runtime (docs/performance.md "Async device
  runtime"): rows split into bounded waves, each wave's payload
  buffers uploaded fresh and DONATED to the jitted kernel
  (``interval_hits_donated`` — resident advisory tables are never
  donated), the kernel enqueued non-blocking, and the blocking
  materialize pushed to a :class:`runtime.ring.DispatchRing` drain
  thread so wave N+1 packs while wave N computes. Results are
  byte-identical to the synchronous ladder at every wave split,
  dispatch depth, and device count (property-tested).
"""

from __future__ import annotations

from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..ops.intervals import (MAX_INTERVALS, NEG_INF, POS_INF,
                             interval_hits, interval_hits_host)
from ..utils import get_logger
from ..vercmp import get_comparer
from ..vercmp.base import Interval
from .ccache import INTERVAL_CACHE
from .metrics import DETECT_METRICS

log = get_logger("detect.batch")


@dataclass
class PairJob:
    """One (package, advisory) candidate pair after the name join."""

    grammar: str
    pkg_version: str
    vulnerable: list = field(default_factory=list)  # constraint strings
    patched: list = field(default_factory=list)
    unaffected: list = field(default_factory=list)
    payload: object = None          # opaque — returned with hits
    # ospkg-style single bounds:
    fixed_version: str = ""
    affected_version: str = ""
    report_unfixed: bool = True
    kind: str = "library"           # "library" | "ospkg"

    def dedup_key(self) -> tuple:
        """Everything that affects evaluation — NOT the payload.
        Jobs sharing a key are provably equivalent, so one kernel
        row serves all of them."""
        return (self.kind, self.grammar, self.pkg_version,
                tuple(self.vulnerable), tuple(self.patched),
                tuple(self.unaffected), self.fixed_version,
                self.affected_version, self.report_unfixed)


class _RankSpace:
    """Per-grammar rank universe over the batch's version strings."""

    def __init__(self, grammar: str):
        self.comparer = get_comparer(grammar)
        self.keys: dict = {}
        self.extra: list = []           # constraint bound keys

    def key(self, version: str):
        if version not in self.keys:
            self.keys[version] = self.comparer.parse(version)
        return self.keys[version]

    def add_key(self, key) -> None:
        self.extra.append(key)

    def finalize(self):
        self.sorted_keys = sorted(
            set(self.keys.values()) | set(self.extra))

    def rank(self, key) -> int:
        return 2 * bisect_left(self.sorted_keys, key)

    def encode(self, iv: Interval) -> tuple:
        lo = NEG_INF if iv.lo is None else \
            self.rank(iv.lo) + (0 if iv.lo_incl else 1)
        hi = POS_INF if iv.hi is None else \
            self.rank(iv.hi) - (0 if iv.hi_incl else 1)
        return lo, hi


# device-kernel wall time of the most recent dispatch_jobs call,
# for the host/device split in stats + tracing. Callers that
# dispatch from several threads (the sched device executor) pass
# their own ``stats`` sink instead of sharing this module global.
last_dispatch_stats: dict = {"device_s": 0.0, "dispatch_s": 0.0}


def _book(sink: dict, span, *keys: str) -> None:
    """Add a finished phase's seconds to a per-call stats sink:
    ``device_s`` (upload + kernel wall, what the cost ledger bills)
    and ``dispatch_s`` (every interval phase the calling thread sat
    in) are sums of span durations, never a second clock."""
    for key in keys:
        sink[key] = sink.get(key, 0.0) + span.duration_s


_WAVE_ROWS = 4096      # max kernel rows launched per wave


def _job_bucket(n: int) -> int:
    """Pair-row shape ladder: powers of two up to 8192, then
    8192-steps (the shared ops.keywords ladder with pair-row
    constants). Pad rows are inert (flags=0 → never hit) and the
    caller trims the output, so the only cost is a few wasted lanes
    — repaid many times over by XLA compile-cache hits."""
    from ..ops.keywords import _bucket
    return _bucket(n, base=64, cap=8192)


def _resident_waves(n: int) -> list:
    """``(first row, rows, padded rows)`` of each wave the
    synchronous resident dispatch sends ``n`` rows in. Rows that fit
    one wave go at their ``_job_bucket`` rung, the rungs the
    scheduler's waves take (``runtime/aot.interval_rungs``); more
    rows go in waves of ``_WAVE_ROWS``, the last one padded to the
    full rung too, so a pass of any size above one wave runs ONE
    program, and a pass of ``scan_boms`` whose distinct rows differ
    from the last pass's compiles nothing."""
    if n <= _WAVE_ROWS:
        return [(0, n, _job_bucket(n))]
    return [(a, min(_WAVE_ROWS, n - a), _WAVE_ROWS)
            for a in range(0, n, _WAVE_ROWS)]


def _dedup(jobs: list, key_fn) -> tuple:
    """(representatives, members): one representative job per
    distinct key, plus the original job index list behind each."""
    index: dict = {}
    reps: list = []
    members: list = []
    for i, job in enumerate(jobs):
        k = key_fn(job)
        gi = index.get(k)
        if gi is None:
            index[k] = len(reps)
            reps.append(job)
            members.append([i])
        else:
            members[gi].append(i)
    return reps, members


def _prep_classic(jobs: list, sink: dict) -> tuple:
    """Dedup + per-grammar compile shared by the sync and async
    dispatch paths: ``(reps, members, spaces, rows, host_groups)``
    where ``rows`` holds the kernel-path representatives in group
    order. Rank spaces are NOT finalized yet (wave packing must see
    every interned constraint bound first)."""
    reps, members = _dedup(jobs, PairJob.dedup_key)
    sink["jobs_in"] = sink.get("jobs_in", 0) + len(jobs)
    sink["jobs_unique"] = sink.get("jobs_unique", 0) + len(reps)
    DETECT_METRICS.note_dispatch(len(jobs), len(reps))

    spaces: dict = {}
    rows = []          # (group idx, job, pkg_key, vuln, sec, flags)
    host_groups = []   # fallback: group indices
    for gi, job in enumerate(reps):
        sp = spaces.setdefault(job.grammar, _RankSpace(job.grammar))
        try:
            pkg_key = sp.key(job.pkg_version)
        except ValueError as e:
            log.debug("package version parse error: %s", e)
            continue                      # reference: skip the package
        try:
            vuln_ivs, sec_ivs, flags = _compile(job, sp)
        except _HostFallback:
            host_groups.append(gi)
            continue
        except ValueError as e:
            log.debug("constraint error: %s", e)
            continue                      # reference: warn + not vuln
        if flags is None:
            continue                      # statically not vulnerable
        rows.append((gi, job, pkg_key, vuln_ivs, sec_ivs, flags))
    return reps, members, spaces, rows, host_groups


def _pack_classic(rows: list, spaces: dict, Pp: int) -> tuple:
    """Pack a row slice into padded [Pp] / [Pp, M] tables (pad rows
    inert: flags=0). One fancy-index store per table, as before —
    a wave packs exactly like the monolithic table did, so a hit is
    position-independent and the wave split cannot change results."""
    pkg_rank = np.zeros(Pp, np.int32)
    v_lo = np.full((Pp, MAX_INTERVALS), POS_INF, np.int32)
    v_hi = np.full((Pp, MAX_INTERVALS), NEG_INF, np.int32)
    s_lo = np.full((Pp, MAX_INTERVALS), POS_INF, np.int32)
    s_hi = np.full((Pp, MAX_INTERVALS), NEG_INF, np.int32)
    flags_arr = np.zeros(Pp, np.int32)
    # encode per row, store with ONE fancy-index write per
    # table instead of one scalar store per interval slot
    vi: list = []
    vj: list = []
    vb: list = []
    si: list = []
    sj: list = []
    sb: list = []
    for i, (gi, job, pkg_key, vuln_ivs, sec_ivs, flags) in \
            enumerate(rows):
        sp = spaces[job.grammar]
        pkg_rank[i] = sp.rank(pkg_key)
        flags_arr[i] = flags
        for j, iv in enumerate(vuln_ivs):
            vi.append(i)
            vj.append(j)
            vb.append(sp.encode(iv))
        for j, iv in enumerate(sec_ivs):
            si.append(i)
            sj.append(j)
            sb.append(sp.encode(iv))
    if vb:
        b = np.asarray(vb, np.int32)
        v_lo[vi, vj] = b[:, 0]
        v_hi[vi, vj] = b[:, 1]
    if sb:
        b = np.asarray(sb, np.int32)
        s_lo[si, sj] = b[:, 0]
        s_hi[si, sj] = b[:, 1]
    return pkg_rank, v_lo, v_hi, s_lo, s_hi, flags_arr


def detect_pairs(jobs: list, backend: str = "tpu",
                 mesh=None, stats: Optional[dict] = None) -> list:
    """Returns payloads of vulnerable pairs, batch order preserved.
    With ``mesh``, pair rows shard over every chip (see
    parallel.interval_shard)."""
    if not jobs:
        return []
    from ..obs.trace import phase_span
    sink = stats if stats is not None else last_dispatch_stats
    reps, members, spaces, rows, host_groups = \
        _prep_classic(jobs, sink)

    hit_jobs: list = []          # original job indices that hit
    if rows:
        with phase_span("pack", pipeline="detect", jobs=len(jobs),
                        unique=len(reps)) as psp:
            for sp in spaces.values():
                sp.finalize()
            P = len(rows)
            Pp = P if backend == "cpu-ref" else _job_bucket(P)
            (pkg_rank, v_lo, v_hi, s_lo, s_hi,
             flags_arr) = _pack_classic(rows, spaces, Pp)
        _book(sink, psp, "dispatch_s")
        # device_compute brackets the kernel execution alone — it is
        # what the idle-attribution timeline (obs/timeline.py) counts
        # as the device being busy; the H2D upload keeps its own
        # disjoint h2d_upload span, so upload
        # wall attributes as upload_serialized, never as compute
        if backend == "cpu-ref":
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=P) as csp:
                hits = np.asarray(interval_hits_host(
                    pkg_rank, v_lo, v_hi, s_lo, s_hi, flags_arr))
        elif mesh is not None:
            from ..parallel.interval_shard import \
                sharded_interval_hits
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=P) as csp:
                hits = sharded_interval_hits(
                    mesh, pkg_rank, v_lo, v_hi, s_lo, s_hi,
                    flags_arr)
        else:
            import jax
            from ..ops.intervals import interval_hits_donated
            arrs = (pkg_rank, v_lo, v_hi, s_lo, s_hi, flags_arr)
            with phase_span("h2d_upload", pipeline="detect",
                            bytes=int(sum(a.nbytes for a in arrs))) \
                    as usp:
                dev = [jax.device_put(a) for a in arrs]
            _book(sink, usp, "device_s", "dispatch_s")
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=P) as csp:
                # materialize INSIDE the span: interval_hits is
                # jitted (async dispatch), so closing the span after
                # the enqueue would leave the real kernel wall to
                # dispatch_gap. Every operand is a fresh
                # per-dispatch upload, so the donated variant lets
                # the kernel reuse the payload HBM (buffer-donation
                # audit, docs/performance.md §8)
                hits = np.asarray(interval_hits_donated(*dev))
        _book(sink, csp, "device_s", "dispatch_s")
        if backend != "cpu-ref":
            DETECT_METRICS.note_wave(P)
        for i in np.nonzero(hits[:P])[0]:
            hit_jobs.extend(members[rows[i][0]])

    out = [jobs[i].payload for i in sorted(hit_jobs)]

    # host fallback pairs: exact per-pair evaluation, once per
    # distinct key — the verdict fans out to every duplicate
    host_hits: list = []
    for gi in host_groups:
        if _host_eval(reps[gi]):
            host_hits.extend(members[gi])
    out.extend(jobs[i].payload for i in sorted(host_hits))
    return out


class _HostFallback(Exception):
    pass


def _compile(job: PairJob, sp: _RankSpace):
    """job → (vuln intervals, secure intervals, flags) or None when
    statically not vulnerable. Raises _HostFallback on complexity."""
    if job.kind == "ospkg":
        return _compile_ospkg(job, sp)

    flags = 0
    if any(v == "" for v in list(job.vulnerable) + list(job.patched)):
        return [], [], 2                  # force-vulnerable
    # node-semver's prerelease-exclusion rule is not an interval
    # property; prerelease npm versions take the exact host path
    if getattr(sp.comparer, "is_prerelease",
               lambda v: False)(job.pkg_version):
        raise _HostFallback

    vuln_ivs: list = []
    if job.vulnerable:
        flags |= 1
        for constraint in " || ".join(job.vulnerable).split("||"):
            if not constraint.strip():
                raise ValueError("empty constraint alternative")
            vuln_ivs.extend(INTERVAL_CACHE.intervals(
                job.grammar, sp.comparer, constraint))
    secure = list(job.patched) + list(job.unaffected)
    sec_ivs: list = []
    if secure:
        flags |= 4
        for constraint in " || ".join(secure).split("||"):
            if not constraint.strip():
                raise ValueError("empty constraint alternative")
            sec_ivs.extend(INTERVAL_CACHE.intervals(
                job.grammar, sp.comparer, constraint))
    if len(vuln_ivs) > MAX_INTERVALS or len(sec_ivs) > MAX_INTERVALS:
        raise _HostFallback
    for iv in vuln_ivs + sec_ivs:
        _intern_bounds(iv, sp)
    return vuln_ivs, sec_ivs, flags


def _compile_ospkg(job: PairJob, sp: _RankSpace):
    """OS advisory → vulnerable interval [affected, fixed)."""
    lo = None
    if job.affected_version:
        lo = sp.key(job.affected_version)    # may raise ValueError
    if job.fixed_version == "":
        if not job.report_unfixed:
            return [], [], None       # statically not vulnerable
        iv = Interval(lo=lo)
    else:
        iv = Interval(lo=lo, hi=sp.key(job.fixed_version),
                      hi_incl=False)
    return [iv], [], 1


def _intern_bounds(iv: Interval, sp: _RankSpace) -> None:
    """Constraint bounds are parsed keys — register them in the rank
    universe so ``finalize`` covers them."""
    if iv.lo is not None:
        sp.add_key(iv.lo)
    if iv.hi is not None:
        sp.add_key(iv.hi)


def _host_eval(job: PairJob) -> bool:
    from ..vercmp.base import is_vulnerable
    comparer = get_comparer(job.grammar)
    return is_vulnerable(comparer, job.pkg_version, job.vulnerable,
                         job.patched, job.unaffected)


# ---- compiled-store path (TPU-resident advisory tables) ----

@dataclass
class ResidentPairJob:
    """(package, advisory-row) pair against a CompiledDB — no
    constraint strings, no per-dispatch compilation."""

    cdb: object                 # CompiledDB
    row: int
    grammar: str
    pkg_version: str
    report_unfixed: bool = True
    payload: object = None

    def dedup_key(self) -> tuple:
        # the DB identity is part of the key: row N of one compiled
        # generation says nothing about row N of another, and a
        # caller may hand detect_pairs_resident a mixed list even
        # though dispatch_jobs groups by store first
        return (getattr(self.cdb, "generation", id(self.cdb)),
                self.row, self.grammar, self.pkg_version,
                self.report_unfixed)


def _prep_resident(jobs: list, cdb, sink: dict) -> tuple:
    """Dedup + row triage shared by the sync and async resident
    paths: ``(reps, members, kept, ranks, rows, host)``."""
    from ..db.compiled import F_HOST, F_UNFIXED
    reps, members = _dedup(jobs, ResidentPairJob.dedup_key)
    sink["jobs_in"] = sink.get("jobs_in", 0) + len(jobs)
    sink["jobs_unique"] = sink.get("jobs_unique", 0) + len(reps)
    DETECT_METRICS.note_dispatch(len(jobs), len(reps))

    kept: list = []              # group indices on the kernel path
    ranks: list = []
    rows: list = []
    host: list = []              # group indices on the host path
    for gi, job in enumerate(reps):
        flags = int(cdb.flags[job.row])
        if (flags & F_UNFIXED) and not job.report_unfixed:
            continue
        comparer = get_comparer(job.grammar)
        if (flags & F_HOST) or getattr(
                comparer, "is_prerelease",
                lambda v: False)(job.pkg_version):
            host.append(gi)
            continue
        r = cdb.pkg_rank(job.grammar, job.pkg_version)
        if r is None:
            continue                 # version parse error: skip
        kept.append(gi)
        ranks.append(r)
        rows.append(job.row)
    return reps, members, kept, ranks, rows, host


def detect_pairs_resident(jobs: list, backend: str = "tpu",
                          mesh=None,
                          stats: Optional[dict] = None) -> list:
    """Evaluate ResidentPairJobs by gather-dispatch against the
    resident tables: one gather on the host engine and on a mesh,
    :func:`_resident_waves` on one device. Host work is O(distinct
    jobs): duplicates are folded before rank lookup, rank lookups
    are cached per (grammar, version), and the advisory universe is
    never touched."""
    if not jobs:
        return []
    from ..obs.trace import phase_span
    sink = stats if stats is not None else last_dispatch_stats

    cdb = jobs[0].cdb
    if any(j.cdb is not cdb for j in jobs):
        # the kernel path below gathers from ONE store's tables;
        # a mixed list (dispatch_jobs pre-groups, direct callers
        # may not) evaluates per store
        by_db: dict = {}
        for j in jobs:
            by_db.setdefault(id(j.cdb), []).append(j)
        out = []
        for js in by_db.values():
            out.extend(detect_pairs_resident(
                js, backend=backend, mesh=mesh, stats=stats))
        return out
    hit_jobs: list = []
    with phase_span("pack", pipeline="detect", jobs=len(jobs)) \
            as psp:
        reps, members, kept, ranks, rows, host = \
            _prep_resident(jobs, cdb, sink)
        psp.set("unique", len(reps))
        P = len(kept)
        # one gather for the host engine and for a mesh (its rows
        # shard over the chips); on one device, waves of _WAVE_ROWS
        if not kept:
            waves = []
        elif backend == "cpu-ref":
            waves = [(0, P, P)]
        elif mesh is not None:
            waves = [(0, P, _job_bucket(P))]
        else:
            waves = _resident_waves(P)
        packed = []
        for a, n, padded in waves:
            pkg_rank = np.zeros(padded, np.int32)
            row_idx = np.zeros(padded, np.int32)
            pkg_rank[:n] = ranks[a:a + n]
            row_idx[:n] = rows[a:a + n]
            packed.append((pkg_rank, row_idx))
    _book(sink, psp, "dispatch_s")

    if kept:
        # device_compute = kernel execution only (obs/timeline.py
        # busy set); table staging keeps its db_upload span
        if backend == "cpu-ref":
            pkg_rank, row_idx = packed[0]
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=P) as csp:
                hits = interval_hits_host(
                    pkg_rank, cdb.v_lo[row_idx], cdb.v_hi[row_idx],
                    cdb.s_lo[row_idx], cdb.s_hi[row_idx],
                    cdb.flags[row_idx])
        elif mesh is not None:
            from ..parallel.interval_shard import \
                sharded_interval_hits_resident
            tables = cdb.device_tables(mesh=mesh)
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=P) as csp:
                hits = sharded_interval_hits_resident(
                    mesh, *packed[0], tables)
        else:
            import jax
            from ..ops.intervals import \
                interval_hits_resident_donated
            tables = cdb.device_tables()
            with phase_span("h2d_upload", pipeline="detect",
                            bytes=int(sum(a.nbytes + b.nbytes
                                          for a, b in packed))) as usp:
                staged = [(jax.device_put(a), jax.device_put(b))
                          for a, b in packed]
            _book(sink, usp, "device_s", "dispatch_s")
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=P) as csp:
                # the gather operands are fresh per-wave uploads →
                # donated; the resident tables are shared across
                # every dispatch of this generation → never donated.
                # Every wave is enqueued before the first is
                # fetched, so the device runs through the fetches
                lazy = [interval_hits_resident_donated(
                    dr, di, *tables) for dr, di in staged]
                hits = np.concatenate(
                    [np.asarray(h)[:n]
                     for h, (_, n, _) in zip(lazy, waves)])
        _book(sink, csp, "device_s", "dispatch_s")
        if backend != "cpu-ref":
            for _, n, _ in waves:
                DETECT_METRICS.note_wave(n)
        for i in np.nonzero(hits[:P])[0]:
            hit_jobs.extend(members[kept[i]])
    out = [jobs[i].payload for i in sorted(hit_jobs)]

    host_hits: list = []
    for gi in host:
        job = reps[gi]
        # each job's OWN store, not the batch head's — the kernel
        # path above assumes a homogeneous batch, the host path
        # need not
        if job.cdb.host_eval(job.row, job.pkg_version):
            host_hits.extend(members[gi])
    out.extend(jobs[i].payload for i in sorted(host_hits))
    return out


def dispatch_jobs(jobs: list, backend: str = "tpu",
                  mesh=None, stats: Optional[dict] = None) -> list:
    """Mixed-job dispatcher: classic PairJobs (per-dispatch compile)
    and ResidentPairJobs (compiled store), each in one kernel call.
    ``stats`` (optional) receives this call's device_s and the
    dedup counters (``jobs_in`` / ``jobs_unique``) instead of the
    shared module global — pass one per thread."""
    sink = stats if stats is not None else last_dispatch_stats
    sink["device_s"] = 0.0
    sink["dispatch_s"] = 0.0
    sink["jobs_in"] = 0
    sink["jobs_unique"] = 0
    plain = [j for j in jobs if isinstance(j, PairJob)]
    resident = [j for j in jobs if isinstance(j, ResidentPairJob)]
    out = detect_pairs(plain, backend=backend, mesh=mesh,
                       stats=sink) \
        if plain else []
    by_db: dict = {}
    for j in resident:
        by_db.setdefault(id(j.cdb), []).append(j)
    for js in by_db.values():
        out.extend(detect_pairs_resident(js, backend=backend,
                                         mesh=mesh, stats=sink))
    return out


# ---- async slot dispatch (docs/performance.md §8) ----
#
# dispatch_jobs_async() splits the kernel rows into bounded WAVES,
# enqueues every wave non-blocking (payload buffers device_put fresh
# per wave and DONATED to the kernel), and defers the blocking
# materialize to collect_dispatch() — or, when a DispatchRing is
# passed, to the ring's drain thread, which blocks on wave N while
# the submitting thread packs and uploads wave N+1. The drain
# thread's wait is where the device wall actually passes, so its
# device_compute spans carry the true kernel wall for the
# idle-attribution timeline.


def _activate_ctx(span):
    from ..obs.trace import activate_or_null
    return activate_or_null(span)


class _EagerSegment:
    """Backend with no async device path (cpu-ref): the synchronous
    ladder already ran at dispatch; collect replays its output."""

    def __init__(self, out: list):
        self.out = out

    def collect(self) -> list:
        return self.out


class _WaveSegment:
    """Shared wave bookkeeping for the classic and resident async
    paths: launch waves, collect them FIFO, fan hits back out
    through the dedup members exactly like the synchronous path."""

    def __init__(self, jobs: list, sink: dict, ring):
        from ..obs.trace import current_span
        self.jobs = jobs
        self.sink = sink
        self.ring = ring
        # phase spans from ring/pool threads parent under whatever
        # span was active at launch (the batch's device span)
        self.ctx_span = current_span()
        self.waves: list = []
        self.members: list = []
        self.reps: list = []

    def _launch_wave(self, k: int, build) -> None:
        """``build()`` does the upload + non-blocking enqueue and
        returns the wave dict. With a ring it runs as the submit's
        ``launch`` callable, AFTER capacity is acquired — so a full
        ring parks before wave k+1 stages any HBM (the depth bound
        covers staged buffers, not just bookkeeping)."""
        if self.ring is not None:
            built: dict = {}

            def _launch():
                built["wave"] = build()
                return built["wave"]

            slot = self.ring.submit(self._collect_wave,
                                    launch=_launch,
                                    label=f"interval:w{k}")
            wave = built["wave"]
            wave["slot"] = slot
        else:
            wave = build()
        DETECT_METRICS.note_wave(wave["rows"])
        self.waves.append(wave)

    def _collect_wave(self, wave: dict):
        from ..obs.trace import phase_span
        with _activate_ctx(self.ctx_span):
            with phase_span("device_compute", pipeline="detect",
                            kind="interval",
                            rows=wave["rows"]) as sp:
                # materializing blocks until the enqueued kernel
                # finished — on the drain thread this runs
                # concurrently with the next wave's pack/upload,
                # and the span brackets the real device wall
                hits = np.asarray(wave["lazy"])
        wave["hits"] = hits
        wave["lazy"] = None          # free the donated output early
        _book(self.sink, sp, "device_s")
        if self.ring is None:
            # collected inline: the caller's own wall (a ring's
            # drain thread overlaps the caller, whose wait is the
            # wave_wait phase in _kernel_hits)
            _book(self.sink, sp, "dispatch_s")

    def _kernel_hits(self) -> list:
        from ..obs.trace import phase_span
        hit_jobs: list = []
        for wave in self.waves:
            slot = wave.get("slot")
            if slot is not None:
                with phase_span("wave_wait", pipeline="detect",
                                rows=wave["rows"]) as sp:
                    slot.wait()
                _book(self.sink, sp, "dispatch_s")
            elif "hits" not in wave:
                self._collect_wave(wave)
            for i in np.nonzero(wave["hits"][:wave["rows"]])[0]:
                hit_jobs.extend(self.members[wave["groups"][i]])
        return hit_jobs

    def _host_hits(self, host_groups: list, eval_fn) -> list:
        host_hits: list = []
        for gi in host_groups:
            if eval_fn(self.reps[gi]):
                host_hits.extend(self.members[gi])
        return host_hits


class _ClassicSegment(_WaveSegment):
    def __init__(self, jobs: list, mesh, sink: dict, ring,
                 max_wave_rows: int):
        super().__init__(jobs, sink, ring)
        import jax
        from ..obs.trace import phase_span
        with phase_span("pack", pipeline="detect",
                        jobs=len(jobs)) as psp:
            (self.reps, self.members, spaces, rows,
             self.host_groups) = _prep_classic(jobs, sink)
            for sp in spaces.values():
                sp.finalize()
            psp.set("unique", len(self.reps))
        _book(sink, psp, "dispatch_s")
        if not rows:
            return
        w = max(1, int(max_wave_rows))
        slices = [rows[a:a + w] for a in range(0, len(rows), w)]

        def _pack(sl):
            Pp = _job_bucket(len(sl))
            with _activate_ctx(self.ctx_span):
                with phase_span("pack", pipeline="detect",
                                rows=len(sl)):
                    return _pack_classic(sl, spaces, Pp)

        # pool-parallel wave packing: the fancy-index fills of every
        # wave run on the hostpool while this thread uploads and
        # enqueues the waves in order (runtime/hostpool.py — pack is
        # pure compute, never blocks on scheduler events)
        futs = None
        if len(slices) > 1:
            from ..runtime.hostpool import get_host_pool
            import threading as _threading
            if not _threading.current_thread().name.startswith(
                    "trivy-hostpool"):
                pool = get_host_pool()
                if pool is not None:
                    futs = [pool.submit(_pack, sl) for sl in slices]
        for k, sl in enumerate(slices):

            def build(k=k, sl=sl):
                arrays = futs[k].result() if futs is not None \
                    else _pack(sl)
                if mesh is not None:
                    from ..parallel.interval_shard import \
                        sharded_interval_hits_async
                    lazy = sharded_interval_hits_async(mesh,
                                                       *arrays)
                else:
                    from ..ops.intervals import \
                        interval_hits_donated
                    with phase_span("h2d_upload",
                                    pipeline="detect", bytes=int(
                                        sum(a.nbytes
                                            for a in arrays))) as usp:
                        dev = [jax.device_put(a) for a in arrays]
                    _book(sink, usp, "dispatch_s")
                    # dev buffers are this wave's alone → donated;
                    # the kernel reuses the slot HBM for its output
                    lazy = interval_hits_donated(*dev)
                return {"lazy": lazy, "rows": len(sl),
                        "groups": [r[0] for r in sl]}

            self._launch_wave(k, build)

    def collect(self) -> list:
        hit_jobs = self._kernel_hits()
        out = [self.jobs[i].payload for i in sorted(hit_jobs)]
        host_hits = self._host_hits(self.host_groups, _host_eval)
        out.extend(self.jobs[i].payload
                   for i in sorted(host_hits))
        return out


class _ResidentSegment(_WaveSegment):
    def __init__(self, jobs: list, cdb, mesh, sink: dict, ring,
                 max_wave_rows: int):
        super().__init__(jobs, sink, ring)
        import jax
        from ..obs.trace import phase_span
        self.cdb = cdb
        with phase_span("pack", pipeline="detect",
                        jobs=len(jobs)) as psp:
            (self.reps, self.members, kept, ranks, rows,
             self.host_groups) = _prep_resident(jobs, cdb, sink)
            psp.set("unique", len(self.reps))
        _book(sink, psp, "dispatch_s")
        if not kept:
            return
        w = max(1, int(max_wave_rows))
        tables = cdb.device_tables(mesh=mesh) if mesh is not None \
            else cdb.device_tables()
        for k, a in enumerate(range(0, len(kept), w)):

            def build(a=a):
                sl_kept = kept[a:a + w]
                P = len(sl_kept)
                Pp = _job_bucket(P)
                pkg_rank = np.zeros(Pp, np.int32)
                row_idx = np.zeros(Pp, np.int32)
                pkg_rank[:P] = ranks[a:a + w]
                row_idx[:P] = rows[a:a + w]
                if mesh is not None:
                    from ..parallel.interval_shard import \
                        sharded_interval_hits_resident_async
                    lazy = sharded_interval_hits_resident_async(
                        mesh, pkg_rank, row_idx, tables)
                else:
                    from ..ops.intervals import \
                        interval_hits_resident_donated
                    with phase_span("h2d_upload",
                                    pipeline="detect", bytes=int(
                                        pkg_rank.nbytes
                                        + row_idx.nbytes)) as usp:
                        dr = jax.device_put(pkg_rank)
                        di = jax.device_put(row_idx)
                    _book(sink, usp, "dispatch_s")
                    # gather operands donated; the resident
                    # advisory tables are shared state and NEVER
                    # donated
                    lazy = interval_hits_resident_donated(
                        dr, di, *tables)
                return {"lazy": lazy, "rows": P,
                        "groups": sl_kept}

            self._launch_wave(k, build)

    def collect(self) -> list:
        hit_jobs = self._kernel_hits()
        out = [self.jobs[i].payload for i in sorted(hit_jobs)]
        host_hits = self._host_hits(
            self.host_groups,
            lambda job: job.cdb.host_eval(job.row,
                                          job.pkg_version))
        out.extend(self.jobs[i].payload
                   for i in sorted(host_hits))
        return out


class IntervalDispatch:
    """Handle returned by :func:`dispatch_jobs_async`; pass it to
    :func:`collect_dispatch` (exactly once) to fetch results."""

    def __init__(self, sink: dict):
        self.sink = sink
        self.segments: list = []

    @property
    def waves(self) -> int:
        # eager (cpu-ref) segments count as one synchronous
        # dispatch; wave segments count their actual launches — a
        # segment whose jobs all host-fell-back launched ZERO waves
        # and must report zero
        return sum(len(s.waves) if hasattr(s, "waves") else 1
                   for s in self.segments)


def dispatch_jobs_async(jobs: list, backend: str = "tpu",
                        mesh=None, stats: Optional[dict] = None,
                        ring=None,
                        max_wave_rows: int = _WAVE_ROWS) \
        -> IntervalDispatch:
    """Async half of :func:`dispatch_jobs`: dedup + compile + pack,
    then enqueue every wave without materializing. ``ring`` (a
    runtime.ring.DispatchRing) bounds in-flight waves and collects
    them on its drain thread; without one the waves collect lazily
    inside :func:`collect_dispatch` on the calling thread. Output
    (via collect_dispatch) is byte-identical to dispatch_jobs for
    any wave size, ring depth, and device count."""
    sink = stats if stats is not None else last_dispatch_stats
    sink["device_s"] = 0.0
    sink["dispatch_s"] = 0.0
    sink["jobs_in"] = 0
    sink["jobs_unique"] = 0
    handle = IntervalDispatch(sink)
    if backend == "cpu-ref":
        # the exact host reference engine has no device work to
        # overlap — run the synchronous ladder now (the differential
        # baseline stays the differential baseline)
        handle.segments.append(_EagerSegment(dispatch_jobs(
            jobs, backend=backend, mesh=mesh, stats=sink)))
        return handle
    plain = [j for j in jobs if isinstance(j, PairJob)]
    resident = [j for j in jobs if isinstance(j, ResidentPairJob)]
    if plain:
        handle.segments.append(_ClassicSegment(
            plain, mesh, sink, ring, max_wave_rows))
    by_db = {}
    for j in resident:
        by_db.setdefault(id(j.cdb), []).append(j)
    for js in by_db.values():
        handle.segments.append(_ResidentSegment(
            js, js[0].cdb, mesh, sink, ring, max_wave_rows))
    return handle


def collect_dispatch(handle: IntervalDispatch) -> list:
    """Blocking half: wait for every wave (FIFO), fan hits out to
    the duplicate payloads, evaluate host-fallback pairs — same
    output, same order, as the synchronous dispatcher."""
    out: list = []
    for seg in handle.segments:
        out.extend(seg.collect())
    return out
