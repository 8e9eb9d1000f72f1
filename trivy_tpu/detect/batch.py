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
duplicate's payload.

One dispatcher, :func:`dispatch_jobs_async` / :func:`collect_dispatch`
(docs/performance.md §8), with :func:`dispatch_jobs` its blocking
form. The jobs split into segments — the classic rows, and the
resident rows of each compiled store — and every segment runs one
path:

* ``pack``: dedup, triage and every wave's host arrays, built before
  the first launch. The wave plan (:func:`_wave_plan`) sends rows
  that fit one wave at their ``_job_bucket`` rung and more rows in
  waves of ``_WAVE_ROWS``, the last padded to the full rung, so XLA's
  compile cache sees a handful of shapes.
* each wave's upload (``h2d_upload``) and the non-blocking enqueue of
  its DONATED program (the resident advisory tables are never
  donated). With a :class:`runtime.ring.DispatchRing` these run as
  the ring's launch, so a full ring parks before a wave stages HBM.
* ``device_compute``: each wave fetched FIFO, on the ring's drain
  thread or in :func:`collect_dispatch`; the hits and the host
  verdicts fan back out to every duplicate.

The host engine (``cpu-ref``) is one eager, unpadded evaluation of
all kept rows, and a mesh runs each wave through
parallel.interval_shard's sharded programs. Results are
byte-identical at every wave size, ring depth and device count
(property-tested).
"""

from __future__ import annotations

from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from ..ops.intervals import (MAX_INTERVALS, NEG_INF, POS_INF,
                             interval_hits_host)
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


def _wave_plan(n: int) -> list:
    """``(first row, rows, padded rows)`` of each wave ``n`` kept
    rows go in, on one device and on a mesh. Rows that fit one wave
    go at their ``_job_bucket`` rung, the rungs the scheduler's
    waves take (``runtime/aot.interval_rungs``); more rows go in
    waves of ``_WAVE_ROWS``, the last one padded to the full rung
    too, so a pass of any size above one wave runs ONE program, and
    a pass of ``scan_boms`` whose distinct rows differ from the last
    pass's compiles nothing."""
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
    """Dedup + per-grammar compile of the classic rows:
    ``(reps, members, spaces, rows, host_groups)`` where ``rows``
    holds the kernel-path representatives in group order. Rank
    spaces are NOT finalized yet (wave packing must see every
    interned constraint bound first)."""
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
        # generation says nothing about row N of another
        return (getattr(self.cdb, "generation", id(self.cdb)),
                self.row, self.grammar, self.pkg_version,
                self.report_unfixed)


def _prep_resident(jobs: list, cdb, sink: dict) -> tuple:
    """Dedup + row triage of one store's resident rows:
    ``(reps, members, kept, ranks, rows, host)``."""
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


# ---- the dispatcher (docs/performance.md §8) ----


@dataclass
class _Rows:
    """A segment's rows after dedup and triage, and the four things
    in which the classic and the resident row kinds differ."""

    reps: list                  # one job per distinct dedup key
    members: list               # original job indices behind each rep
    kept: list                  # rep indices on the kernel path
    host: list                  # rep indices the host evaluates
    pack: Callable              # (first, rows, padded) -> host arrays
    evaluate: Callable          # host arrays -> hits (the host engine)
    program: Callable           # mesh -> enqueue(device arrays)
    host_eval: Callable         # rep -> vulnerable (host fallback)


def _classic_rows(jobs: list, sink: dict) -> _Rows:
    reps, members, spaces, rows, host = _prep_classic(jobs, sink)
    for sp in spaces.values():
        sp.finalize()

    def pack(first: int, n: int, padded: int) -> tuple:
        return _pack_classic(rows[first:first + n], spaces, padded)

    def program(mesh):
        if mesh is not None:
            from ..parallel.interval_shard import sharded_interval_hits
            return lambda dev: sharded_interval_hits(mesh, *dev)
        # every operand is this wave's own upload → donated
        from ..ops.intervals import interval_hits_donated
        return lambda dev: interval_hits_donated(*dev)

    return _Rows(reps, members, [r[0] for r in rows], host, pack,
                 lambda arrays: interval_hits_host(*arrays), program,
                 _host_eval)


def _resident_rows(jobs: list, sink: dict) -> _Rows:
    cdb = jobs[0].cdb
    reps, members, kept, ranks, rows, host = \
        _prep_resident(jobs, cdb, sink)

    def pack(first: int, n: int, padded: int) -> tuple:
        pkg_rank = np.zeros(padded, np.int32)
        row_idx = np.zeros(padded, np.int32)
        pkg_rank[:n] = ranks[first:first + n]
        row_idx[:n] = rows[first:first + n]
        return pkg_rank, row_idx

    def evaluate(arrays: tuple):
        pkg_rank, row_idx = arrays
        return interval_hits_host(
            pkg_rank, cdb.v_lo[row_idx], cdb.v_hi[row_idx],
            cdb.s_lo[row_idx], cdb.s_hi[row_idx], cdb.flags[row_idx])

    def program(mesh):
        # staged once per generation (its own db_upload span); the
        # gather operands are donated, the resident tables are
        # shared by every dispatch of the generation and never are
        tables = cdb.device_tables(mesh=mesh)
        if mesh is not None:
            from ..parallel.interval_shard import \
                sharded_interval_hits_resident
            return lambda dev: sharded_interval_hits_resident(
                mesh, *dev, tables)
        from ..ops.intervals import interval_hits_resident_donated
        return lambda dev: interval_hits_resident_donated(*dev, *tables)

    return _Rows(reps, members, kept, host, pack, evaluate, program,
                 lambda job: job.cdb.host_eval(job.row,
                                               job.pkg_version))


def _upload(arrays: tuple, mesh) -> tuple:
    if mesh is not None:
        from ..parallel.interval_shard import shard_rows
        return shard_rows(mesh, *arrays)
    import jax
    return tuple(jax.device_put(a) for a in arrays)


class _Segment:
    """One segment's dispatch: the rows, packed into the wave plan,
    launched at construction; :meth:`collect` fetches the waves FIFO
    and fans hits and host verdicts out through the dedup members."""

    def __init__(self, rows_fn, jobs: list, backend: str, mesh,
                 sink: dict, ring):
        from ..obs.trace import current_span, phase_span
        self.jobs = jobs
        self.sink = sink
        self.ring = ring
        # phase spans from the ring's drain thread parent under the
        # span active at launch (the batch's device span)
        self.ctx_span = current_span()
        self.waves: list = []
        with phase_span("pack", pipeline="detect",
                        jobs=len(jobs)) as psp:
            self.rows = rows = rows_fn(jobs, sink)
            psp.set("unique", len(rows.reps))
            n = len(rows.kept)
            if not n:
                plan = []
            elif backend == "cpu-ref":
                plan = [(0, n, n)]
            else:
                plan = _wave_plan(n)
            packed = [rows.pack(*wave) for wave in plan]
        _book(sink, psp, "dispatch_s")
        if not plan:
            return
        if backend == "cpu-ref":
            # the host engine: one eager evaluation, no device wave
            with phase_span("device_compute", pipeline="detect",
                            kind="interval", rows=n) as csp:
                hits = rows.evaluate(packed[0])
            _book(sink, csp, "device_s", "dispatch_s")
            self.waves.append({"first": 0, "rows": n, "hits": hits})
            return
        run = rows.program(mesh)
        for k, ((first, count, _), arrays) in enumerate(
                zip(plan, packed)):

            def launch(first=first, count=count,
                       arrays=arrays) -> dict:
                with phase_span("h2d_upload", pipeline="detect",
                                bytes=int(sum(a.nbytes
                                              for a in arrays))) as usp:
                    dev = _upload(arrays, mesh)
                _book(sink, usp, "device_s", "dispatch_s")
                return {"first": first, "rows": count,
                        "lazy": run(dev)}

            if ring is not None:
                # launch runs AFTER the ring has a free position, so
                # its bound covers staged HBM, not just bookkeeping
                slot = ring.submit(self._collect_wave, launch=launch,
                                   label=f"interval:w{k}")
                wave = slot.payload
                wave["slot"] = slot
            else:
                wave = launch()
            DETECT_METRICS.note_wave(count)
            self.waves.append(wave)

    def _collect_wave(self, wave: dict) -> None:
        from ..obs.trace import activate_or_null, phase_span
        with activate_or_null(self.ctx_span):
            with phase_span("device_compute", pipeline="detect",
                            kind="interval",
                            rows=wave["rows"]) as sp:
                # materializing blocks until the enqueued kernel
                # finished — on the drain thread this runs
                # concurrently with the next wave's upload, and the
                # span brackets the real device wall
                wave["hits"] = np.asarray(wave["lazy"])
        wave["lazy"] = None          # free the donated output early
        _book(self.sink, sp, "device_s")
        if self.ring is None:
            # collected inline: the caller's own wall (a ring's
            # drain thread overlaps the caller, whose wait is the
            # wave_wait phase in collect)
            _book(self.sink, sp, "dispatch_s")

    def collect(self) -> list:
        from ..obs.trace import phase_span
        rows = self.rows
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
            first = wave["first"]
            for i in np.nonzero(wave["hits"][:wave["rows"]])[0]:
                hit_jobs.extend(rows.members[rows.kept[first + i]])
        out = [self.jobs[i].payload for i in sorted(hit_jobs)]
        # host fallback pairs: exact per-pair evaluation, once per
        # distinct key — the verdict fans out to every duplicate
        host_hits: list = []
        for gi in rows.host:
            if rows.host_eval(rows.reps[gi]):
                host_hits.extend(rows.members[gi])
        out.extend(self.jobs[i].payload for i in sorted(host_hits))
        return out


class IntervalDispatch:
    """Handle returned by :func:`dispatch_jobs_async`; pass it to
    :func:`collect_dispatch` (exactly once) to fetch results."""

    def __init__(self, sink: dict, backend: str):
        self.sink = sink
        self.backend = backend
        self.segments: list = []

    @property
    def waves(self) -> int:
        # the host engine counts as one synchronous dispatch; device
        # segments count their actual launches — a segment whose
        # jobs all host-fell-back launched ZERO waves
        if self.backend == "cpu-ref":
            return 1
        return sum(len(s.waves) for s in self.segments)


def dispatch_jobs_async(jobs: list, backend: str = "tpu",
                        mesh=None, stats: Optional[dict] = None,
                        ring=None) -> IntervalDispatch:
    """Dedup, triage and pack every segment's waves, then enqueue
    each wave without materializing. ``ring`` (a
    runtime.ring.DispatchRing) bounds in-flight waves and collects
    them on its drain thread; without one the waves collect inside
    :func:`collect_dispatch` on the calling thread. ``stats``
    receives this call's ``device_s`` (upload + kernel), its
    ``dispatch_s`` and the dedup counters (``jobs_in`` /
    ``jobs_unique``) — pass one per thread."""
    sink = stats if stats is not None else {}
    sink.update(device_s=0.0, dispatch_s=0.0, jobs_in=0,
                jobs_unique=0)
    handle = IntervalDispatch(sink, backend)
    plain = [j for j in jobs if isinstance(j, PairJob)]
    by_db: dict = {}
    for j in jobs:
        if isinstance(j, ResidentPairJob):
            by_db.setdefault(id(j.cdb), []).append(j)
    groups = [(_classic_rows, plain)] if plain else []
    groups += [(_resident_rows, js) for js in by_db.values()]
    for rows_fn, js in groups:
        handle.segments.append(
            _Segment(rows_fn, js, backend, mesh, sink, ring))
    return handle


def collect_dispatch(handle: IntervalDispatch) -> list:
    """Blocking half: wait for every wave (FIFO), fan hits out to
    the duplicate payloads, evaluate host-fallback pairs. The
    classic rows' payloads come first, then each store's."""
    out: list = []
    for seg in handle.segments:
        out.extend(seg.collect())
    return out


def dispatch_jobs(jobs: list, backend: str = "tpu", mesh=None,
                  stats: Optional[dict] = None) -> list:
    """The blocking form of :func:`dispatch_jobs_async`: the payloads
    of vulnerable pairs, each segment's in batch order."""
    return collect_dispatch(dispatch_jobs_async(
        jobs, backend=backend, mesh=mesh, stats=stats))
