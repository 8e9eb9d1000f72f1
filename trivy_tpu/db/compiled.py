"""Compiled, persistent, TPU-resident advisory tables.

Round-1 rebuilt the rank universe from scratch on every dispatch
(detect/batch._RankSpace), which is O(advisory universe) host work per
scan — fine for fixtures, fatal at trivy-db scale. This module is the
SURVEY §7 step-5 design: flatten the advisory store ONCE at DB-load
time into

  - per-grammar sorted bound-key universes (every constraint parsed
    exactly once, at compile time);
  - int32 interval tables [N, MAX_INTERVALS] in a doubled rank space
    (bound = 2·rank + grammar band offset, exclusivity = ±1);
  - a host-side name-join index bucket → package → row span;
  - per-row metadata for DetectedVulnerability assembly, kept as
    columns of integers over one table of strings (``_RowTable``):
    the table holds no Python object a row, and a row's
    ``(bucket, package, Advisory)`` is built when it is read;
  - host-fallback rows for constraints the interval form can't carry
    (> MAX_INTERVALS alternatives, parse errors, npm prereleases).

At scan time, per-dispatch host work is O(packages): parse each
distinct installed version once, binary-search its rank, gather
candidate rows via the dict join — then the resident-table kernel
(ops.intervals.interval_hits_resident_donated) evaluates every
(package, advisory) pair in waves. The tables are pushed to device once and
reused across scans; ``SwappableStore`` double-buffers them for hot
swaps (reference: pkg/rpc/server/listen.go:71-80).

Persistence: ``save``/``load`` round-trip the arrays, the row
columns and the universes as ONE npz file whose ``strings`` and
``meta`` members are (tagged) JSON — a data-only format (no pickle:
a compiled DB may arrive over the network in the reference's
trivy-db workflow, and the server hot-swaps whatever appears at the
watched path, so deserialization must not be code execution),
written to a temp name and atomically renamed so the hot-swap
watcher can never observe a half-written pair.
"""

from __future__ import annotations

import contextlib

import json
from array import array
import os
import threading
from bisect import bisect_left
from collections.abc import Sequence
from dataclasses import dataclass, field
from itertools import accumulate
from typing import Optional

import numpy as np

from ..ops.intervals import MAX_INTERVALS, NEG_INF, POS_INF
from ..types import DataSource
from ..utils import get_logger
import datetime as _dt

from ..vercmp import get_comparer
from ..vercmp.maven import _PaddedKey
from ..vercmp.rubygems import _GemKey
from ..vercmp.semver import SemverKey
from .store import Advisory, AdvisoryStore

log = get_logger("db.compiled")


@contextlib.contextmanager
def gc_paused():
    """Pause the cyclic collector across bulk object construction,
    restoring the caller's setting (used by compile and the boltdb
    ingest)."""
    import gc
    was_on = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_on:
            gc.enable()

def _eco_grammar() -> dict:
    """ecosystem prefix (before ::) → version grammar, derived from
    the single source of truth in detect.library._TYPES (lazy to
    avoid a circular import through trivy_tpu.db)."""
    from ..detect.library import _TYPES
    return {eco: grammar for eco, grammar in _TYPES.values()}

# OS bucket leading token → distro version grammar (detect/ospkg)
_OS_GRAMMAR = {
    "alpine": "apk",
    "debian": "deb",
    "ubuntu": "deb",
    "amazon": "rpm",
    "oracle": "rpm",
    "alma": "rpm",
    "rocky": "rpm",
    "red": "rpm",           # "Red Hat"
    "centos": "rpm",
    "fedora": "rpm",
    "cbl-mariner": "rpm",
    "photon": "rpm",
    "opensuse": "rpm",
    "suse": "rpm",
}

# row flag bits (0-2 shared with ops.intervals)
F_HAS_VULN = 1
F_FORCE = 2
F_HAS_SEC = 4
F_HOST = 8            # evaluate on host (exact fallback)
F_UNFIXED = 16        # os advisory without FixedVersion


def bucket_grammar(bucket: str) -> Optional[str]:
    if "::" in bucket:
        return _eco_grammar().get(bucket.split("::", 1)[0])
    return _OS_GRAMMAR.get(bucket.split()[0].lower()) if bucket \
        else None


@dataclass
class _Row:
    bucket: str
    pkg: str
    advisory: Advisory
    grammar: str
    vuln_ivs: list = field(default_factory=list)
    sec_ivs: list = field(default_factory=list)
    flags: int = 0


# an Advisory's six list-valued fields, in the order a row's items
# are stored
_LIST_FIELDS = ("vulnerable_versions", "patched_versions",
                "unaffected_versions", "arches", "vendor_ids",
                "content_sets")


class _RowTable(Sequence):
    """The table's per-row metadata as columns: ``table[row]`` is
    ``(bucket, package, Advisory)``, built when the row is read.

    A million rows kept as ``(bucket, pkg, Advisory)`` tuples are
    eight collector-tracked objects a row, and every full collection
    walks all of them (``utils.defer_gc`` runs one a ``scan_boms``
    pass). Here a row is seven integers of one ``int32`` array and
    nothing else, so the table holds a number of tracked objects that
    does not grow with its rows:

    - ``strings``: every distinct string of the table, once, in a
      tuple: the collector stops tracking a tuple of strings the
      first time it meets it, where it would walk a list of them
      in every full collection;
    - ``cols`` ``[N, 7]``: a row's bucket, package, vulnerability id,
      fixed and affected version (indices into ``strings``), its
      form (an index into ``forms``) and where its items start;
    - ``forms`` ``[K, 10]``: the distinct combinations of severity,
      data source (id, name, url in ``strings``; -1 for none) and the
      lengths of the six list fields (``_LIST_FIELDS``): most rows
      share a handful, since most leave arches, vendor ids and
      content sets empty;
    - ``items`` ``[M]``: the elements of every row's lists, row after
      row, as indices into ``strings``.

    Each read gives a fresh ``Advisory`` equal to the one compiled
    in; mutating it changes nothing here. The columns may come from
    a file (``CompiledDB.load``), so they are range-checked once, on
    the way in."""

    __slots__ = ("strings", "cols", "forms", "items", "_forms")

    def __init__(self, strings: list, rows: np.ndarray,
                 forms: np.ndarray, items: np.ndarray):
        rows = np.asarray(rows, np.int32).reshape(-1, 6)
        forms = np.asarray(forms, np.int32).reshape(-1, 10)
        items = np.asarray(items, np.int32).reshape(-1)
        n_str, n_forms = len(strings), len(forms)

        def within(a, lo, hi):
            return a.size == 0 or (a.min() >= lo and a.max() < hi)

        ok = within(rows[:, :5], 0, n_str) \
            and within(rows[:, 5], 0, n_forms) \
            and within(items, 0, n_str) \
            and within(forms[:, 1:4], -1, n_str) \
            and within(forms[:, 4:], 0, len(items) + 1)
        if ok:      # items a row, by its form
            per_row = forms[:, 4:].sum(axis=1, dtype=np.int64)[
                rows[:, 5]]
            ok = int(per_row.sum()) == len(items)
        if not ok:
            raise ValueError("compiled db: row columns out of range "
                             "— rebuild with 'db build'")
        self.strings = tuple(strings)
        self.cols = np.empty((len(rows), 7), np.int32)
        self.cols[:, :6] = rows
        self.cols[:, 6] = np.cumsum(per_row) - per_row
        self.forms = forms
        self.items = items
        # forms decoded once: (severity, data source or None, where
        # each list ends among the row's items or None for no items)
        self._forms = []
        for sev, sid, sname, surl, *counts in forms.tolist():
            ends = tuple(accumulate(counts))
            self._forms.append((
                sev,
                (strings[sid], strings[sname], strings[surl])
                if min(sid, sname, surl) >= 0 else None,
                ends if ends[-1] else None))

    @classmethod
    def build(cls, rows) -> "_RowTable":
        """From an iterable of ``(bucket, package, Advisory)``, which
        is read once and not kept."""
        strings: dict = {}
        forms: dict = {}
        cols, items = array("i"), array("i")

        def s(v: str) -> int:
            return strings.setdefault(v, len(strings))

        for bucket, pkg, adv in rows:
            ds = adv.data_source
            lists = [getattr(adv, f) for f in _LIST_FIELDS]
            form = (adv.severity,
                    *((s(ds.id), s(ds.name), s(ds.url))
                      if ds is not None else (-1, -1, -1)),
                    *map(len, lists))
            cols.extend((s(bucket), s(pkg), s(adv.vulnerability_id),
                         s(adv.fixed_version),
                         s(adv.affected_version),
                         forms.setdefault(form, len(forms))))
            for values in lists:
                items.extend(map(s, values))
        return cls(list(strings),
                   np.frombuffer(cols, np.int32).copy(),
                   np.array(list(forms), np.int32),
                   np.frombuffer(items, np.int32).copy())

    def __len__(self) -> int:
        return len(self.cols)

    def __getitem__(self, row: int) -> tuple:
        strings = self.strings
        bucket, pkg, vid, fixed, affected, form, start = \
            self.cols[row].tolist()
        severity, source, ends = self._forms[form]
        if ends is None:
            vulnerable, patched, unaffected, arches, vendor_ids, \
                content_sets = [], [], [], [], [], []
        else:
            a, b, c, d, e, end = ends
            its = [strings[i] for i in
                   self.items[start:start + end].tolist()]
            vulnerable, patched, unaffected, arches, vendor_ids, \
                content_sets = (its[:a], its[a:b], its[b:c],
                                its[c:d], its[d:e], its[e:])
        return (strings[bucket], strings[pkg], Advisory(
            strings[vid], strings[fixed], strings[affected],
            vulnerable, patched, unaffected, arches, severity,
            vendor_ids,
            DataSource(*source) if source is not None else None,
            content_sets))

    def runs(self):
        """``(bucket, package, range of rows)`` for each run of rows
        that share both, in row order."""
        n = len(self.cols)
        if not n:
            return
        keys = self.cols[:, :2]
        cuts = np.flatnonzero(
            (keys[1:] != keys[:-1]).any(axis=1)) + 1
        starts = [0] + cuts.tolist()
        strings = self.strings
        for (b, p), lo, hi in zip(keys[starts].tolist(), starts,
                                  starts[1:] + [n]):
            yield strings[b], strings[p], range(lo, hi)


_NO_ROWS = range(0)

_GENERATION_LOCK = threading.Lock()
_GENERATION_SEQ = [0]


def _next_generation() -> int:
    """Process-monotonic table generation key: every compile/load
    gets a fresh one, so device buffers, caches and metrics can tell
    "the same tables again" from "a hot-swapped update" without
    hashing gigabytes (docs/performance.md). Shared by the advisory
    DB and the secret DFA table (ops/dfa.py) — one namespace means
    one invalidation story."""
    with _GENERATION_LOCK:
        _GENERATION_SEQ[0] += 1
        return _GENERATION_SEQ[0]


import weakref

# every live ResidentTables instance, for the /metrics residency
# gauges (trivy_tpu_resident_bytes{table,placement}) — weak refs so
# a dropped table (hot-swap, test teardown) leaves no ghost row
_RESIDENT_REGISTRY: "weakref.WeakSet" = weakref.WeakSet()
_RESIDENT_REG_LOCK = threading.Lock()


def _placement_label(key) -> str:
    """A bounded, human-stable label for a placement key: "default",
    "mesh", or "device" — never the repr of a device object (labels
    are /metrics cardinality)."""
    if key == "default":
        return "default"
    if hasattr(key, "devices"):
        return "mesh"
    return "device"


def resident_snapshot() -> list:
    """[{table, placement, bytes, generation}] across every live
    resident table — what ``trivy_tpu_resident_bytes`` serves. Only
    placements currently STAGED count; ``invalidate_device`` drops
    the rows (the superseded HBM is freed when in-flight dispatches
    release it)."""
    with _RESIDENT_REG_LOCK:
        tables = list(_RESIDENT_REGISTRY)
    out = []
    for t in tables:
        with t._device_lock:
            rows = [(key, nbytes)
                    for key, nbytes in t._device_bytes.items()]
            gen = t.generation
        for key, nbytes in rows:
            out.append({"table": t._TABLE,
                        "placement": _placement_label(key),
                        "bytes": int(nbytes),
                        "generation": gen})
    out.sort(key=lambda r: (r["table"], r["placement"],
                            r["generation"]))
    return out


def prewarm_resident() -> list:
    """Stage every live resident table's default placement NOW —
    the HBM-upload half of a joining replica's prewarm
    (docs/serving.md "Elastic lifecycle"). ``device_tables`` is
    idempotent per (generation, placement), so an already-staged
    table is a no-op; a table whose upload fails (device pressure
    mid-join) is skipped — prewarm is an optimization, the first
    dispatch will stage it like before. Returns
    ``[{table, generation, staged}]`` for the boot log."""
    with _RESIDENT_REG_LOCK:
        tables = list(_RESIDENT_REGISTRY)
    out = []
    for t in sorted(tables, key=lambda x: x._TABLE):
        row = {"table": t._TABLE, "generation": t.generation,
               "staged": True}
        try:
            t.device_tables()
        except (RuntimeError, OSError, ValueError) as e:
            log.warning("prewarm staging skipped %s: %r",
                        t._TABLE, e)
            row["staged"] = False
        out.append(row)
    return out


class ResidentTables:
    """Device-residency plumbing shared by every table that lives in
    HBM across dispatches: the compiled advisory DB below and the
    secret scanner's DFA table (trivy_tpu.ops.dfa).

    Contract: ``device_tables(placement)`` stages the arrays from
    ``_resident_arrays()`` ONCE per (generation, placement) and
    hands back the same device buffers on every later call;
    ``invalidate_device()`` drops them on hot swap (in-flight
    dispatches keep their references until they finish — jax frees
    the HBM when the last one drops). ``placement`` is None (default
    device), a ``jax.sharding.Mesh`` (replicated to every chip), or
    a single ``jax.Device`` (the async sharded sieve places the DFA
    table per data shard). Upload/dispatch amortization is counted
    in ``device_stats()`` and mirrored to the subclass's metrics via
    the ``_note_*`` hooks."""

    _UPLOAD_SPAN = "db_upload"
    _PIPELINE = "detect"       # whose phase rows the upload joins
    _TABLE = "advisory_db"      # /metrics residency label

    def _init_resident(self) -> None:
        self.generation = _next_generation()
        self._device: dict = {}
        self._device_bytes: dict = {}   # placement -> staged bytes
        self._device_lock = threading.Lock()
        self._device_stats = {"uploads": 0, "upload_bytes": 0,
                              "dispatches": 0, "invalidations": 0}
        with _RESIDENT_REG_LOCK:
            _RESIDENT_REGISTRY.add(self)

    # --- subclass hooks ---

    def _resident_arrays(self) -> tuple:
        raise NotImplementedError

    def _span_attrs(self) -> dict:
        return {}

    def _note_upload(self, nbytes: int) -> None:
        pass

    def _note_dispatch(self) -> None:
        pass

    def _note_invalidation(self) -> None:
        pass

    # --- the shared machinery ---

    def device_tables(self, placement=None) -> tuple:
        import jax

        from ..obs.trace import phase_span
        key = "default" if placement is None else placement
        with self._device_lock:
            placed = self._device.get(key)
            if placed is None:
                arrs = self._resident_arrays()
                nbytes = int(sum(a.nbytes for a in arrs))
                with phase_span(self._UPLOAD_SPAN,
                                pipeline=self._PIPELINE,
                                bytes=nbytes,
                                generation=self.generation,
                                **self._span_attrs()):
                    if placement is None:
                        placed = tuple(jax.device_put(a)
                                       for a in arrs)
                    elif hasattr(placement, "devices"):   # a Mesh
                        from ..parallel.interval_shard import \
                            replicate_tables
                        placed = replicate_tables(placement, arrs)
                    else:                          # a single Device
                        placed = tuple(
                            jax.device_put(a, placement)
                            for a in arrs)
                self._device[key] = placed
                self._device_bytes[key] = nbytes
                self._device_stats["uploads"] += 1
                self._device_stats["upload_bytes"] += nbytes
                self._note_upload(nbytes)
            self._device_stats["dispatches"] += 1
        self._note_dispatch()
        return placed

    def invalidate_device(self) -> None:
        """Drop this generation's device buffers (hot-swap path)."""
        with self._device_lock:
            if not self._device:
                return
            self._device.clear()
            self._device_bytes.clear()
            self._device_stats["invalidations"] += 1
        self._note_invalidation()

    def device_stats(self) -> dict:
        """Upload-amortization numbers for metrics: how many
        dispatches each HBM upload served."""
        with self._device_lock:
            out = dict(self._device_stats)
        out["generation"] = self.generation
        out["amortization"] = round(
            out["dispatches"] / out["uploads"], 2) \
            if out["uploads"] else 0.0
        return out


class CompiledDB(ResidentTables):
    """Flattened advisory tables + join index. Read-only after
    ``compile`` / ``load``."""

    def __init__(self):
        # per row: (bucket, pkg, Advisory), built when read
        self.rows_meta = _RowTable([], (), (), ())
        self.row_grammar: tuple = ()    # per row: grammar name
        self.v_lo = self.v_hi = self.s_lo = self.s_hi = None
        self.flags = None               # np.int32 [N]
        self.index: dict = {}           # bucket → {pkg → range of rows}
        self.universe: dict = {}        # grammar → (keys list, base)
        self.vulnerabilities: dict = {}
        self.data_sources: dict = {}
        self.stats: dict = {}
        self._init_resident()
        self._parse_cache: dict = {}

    # ---- compile ----

    @classmethod
    def compile(cls, store: AdvisoryStore) -> "CompiledDB":
        # the million ``_Row`` and interval objects that live until
        # the columns are built make the cyclic collector
        # quadratic-ish (2.3x at 1M advisories); nothing cyclic is
        # created here, and none of them outlives the call
        with gc_paused():
            return cls._compile(store)

    @classmethod
    def _compile(cls, store: AdvisoryStore) -> "CompiledDB":
        self = cls()
        self.vulnerabilities = dict(store.vulnerabilities)
        self.data_sources = dict(store.data_sources)

        rows: list = []
        n_host = 0
        for bucket in sorted(store.buckets):
            grammar = bucket_grammar(bucket)
            for pkg in sorted(store.buckets[bucket]):
                for adv in store.get(bucket, pkg):
                    row = self._compile_row(bucket, pkg, adv, grammar)
                    n_host += bool(row.flags & F_HOST)
                    rows.append(row)

        # per-grammar bound universes with disjoint band offsets
        bounds: dict = {}
        for row in rows:
            for iv in row.vuln_ivs + row.sec_ivs:
                b = bounds.setdefault(row.grammar, set())
                if iv.lo is not None:
                    b.add(iv.lo)
                if iv.hi is not None:
                    b.add(iv.hi)
        base = 1
        for grammar in sorted(bounds):
            keys = sorted(bounds[grammar])
            self.universe[grammar] = (keys, base)
            base += 2 * len(keys) + 4

        N = len(rows)
        self.v_lo = np.full((N, MAX_INTERVALS), POS_INF, np.int32)
        self.v_hi = np.full((N, MAX_INTERVALS), NEG_INF, np.int32)
        self.s_lo = np.full((N, MAX_INTERVALS), POS_INF, np.int32)
        self.s_hi = np.full((N, MAX_INTERVALS), NEG_INF, np.int32)
        self.flags = np.zeros(N, np.int32)
        for i, row in enumerate(rows):
            self.flags[i] = row.flags
            if row.flags & F_HOST:
                continue
            for j, iv in enumerate(row.vuln_ivs):
                self.v_lo[i, j], self.v_hi[i, j] = \
                    self._encode(row.grammar, iv)
            for j, iv in enumerate(row.sec_ivs):
                self.s_lo[i, j], self.s_hi[i, j] = \
                    self._encode(row.grammar, iv)
        self._set_rows(_RowTable.build(
            (r.bucket, r.pkg, r.advisory) for r in rows))
        self.row_grammar = tuple(r.grammar for r in rows)

        self.stats = {
            "rows": N,
            "host_fallback_rows": n_host,
            "host_fallback_rate": (n_host / N) if N else 0.0,
            "grammars": {g: len(k)
                         for g, (k, _) in self.universe.items()},
        }
        log.info("compiled advisory db: %d rows, %d host-fallback "
                 "(%.3f%%)", N, n_host,
                 100.0 * self.stats["host_fallback_rate"])
        return self

    def _set_rows(self, table: _RowTable) -> None:
        """Install the row columns and the name-join index over
        them. Rows are appended bucket by bucket and package by
        package, so a package's rows are one run and the index holds
        a ``range`` a package, not a list."""
        self.rows_meta = table
        self.index = {}
        for bucket, pkg, rows in table.runs():
            pkgs = self.index.setdefault(bucket, {})
            if pkg in pkgs:
                raise ValueError(
                    f"compiled db: rows of {bucket!r} {pkg!r} are "
                    f"not contiguous — rebuild with 'db build'")
            pkgs[pkg] = rows

    def _compile_row(self, bucket: str, pkg: str, adv: Advisory,
                     grammar: Optional[str]) -> _Row:
        row = _Row(bucket=bucket, pkg=pkg, advisory=adv,
                   grammar=grammar or "generic")
        is_ospkg = not (adv.vulnerable_versions or
                        adv.patched_versions or
                        adv.unaffected_versions)
        # the unfixed marker survives host fallback so the driver's
        # report_unfixed filter still applies (detect/batch._prep_resident)
        unfixed = F_UNFIXED if is_ospkg and \
            adv.fixed_version == "" else 0
        if grammar is None:
            row.flags = F_HOST | unfixed
            return row
        comparer = get_comparer(grammar)
        try:
            if is_ospkg:
                self._compile_ospkg(row, comparer)
            else:
                self._compile_library(row, comparer)
        except ValueError:
            row.vuln_ivs, row.sec_ivs = [], []
            row.flags = F_HOST | unfixed
        return row

    def _compile_library(self, row: _Row, comparer) -> None:
        adv = row.advisory
        if any(v == "" for v in
               list(adv.vulnerable_versions) +
               list(adv.patched_versions)):
            row.flags = F_FORCE
            return
        from ..detect.ccache import INTERVAL_CACHE
        if adv.vulnerable_versions:
            row.flags |= F_HAS_VULN
            for c in " || ".join(adv.vulnerable_versions).split("||"):
                if not c.strip():
                    raise ValueError("empty constraint alternative")
                row.vuln_ivs.extend(INTERVAL_CACHE.intervals(
                    row.grammar, comparer, c))
        secure = list(adv.patched_versions) + \
            list(adv.unaffected_versions)
        if secure:
            row.flags |= F_HAS_SEC
            for c in " || ".join(secure).split("||"):
                if not c.strip():
                    raise ValueError("empty constraint alternative")
                row.sec_ivs.extend(INTERVAL_CACHE.intervals(
                    row.grammar, comparer, c))
        if len(row.vuln_ivs) > MAX_INTERVALS or \
                len(row.sec_ivs) > MAX_INTERVALS:
            row.vuln_ivs, row.sec_ivs = [], []
            row.flags = F_HOST

    def _compile_ospkg(self, row: _Row, comparer) -> None:
        from ..vercmp.base import Interval
        adv = row.advisory
        lo = comparer.parse(adv.affected_version) \
            if adv.affected_version else None
        if adv.fixed_version == "":
            row.vuln_ivs = [Interval(lo=lo)]
            row.flags = F_HAS_VULN | F_UNFIXED
        else:
            row.vuln_ivs = [Interval(
                lo=lo, hi=comparer.parse(adv.fixed_version),
                hi_incl=False)]
            row.flags = F_HAS_VULN

    def _encode(self, grammar: str, iv) -> tuple:
        keys, base = self.universe[grammar]
        if iv.lo is None:
            lo = NEG_INF
        else:
            lo = base + 2 * bisect_left(keys, iv.lo) + \
                (0 if iv.lo_incl else 1)
        if iv.hi is None:
            hi = POS_INF
        else:
            hi = base + 2 * bisect_left(keys, iv.hi) - \
                (0 if iv.hi_incl else 1)
        return lo, hi

    # ---- scan-time API ----

    def pkg_rank(self, grammar: str, version: str) -> Optional[int]:
        """Rank an installed version in its grammar band. Bound keys
        sit at even offsets; a version strictly between bounds gets
        the odd offset below the next bound — containment is then
        EXACT for bounds-only universes. None on parse failure."""
        cached = self._parse_cache.get((grammar, version))
        if cached is not None:
            return cached if cached != -1 else None
        keys, base = self.universe.get(grammar, ([], 1))
        try:
            key = get_comparer(grammar).parse(version)
        except ValueError:
            self._parse_cache[(grammar, version)] = -1
            return None
        i = bisect_left(keys, key)
        if i < len(keys) and keys[i] == key:
            r = base + 2 * i
        else:
            r = base + 2 * i - 1
        self._parse_cache[(grammar, version)] = r
        return r

    def candidate_rows(self, bucket: str, pkg: str) -> Sequence:
        pkgs = self.index.get(bucket)
        return pkgs.get(pkg, _NO_ROWS) if pkgs is not None \
            else _NO_ROWS

    def _prefix_index(self) -> dict:
        """ecosystem prefix ("pip::") → bucket list, built lazily so
        prefix joins are O(1) per package, not O(buckets)."""
        if not hasattr(self, "_prefixes"):
            prefixes: dict = {}
            for bucket in self.index:
                if "::" in bucket:
                    pre = bucket.split("::", 1)[0] + "::"
                    prefixes.setdefault(pre, []).append(bucket)
            self._prefixes = prefixes
        return self._prefixes

    def candidate_rows_prefix(self, prefix: str, pkg: str) -> list:
        buckets = self._prefix_index().get(prefix)
        if buckets is None:               # non-ecosystem prefix query
            buckets = [b for b in self.index if b.startswith(prefix)]
        out = []
        for bucket in buckets:
            out.extend(self.index[bucket].get(pkg, _NO_ROWS))
        return out

    def host_eval(self, row: int, version: str) -> bool:
        """Exact host evaluation for F_HOST rows — must mirror the
        classic paths (base.is_vulnerable / Driver._is_vulnerable)."""
        from ..vercmp.base import is_vulnerable
        bucket, _pkg, adv = self.rows_meta[row]
        grammar = self.row_grammar[row]
        if grammar == "generic":
            grammar = bucket_grammar(bucket) or "semver"
        comparer = get_comparer(grammar)
        if adv.vulnerable_versions or adv.patched_versions or \
                adv.unaffected_versions:
            return is_vulnerable(comparer, version,
                                 adv.vulnerable_versions,
                                 adv.patched_versions,
                                 adv.unaffected_versions)
        # ospkg: affected-version gate first (alpine "introduced in");
        # a parse error rejects, as in Driver._is_vulnerable
        if adv.affected_version:
            try:
                if comparer.parse(adv.affected_version) > \
                        comparer.parse(version):
                    return False
            except ValueError:
                return False
        if adv.fixed_version == "":
            return True
        try:
            return comparer.compare(version, adv.fixed_version) < 0
        except ValueError:
            return False

    # ---- device residency (ResidentTables hooks) ----
    #
    # device_tables(mesh) pushes (v_lo, v_hi, s_lo, s_hi, flags) to
    # the default device (or replicated across the mesh) ONCE per
    # (generation, placement); invalidate_device (hot-swap / ``trivy
    # db update``) drops the buffers so the superseded generation's
    # HBM is reclaimed as soon as its last reader finishes.

    def device_tables(self, mesh=None) -> tuple:
        return super().device_tables(mesh)

    def _resident_arrays(self) -> tuple:
        return (self.v_lo, self.v_hi, self.s_lo, self.s_hi,
                self.flags)

    def _span_attrs(self) -> dict:
        return {"rows": int(len(self.flags))}

    def _note_upload(self, nbytes: int) -> None:
        from ..detect.metrics import DETECT_METRICS
        DETECT_METRICS.note_db_upload(nbytes)

    def _note_dispatch(self) -> None:
        from ..detect.metrics import DETECT_METRICS
        DETECT_METRICS.inc("resident_dispatches")

    def _note_invalidation(self) -> None:
        from ..detect.metrics import DETECT_METRICS
        DETECT_METRICS.inc("db_invalidations")

    # ---- content identity (trivy_tpu.memo) ----

    def content_fingerprint(self) -> str:
        """Content hash of the compiled tables + advisory records —
        the cross-process "DB generation" the findings memo keys on
        (``generation`` is process-monotonic and says nothing about
        content). Cached: a CompiledDB is read-only after
        compile/load."""
        fp = getattr(self, "_content_fp", None)
        if fp is None:
            import hashlib
            h = hashlib.sha256()
            for a in (self.v_lo, self.v_hi, self.s_lo, self.s_hi,
                      self.flags):
                if a is not None:
                    h.update(np.ascontiguousarray(a).tobytes())
            # the bytes of json.dumps over the list of every row's
            # [bucket, pkg, record], a few thousand rows at a time:
            # the value a table of row tuples gave, with no million
            # records alive at once
            n = len(self.rows_meta)
            h.update(b"[")
            with gc_paused():       # nothing cyclic; see compile
                for lo in range(0, n, 4096):
                    part = json.dumps(
                        [[b, p, _adv_enc(a)] for b, p, a in map(
                            self.rows_meta.__getitem__,
                            range(lo, min(lo + 4096, n)))])
                    h.update((", " if lo else "").encode()
                             + part[1:-1].encode())
            h.update(b"]")
            fp = self._content_fp = h.hexdigest()[:32]
        return fp

    # ---- enrichment reads (db.Config parity) ----

    def get_vulnerability(self, vuln_id: str):
        from .store import VulnerabilityDetail
        v = self.vulnerabilities.get(vuln_id)
        if v is None:
            return None
        return VulnerabilityDetail.from_dict(vuln_id, v)

    # ---- persistence ----
    # (tagged-JSON helpers for save/load live at module scope below)

    def save(self, path: str) -> None:
        """Write ``path + ".npz"`` atomically (temp file + rename).

        The row columns ride as array members of their own (``rows``,
        ``forms``, ``items``, ``row_grammar``) beside the interval
        tables, their strings as the JSON list ``strings``;
        universes, vulnerability details, data sources and stats ride
        in ``meta`` as tagged JSON (see ``_enc_key``). A single file
        means the DBWorker's mtime check can never pair new arrays
        with stale metadata."""
        table = self.rows_meta
        grammars = sorted(set(self.row_grammar))
        if len(grammars) > 256:         # a uint8 a row
            raise ValueError("compiled db: more than 256 grammars")
        code = {g: i for i, g in enumerate(grammars)}
        meta = {
            "grammars": grammars,
            "universe": {g: [[_enc_key(k) for k in keys], base]
                         for g, (keys, base) in self.universe.items()},
            "vulnerabilities": self.vulnerabilities,
            "data_sources": self.data_sources,
            "stats": self.stats,
        }

        def blob(obj, **kw) -> np.ndarray:
            return np.frombuffer(json.dumps(obj, **kw).encode(),
                                 np.uint8)

        tmp = path + ".npz.tmp"
        with open(tmp, "wb") as f:
            np.savez_compressed(
                f, v_lo=self.v_lo, v_hi=self.v_hi,
                s_lo=self.s_lo, s_hi=self.s_hi, flags=self.flags,
                rows=np.ascontiguousarray(table.cols[:, :6]),
                forms=table.forms, items=table.items,
                row_grammar=np.fromiter(
                    map(code.__getitem__, self.row_grammar),
                    np.uint8, len(self.row_grammar)),
                strings=blob(table.strings),
                meta=blob(meta, default=_json_default))
        os.replace(tmp, path + ".npz")

    @classmethod
    def load(cls, path: str) -> "CompiledDB":
        self = cls()
        arrs = np.load(path + ".npz")
        self.v_lo, self.v_hi = arrs["v_lo"], arrs["v_hi"]
        self.s_lo, self.s_hi = arrs["s_lo"], arrs["s_hi"]
        self.flags = arrs["flags"]
        if "meta" not in arrs:
            raise ValueError(
                f"{path}.npz has no meta member — rebuild with "
                f"'db build' (pre-data-only-format file?)")
        d = json.loads(arrs["meta"].tobytes().decode(),
                       object_hook=_json_hook)
        if "rows_meta" in d:
            # the format before the row columns: a JSON record a row
            # in ``meta``, converted here once a load
            log.warning("%s.npz is in the old compiled-db format "
                        "(a JSON record a row); it loads, slowly — "
                        "rebuild with 'db build'", path)
            with gc_paused():
                table = _RowTable.build(
                    (b, p, _adv_dec(a)) for b, p, a in d["rows_meta"])
            self.row_grammar = tuple(d["row_grammar"])
        else:
            table = _RowTable(
                json.loads(arrs["strings"].tobytes().decode()),
                arrs["rows"], arrs["forms"], arrs["items"])
            grammars = d["grammars"]
            self.row_grammar = tuple(
                grammars[i] for i in arrs["row_grammar"].tolist())
        if len(self.row_grammar) != len(table) \
                or len(self.flags) != len(table):
            raise ValueError(f"{path}.npz: row columns and interval "
                             f"tables differ in length")
        self._set_rows(table)
        self.universe = {g: ([_dec_key(k) for k in keys], base)
                         for g, (keys, base) in d["universe"].items()}
        self.vulnerabilities = d["vulnerabilities"]
        self.data_sources = d["data_sources"]
        self.stats = d["stats"]
        return self


# ---- data-only persistence helpers ---------------------------------
#
# Version-grammar parse keys are nested tuples, sometimes wrapped in a
# grammar's own comparable class (SemverKey, maven _PaddedKey,
# rubygems _GemKey). bisect at scan time compares freshly parsed keys
# against persisted ones, so the round-trip must restore EXACT types —
# hence a tagged encoding over a closed class set that fails loudly on
# anything new instead of silently pickling it.

def _enc_key(v):
    if isinstance(v, SemverKey):
        return ["sv"] + [_enc_key(x) for x in v]
    if isinstance(v, _PaddedKey):
        return ["mv", _enc_key(v.toks)]
    if isinstance(v, _GemKey):
        return ["gem", _enc_key(v.segs)]
    if isinstance(v, tuple):
        return ["t"] + [_enc_key(x) for x in v]
    if isinstance(v, list):
        return ["l"] + [_enc_key(x) for x in v]
    if v is None or isinstance(v, (int, float, str, bool)):
        return v
    raise TypeError(f"unencodable universe key part: {type(v)}")


def _dec_key(v):
    if not isinstance(v, list):
        return v
    tag, rest = v[0], v[1:]
    if tag == "sv":
        return SemverKey(tuple(_dec_key(x) for x in rest))
    if tag == "mv":
        return _PaddedKey(_dec_key(rest[0]))
    if tag == "gem":
        return _GemKey(_dec_key(rest[0]))
    if tag == "t":
        return tuple(_dec_key(x) for x in rest)
    if tag == "l":
        return [_dec_key(x) for x in rest]
    raise ValueError(f"bad universe key tag: {tag!r}")


def _json_default(o):
    """Vulnerability detail dicts come from YAML fixtures, which parse
    ISO timestamps into datetime (and unquoted day-only values into
    date) — tag both for the round-trip."""
    if isinstance(o, _dt.datetime):
        return {"$dt": o.isoformat()}
    if isinstance(o, _dt.date):
        return {"$d": o.isoformat()}
    raise TypeError(f"unencodable compiled-db value: {type(o)}")


def _json_hook(d: dict):
    if len(d) == 1:
        if "$dt" in d:
            return _dt.datetime.fromisoformat(d["$dt"])
        if "$d" in d:
            return _dt.date.fromisoformat(d["$d"])
    return d


def _adv_enc(a: Advisory) -> list:
    ds = a.data_source
    return [a.vulnerability_id, a.fixed_version, a.affected_version,
            a.vulnerable_versions, a.patched_versions,
            a.unaffected_versions, a.arches, a.severity, a.vendor_ids,
            [ds.id, ds.name, ds.url] if ds is not None else None,
            a.content_sets]


def _adv_dec(v: list) -> Advisory:
    ds = DataSource(id=v[9][0], name=v[9][1], url=v[9][2]) \
        if v[9] is not None else None
    return Advisory(
        vulnerability_id=v[0], fixed_version=v[1],
        affected_version=v[2], vulnerable_versions=v[3],
        patched_versions=v[4], unaffected_versions=v[5],
        arches=v[6], severity=v[7], vendor_ids=v[8], data_source=ds,
        content_sets=v[10] if len(v) > 10 else [])


class SwappableStore:
    """Double-buffered advisory DB holder (reference: the RW-waitgroup
    pair gating the server's hourly DB update, listen.go:54-83).

    Readers take ``current()`` under a shared lock; ``swap`` installs
    a freshly compiled DB after in-flight scans drain. On TPU the old
    device tables stay alive until their last reader finishes, then
    get garbage-collected — the new tables are staged with
    ``device_tables()`` BEFORE the swap so scans never wait on the
    transfer."""

    def __init__(self, db: Optional[CompiledDB] = None):
        self._db = db
        self._lock = threading.Lock()
        self._readers = 0
        self._no_readers = threading.Condition(self._lock)
        # swap hooks (db/lifecycle.attach_memo): called AFTER a new
        # generation installs, with (old, new) — the findings memo
        # registers its delta re-match here
        self._swap_hooks: list = []

    def add_swap_hook(self, fn) -> "SwappableStore":
        """Register ``fn(old_db, new_db)`` to run after every swap.
        Hook failures are logged, never raised — a broken observer
        must not wedge the DB update."""
        self._swap_hooks.append(fn)
        return self

    def remove_swap_hook(self, fn) -> None:
        try:
            self._swap_hooks.remove(fn)
        except ValueError:
            pass

    def acquire(self) -> CompiledDB:
        with self._lock:
            self._readers += 1
            return self._db

    def release(self) -> None:
        with self._lock:
            self._readers -= 1
            if self._readers == 0:
                self._no_readers.notify_all()

    def current(self) -> CompiledDB:
        with self._lock:
            return self._db

    def swap(self, new_db: CompiledDB, stage: bool = True) -> None:
        if stage and new_db.v_lo is not None and len(new_db.v_lo):
            try:
                new_db.device_tables()      # stage HBM copy up front
            except Exception:               # no device available
                pass
        with self._lock:
            while self._readers:
                self._no_readers.wait()
            old, self._db = self._db, new_db
        # the superseded generation's resident buffers are explicitly
        # invalidated (``trivy db update`` lifecycle): dispatches
        # already holding the tuple finish on it, new dispatches key
        # against the new generation, and the old HBM frees as soon
        # as the last in-flight reference drops. getattr: the holder
        # also fronts plain AdvisoryStores (no device residency)
        drop = getattr(old, "invalidate_device", None)
        if drop is not None and old is not new_db:
            drop()
        if old is not new_db:
            for fn in list(self._swap_hooks):
                try:
                    fn(old, new_db)
                except Exception as e:      # noqa: BLE001
                    log.warning("swap hook %r failed: %r", fn, e)
