"""Multi-pattern DFA engine for the secret sieve — compile once,
scan everything in one dispatch.

The round-5 sieve matched rule literals truncated to 8 bytes (one
masked-word compare per code) and left every windowed rule's real
semantics to the host. This module is the Hyperscan-style step
(ROADMAP item 2): the whole rule corpus — full-length gate keywords,
anchor literals, and the provably-finite fixed subchains of the
windowed patterns from ``secret/rx`` — compiles into ONE shared
automaton whose banded transition table is resident in HBM next to
the advisory tables, and a single kernel pass emits per-(segment,
pattern) position bitmasks.

Automaton shape. Every pattern is a *fixed chain*: states 1..k where
state ``i`` is reached from ``i-1`` iff the input byte lies in the
state's byte class. The transition table is therefore banded —
``T[s, c] ∈ {0, s+1}`` — and that band is what makes the engine
TPU-native: instead of walking ``state = T[state, byte]`` serially
(the gather-DFA measured 2.3 MB/s — gathers do not vectorize on the
VPU, see ops/keywords.py), the kernel evaluates EVERY state's band
transition in parallel per text position: a chain of k classes ends
at position t iff all k membership tests pass at t-k+1..t. Literal
runs collapse to masked sliding-window word compares (8 states per
compare) and same-class runs collapse to log-doubling erosion
(ops/runs.py), so the per-byte work is elementwise compares at HBM
rate, not K serial lookups.

Soundness. The compiler only ever OVER-approximates: a pattern hit
is necessary for the rule's Python ``re`` to match, never sufficient
— every hit is re-verified by the CPU-exact scanner, and a miss is a
proof the rule cannot fire (secret/rx/parser.py builds the AST as an
exact-or-superset byte model; boundaries are ε; Unicode-aware units
become variable atoms that break chains instead of lying about byte
widths). Case: literal patterns match on ASCII-lowercased text
(superset of any caseful literal; exact for the case-insensitive
keyword gate), class memberships run on raw bytes with the AST's own
folding.

Residency: ``DfaTable`` shares the generation/invalidation machinery
of the compiled advisory DB (db/compiled.py ResidentTables) — the
packed band arrays upload once per (rule-set hash, placement) with a
``dfa_upload`` span, and ``/metrics`` reports the amortization
(secret/metrics.py).
"""

from __future__ import annotations

import hashlib
import math
import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# NOTE: the sieve kernels donate their per-batch segment buffer
# (freed as soon as the kernel consumes it — the async-runtime
# slot-reuse contract); the uint16 mask output cannot alias the
# uint8 segment input, so XLA's "Some donated buffers were not
# usable" aliasing advisory is expected. Filtered at the
# application level (cli/benchmark/pytest.ini), never here — see
# ops/intervals.py.

from ..db.compiled import ResidentTables
from .keywords import (CODE_CHUNK, MAX_CODE_LEN, N_BLOCKS, SIEVE_CAP,
                       pack_code, pad_batch)
from .program import DeviceProgram
from .runs import RunSpec

MAX_LIT_BYTES = 32        # literal patterns: up to 4 masked words
MAX_CHAIN_LEN = 48        # fixed-chain cap — bounds segment overlap
MIN_CHAIN_BITS = 24.0     # selectivity floor to keep a chain
MAX_CLASS_RANGES = 8      # wider classes get gap-merged (superset)
REP_EXPAND_CAP = 96       # {m} repeat expansion cap, in positions

ALL_BYTES = frozenset(range(256))


# ---------------------------------------------------------------------
# chain extraction: rx AST → fixed byte-class chains
# ---------------------------------------------------------------------

def _lower(b: int) -> int:
    return b + 32 if 65 <= b <= 90 else b


def _atoms(node) -> list:
    """Flatten an rx AST into a list of atoms: a tuple of per-byte
    classes (a FIXED stretch — every match threads through exactly
    these positions) or None (a VARIABLE stretch — chain breaker).
    Zero-width nodes vanish. Always an over-approximation: the
    fixed atoms are mandatory contiguous byte positions of every
    match of ``node``."""
    from ..secret.rx.parser import Alt, Boundary, Cat, Empty, Lit, Rep
    if isinstance(node, (Boundary, Empty)):
        return []
    if isinstance(node, Lit):
        # Unicode-aware units consume 1-4 bytes — variable
        return [(node.bytes,)] if node.ascii_only else [None]
    if isinstance(node, Cat):
        out: list = []
        for p in node.parts:
            out.extend(_atoms(p))
        return out
    if isinstance(node, Rep):
        sub = _atoms(node.node)
        if not sub:
            return []                       # repeat of zero-width
        if node.max is not None and node.min == node.max \
                and all(a is not None for a in sub):
            total = node.min * sum(len(a) for a in sub)
            if total <= REP_EXPAND_CAP:
                return [a for _ in range(node.min) for a in sub]
        return [None]
    if isinstance(node, Alt):
        flats = []
        for o in node.options:
            sub = _atoms(o)
            if any(a is None for a in sub):
                return [None]
            flats.append(tuple(c for a in sub for c in a))
        flats = [f for f in flats if f] or [()]
        if all(len(f) == len(flats[0]) for f in flats) \
                and len(flats) == len(node.options):
            # equal-length branches: positionwise class union is a
            # fixed superset (e.g. (test_|live_), (AKIA|ASIA|...))
            n = len(flats[0])
            return [tuple(frozenset().union(*(f[i] for f in flats))
                          for i in range(n))] if n else []
        return [None]
    raise TypeError(node)


def _bits(cls: frozenset) -> float:
    """Selectivity of one position in bits; case-pairs matched on
    lowered text count their folded width."""
    lows = {_lower(b) for b in cls}
    width = 2 * len(lows) if len(lows) < len(cls) or any(
        97 <= b <= 122 for b in lows) else len(cls)
    return math.log2(256 / min(256, max(1, width)))


def best_fixed_chain(node) -> Optional[tuple]:
    """The most selective fixed byte-class window (≤ MAX_CHAIN_LEN
    positions) that every match of ``node`` must contain
    contiguously — or None when nothing clears MIN_CHAIN_BITS.
    Returns a tuple of frozenset classes."""
    runs: list = []
    cur: list = []
    for a in _atoms(node):
        if a is None:
            if cur:
                runs.append(cur)
            cur = []
        else:
            cur.extend(a)
    if cur:
        runs.append(cur)
    best, best_score = None, 0.0
    for run in runs:
        bits = [_bits(c) for c in run]
        n = len(run)
        w = min(n, MAX_CHAIN_LEN)
        # best score window of width ≤ w (prefix sums)
        pre = [0.0]
        for b in bits:
            pre.append(pre[-1] + b)
        for i in range(n - w + 1) if n else ():
            score = pre[i + w] - pre[i]
            if score > best_score:
                best, best_score = tuple(run[i:i + w]), score
    if best is None or best_score < MIN_CHAIN_BITS:
        return None
    return best


def _merge_ranges(ranges: tuple) -> tuple:
    """Cap a class's range list at MAX_CLASS_RANGES by repeatedly
    merging the smallest gap — a byteset SUPERSET, so memberships
    stay a sound over-approximation."""
    rs = [list(r) for r in ranges]
    while len(rs) > MAX_CLASS_RANGES:
        gaps = [(rs[i + 1][0] - rs[i][1], i)
                for i in range(len(rs) - 1)]
        _, i = min(gaps)
        rs[i][1] = rs[i + 1][1]
        del rs[i + 1]
    return tuple((lo, hi) for lo, hi in rs)


def chain_units(classes: tuple) -> tuple:
    """Compile a fixed class chain into the band encoding the kernel
    evaluates: runs of literal-exact positions become ("lit", bytes)
    (masked word compares on lowered text); CONSECUTIVE class
    positions collapse into one ("run", ranges, n) over their byte
    UNION — a further sound over-approximation (any string matching
    the positioned classes is n bytes drawn from the union) that
    keeps the per-chain kernel work at one membership + one
    log-doubling erosion per run instead of one per position.
    Per-position unions rarely cost selectivity: the corpus's class
    stretches are token bodies ([A-Z0-9]{16}, hex{32}) whose union
    is the stretch's own alphabet."""
    units: list = []
    lit: list = []
    run: list = []               # [union byteset, length]

    def flush_lit():
        nonlocal lit
        if lit:
            units.append(("lit", bytes(lit)))
            lit = []

    def flush_run():
        nonlocal run
        if run:
            ranges = _merge_ranges(
                RunSpec.from_byteset(frozenset(run[0]), 1).ranges)
            units.append(("run", ranges, run[1]))
            run = []

    for cls in classes:
        lows = {_lower(b) for b in cls}
        if len(lows) == 1 and len(cls) <= 2:
            flush_run()
            lit.append(next(iter(lows)))
            continue
        flush_lit()
        if run:
            run = [run[0] | cls, run[1] + 1]
        else:
            run = [set(cls), 1]
    flush_lit()
    flush_run()
    return tuple(units)


def chain_len(units: tuple) -> int:
    return sum(len(u[1]) if u[0] == "lit" else u[2] for u in units)


# ---------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------

@dataclass(frozen=True)
class _LitGroup:
    chunks: int          # masked words per literal
    start: int           # first pattern column of this group
    count: int           # literals in the group


class DfaTable(ResidentTables):
    """One compiled multi-pattern table: literal patterns (full-length
    keywords + anchors, grouped by word-chunk count) followed by
    chain patterns. Pattern *columns* are the index space the scan
    plan stores; ``masks[:, col]`` is a 16-block position bitmask —
    START positions for literals (window math identical to the old
    code table), END positions for chains (file-level gates)."""

    _UPLOAD_SPAN = "dfa_upload"
    _PIPELINE = "secret"
    _TABLE = "dfa"              # /metrics residency label

    def __init__(self, literals: list, chains: list):
        # literals: lowercased bytes, 1..MAX_LIT_BYTES, deduped by
        # caller; chains: unit tuples from chain_units, deduped
        self._init_resident()
        order = sorted(range(len(literals)),
                       key=lambda i: (-(-len(literals[i]) //
                                        MAX_CODE_LEN), literals[i]))
        self.literals = tuple(literals[i] for i in order)
        self.chains = tuple(chains)
        self._lit_col = {b: c for c, b in enumerate(self.literals)}
        self._chain_col = {u: len(self.literals) + c
                           for c, u in enumerate(self.chains)}
        self.n_patterns = len(self.literals) + len(self.chains)

        self.groups: list = []
        self._arrays: list = []
        col = 0
        i = 0
        while i < len(self.literals):
            c = -(-len(self.literals[i]) // MAX_CODE_LEN)
            j = i
            while j < len(self.literals) and \
                    -(-len(self.literals[j]) // MAX_CODE_LEN) == c:
                j += 1
            group = self.literals[i:j]
            self.groups.append(_LitGroup(chunks=c, start=col,
                                         count=len(group)))
            self._arrays.extend(self._pack_group(group, c))
            col += len(group)
            i = j
        self.rules_hash = hashlib.sha256(
            repr((self.literals, self.chains)).encode()
        ).hexdigest()[:16]
        self._fns: dict = {}
        self._fns_lock = threading.Lock()

    @staticmethod
    def _pack_group(group: tuple, chunks: int) -> list:
        Kg = len(group)
        Kp = -(-Kg // CODE_CHUNK) * CODE_CHUNK
        lo = np.zeros((chunks, Kp), np.uint64)
        hi = np.zeros((chunks, Kp), np.uint64)
        lom = np.zeros((chunks, Kp), np.uint64)
        him = np.zeros((chunks, Kp), np.uint64)
        # pad columns must never hit: code 0 under a full mask only
        # matches 8 NULs, and pad columns are sliced off before any
        # consumer sees them anyway
        lom[0, Kg:] = him[0, Kg:] = 0xFFFFFFFF
        for k, lit in enumerate(group):
            for j in range(chunks):
                part = lit[j * MAX_CODE_LEN:(j + 1) * MAX_CODE_LEN]
                if not part:
                    continue            # trailing chunk: always-true
                lo[j, k], hi[j, k], lom[j, k], him[j, k] = \
                    pack_code(part)
        return [a.astype(np.uint32) for a in (lo, hi, lom, him)]

    # --- index space (the scan plan stores these columns) ---

    def lit_col(self, literal: bytes) -> int:
        return self._lit_col[literal.lower()]

    def chain_col(self, units: tuple) -> int:
        return self._chain_col[units]

    def lit_len(self, col: int) -> int:
        return len(self.literals[col])

    @property
    def max_chunks(self) -> int:
        cs = [g.chunks for g in self.groups]
        for units in self.chains:
            cs.extend(-(-len(u[1]) // MAX_CODE_LEN)
                      for u in units if u[0] == "lit")
        return max(cs, default=1)

    # --- residency hooks (ResidentTables) ---

    def _resident_arrays(self) -> tuple:
        return tuple(self._arrays)

    def _span_attrs(self) -> dict:
        return {"patterns": self.n_patterns,
                "rules_hash": self.rules_hash}

    def _note_upload(self, nbytes: int) -> None:
        from ..secret.metrics import SECRET_METRICS
        SECRET_METRICS.note_dfa_upload(nbytes)

    def _note_dispatch(self) -> None:
        from ..secret.metrics import SECRET_METRICS
        SECRET_METRICS.inc("dfa_dispatches")

    def _note_invalidation(self) -> None:
        from ..secret.metrics import SECRET_METRICS
        SECRET_METRICS.inc("dfa_invalidations")

    # --- compiled scan functions (cached per table) ---

    def fused_sieve(self, run_specs: tuple, platform: str):
        """ONE jit dispatch over a device-resident segment buffer:
        pattern blockmasks + class-run hits, with the fetch
        COMPACTED to hit rows (ops/keywords.make_fused_sieve
        semantics: returns (nhit, idx, cmasks, run_hits))."""
        return self._fn(("fused", run_specs, platform))

    def full_sieve(self, run_specs: tuple, platform: str):
        """Full-fetch variant: (masks [B, K] uint16, run_hits). The
        single-device path falls back to it (with ``run_specs=()``)
        when a batch overflows the compaction capacity."""
        return self._fn(("full", run_specs, platform))

    def mesh_sieve(self, mesh, run_specs: tuple, platform: str):
        """Mesh variant: the segment rows shard over EVERY chip
        (flat — masks are row-elementwise, no collective needed),
        the band arrays replicate, and the whole sieve is ONE
        shard_map dispatch — one compile per (mesh, shape), where a
        per-device dispatch loop would compile once per DEVICE per
        shape (measured ~1.3 s × devices × shapes of pure compile
        thrash on the CPU sim). Returns (masks [B, K] uint16,
        run_hits [B, n_specs])."""
        return self._fn(("mesh", mesh, run_specs, platform))

    def _fn(self, key: tuple):
        with self._fns_lock:
            fn = self._fns.get(key)
            if fn is None:
                if key[0] == "mesh":
                    fn = _build_mesh_sieve(self, *key[1:])
                else:
                    fn = _build_sieve(self, *key)
                self._fns[key] = fn
        return fn


_TABLE_CACHE: dict = {}
_TABLE_LOCK = threading.Lock()
_TABLE_CACHE_MAX = 8


def build_table(literals, chains) -> DfaTable:
    """Compile (or fetch) the table for one rule corpus. Cached on
    the rule-set hash so every scanner instance built from the same
    rules shares one table — and therefore one HBM upload per
    placement (the ``trivy-secret.yaml`` fleet case compiles custom
    rules into their own cached table)."""
    lits = tuple(sorted({x.lower() for x in literals if x}))
    chs = tuple(sorted(set(chains), key=repr))
    fp = hashlib.sha256(repr((lits, chs)).encode()).hexdigest()
    evicted = []
    with _TABLE_LOCK:
        table = _TABLE_CACHE.get(fp)
        if table is None:
            table = DfaTable(list(lits), list(chs))
            _TABLE_CACHE[fp] = table
            while len(_TABLE_CACHE) > _TABLE_CACHE_MAX:
                # FIFO eviction; dropped tables free their HBM once
                # the last in-flight dispatch releases its buffers
                evicted.append(_TABLE_CACHE.pop(
                    next(iter(_TABLE_CACHE))))
    for old in evicted:
        # invalidate_device takes the table's ResidentTables lock —
        # outside _TABLE_LOCK (lint: lock-discipline)
        old.invalidate_device()
    return table


# ---------------------------------------------------------------------
# the kernel body (jnp interpreter — ops/dfa_pallas.py is the TPU
# kernel; both evaluate the same banded table)
# ---------------------------------------------------------------------

def _shift_left(a, k: int):
    """a[:, i] ← a[:, i+k], zero-filled at the tail."""
    if k == 0:
        return a
    return jnp.pad(a[:, k:], ((0, 0), (0, k)))


def _shift_right(a, k: int):
    """a[:, i] ← a[:, i-k], zero-filled at the head."""
    if k == 0:
        return a
    return jnp.pad(a[:, :-k], ((0, 0), (k, 0)))


def _window_words_lower(segments):
    """Sliding 8-byte windows of the ASCII-lowercased input, as
    (lo, hi) uint32 pairs for every word offset the table needs."""
    x = segments.astype(jnp.uint32)
    x = jnp.where((x >= 65) & (x <= 90), x + 32, x)
    sh = [_shift_left(x, i) for i in range(8)]
    lo = sh[0] | (sh[1] << 8) | (sh[2] << 16) | (sh[3] << 24)
    hi = sh[4] | (sh[5] << 8) | (sh[6] << 16) | (sh[7] << 24)
    return lo, hi


def _blockmask(hits, bits):
    """[B, L] bool → [B] uint32 16-block position bitmask."""
    B, L = hits.shape
    hb = hits.reshape(B, N_BLOCKS, L // N_BLOCKS).any(axis=2)
    return jnp.sum(jnp.where(hb, bits, jnp.uint32(0)), axis=1,
                   dtype=jnp.uint32)


def _membership(x, ranges):
    m = jnp.zeros(x.shape, bool)
    for lo, hi in ranges:
        m = m | (x == lo) if lo == hi else \
            m | ((x >= lo) & (x <= hi))
    return m


def _lit_pred(lo_sh, hi_sh, data: bytes):
    """[B, L] bool: full literal ``data`` starts at position t (on
    lowered text). Chunk j is one masked compare of the word at
    t + 8j."""
    p = None
    for j in range(-(-len(data) // MAX_CODE_LEN)):
        part = data[j * MAX_CODE_LEN:(j + 1) * MAX_CODE_LEN]
        klo, khi, mlo, mhi = (jnp.uint32(v) for v in pack_code(part))
        cmp = ((lo_sh[j] & mlo) == klo) & ((hi_sh[j] & mhi) == khi)
        p = cmp if p is None else p & cmp
    return p


def _erode(m, n: int):
    """e[i] = AND of m[i..i+n-1] (log-doubling, ops/runs shape)."""
    e = m
    span = 1
    while span < n:
        step = min(span, n - span)
        e = e & _shift_left(e, step)
        span += step
    return e


def dfa_masks_impl(segments, dev_arrays: tuple, table: DfaTable):
    """[B, L] uint8 × resident table → [B, n_patterns] uint32
    blockmasks. ``table`` supplies only STATIC structure (groups,
    chain units, lengths); the packed band arrays come in as device
    operands so residency is real."""
    B, L = segments.shape
    blk = L // N_BLOCKS
    bits = (jnp.uint32(1) << jnp.arange(N_BLOCKS, dtype=jnp.uint32))

    lo, hi = _window_words_lower(segments)
    nch = table.max_chunks
    lo_sh = [_shift_left(lo, 8 * j) for j in range(nch)]
    hi_sh = [_shift_left(hi, 8 * j) for j in range(nch)]

    cols = []
    ai = 0
    for g in table.groups:
        glo, ghi, glom, ghim = dev_arrays[ai:ai + 4]
        ai += 4
        c = g.chunks

        def step(_, kw, c=c):
            klo, khi, mlo, mhi = kw         # each [c, CODE_CHUNK]
            hit = None
            for j in range(c):
                h = (((lo_sh[j][:, :, None] & mlo[j]) == klo[j])
                     & ((hi_sh[j][:, :, None] & mhi[j]) == khi[j]))
                hit = h if hit is None else hit & h
            hb = hit.reshape(B, N_BLOCKS, blk, CODE_CHUNK).any(axis=2)
            mask = jnp.sum(
                jnp.where(hb, bits[None, :, None], jnp.uint32(0)),
                axis=1, dtype=jnp.uint32)   # [B, CODE_CHUNK]
            return None, mask

        xs = tuple(a.reshape(c, -1, CODE_CHUNK).transpose(1, 0, 2)
                   for a in (glo, ghi, glom, ghim))
        _, masks = lax.scan(step, None, xs)
        cols.append(masks.transpose(1, 0, 2)
                    .reshape(B, -1)[:, :g.count])

    if table.chains:
        xi = segments.astype(jnp.int32)
        memb: dict = {}
        erod: dict = {}
        chain_cols = []
        for units in table.chains:
            K = chain_len(units)
            acc = None
            off = 0
            for u in units:
                if u[0] == "lit":
                    pred = _lit_pred(lo_sh, hi_sh, u[1])
                    ulen = len(u[1])
                else:
                    _, ranges, n = u
                    m = memb.get(ranges)
                    if m is None:
                        m = memb[ranges] = _membership(xi, ranges)
                    pred = erod.get((ranges, n))
                    if pred is None:
                        pred = erod[(ranges, n)] = _erode(m, n)
                    ulen = n
                # start-position predicate, rolled to the chain END
                pred = _shift_right(pred, K - 1 - off)
                acc = pred if acc is None else acc & pred
                off += ulen
            chain_cols.append(_blockmask(acc, bits))
        cols.append(jnp.stack(chain_cols, axis=1))

    if not cols:
        return jnp.zeros((B, 0), jnp.uint32)
    return jnp.concatenate(cols, axis=1)


# ---------------------------------------------------------------------
# NumPy reference (differential testing + the cpu-ref backend)
# ---------------------------------------------------------------------

def dfa_masks_host(segments: np.ndarray, table: DfaTable) \
        -> np.ndarray:
    B, L = segments.shape
    blk = L // N_BLOCKS
    bitvals = (np.uint32(1) << np.arange(N_BLOCKS, dtype=np.uint32))

    x = segments.astype(np.uint32)
    xl = np.where((x >= 65) & (x <= 90), x + 32, x)

    def shl(a, k):
        return a if k == 0 else \
            np.pad(a[:, k:], ((0, 0), (0, k)))

    def shr(a, k):
        return a if k == 0 else \
            np.pad(a[:, :-k], ((0, 0), (k, 0)))

    sh = [shl(xl, i) for i in range(8)]
    lo = sh[0] | sh[1] << 8 | sh[2] << 16 | sh[3] << 24
    hi = sh[4] | sh[5] << 8 | sh[6] << 16 | sh[7] << 24
    nch = table.max_chunks
    lo_sh = [shl(lo, 8 * j) for j in range(nch)]
    hi_sh = [shl(hi, 8 * j) for j in range(nch)]

    def blockmask(hits):
        hb = hits.reshape(B, N_BLOCKS, blk).any(axis=2)
        return (hb.astype(np.uint32) * bitvals).sum(
            axis=1, dtype=np.uint32)

    def lit_pred(data):
        p = None
        for j in range(-(-len(data) // MAX_CODE_LEN)):
            part = data[j * MAX_CODE_LEN:(j + 1) * MAX_CODE_LEN]
            klo, khi, mlo, mhi = pack_code(part)
            cmp = ((lo_sh[j] & np.uint32(mlo)) == np.uint32(klo)) \
                & ((hi_sh[j] & np.uint32(mhi)) == np.uint32(khi))
            p = cmp if p is None else p & cmp
        return p

    out = np.zeros((B, table.n_patterns), np.uint32)
    for col, lit in enumerate(table.literals):
        out[:, col] = blockmask(lit_pred(lit))

    xi = segments.astype(np.int32)
    for ci, units in enumerate(table.chains):
        K = chain_len(units)
        acc = None
        off = 0
        for u in units:
            if u[0] == "lit":
                pred = lit_pred(u[1])
                ulen = len(u[1])
            else:
                _, ranges, n = u
                m = np.zeros(xi.shape, bool)
                for a, b in ranges:
                    m |= (xi >= a) & (xi <= b)
                e = m
                span = 1
                while span < n:
                    step = min(span, n - span)
                    e = e & shl(e, step)
                    span += step
                pred, ulen = e, n
            pred = shr(pred, K - 1 - off)
            acc = pred if acc is None else acc & pred
            off += ulen
        out[:, len(table.literals) + ci] = blockmask(acc)
    return out


# ---------------------------------------------------------------------
# fused dispatch factory (compaction shape: ops/keywords.py)
# ---------------------------------------------------------------------

def _masks_fn(table: DfaTable, platform: str):
    if platform != "cpu":
        from .dfa_pallas import dfa_blockmask_pallas

        def masks_fn(segments, dev):
            return dfa_blockmask_pallas(segments, table, dev)
    else:
        def masks_fn(segments, dev):
            return dfa_masks_impl(segments, dev, table)
    return masks_fn


def _build_mesh_sieve(table: DfaTable, mesh, run_specs: tuple,
                      platform: str):
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, RULES_AXIS
    from .runs import run_hits_impl
    masks_fn = _masks_fn(table, platform)
    row = P((DATA_AXIS, RULES_AXIS), None)

    def local(segments, *dev):
        masks = masks_fn(segments, dev).astype(jnp.uint16)
        if run_specs:
            hits = run_hits_impl(segments, run_specs)
        else:
            hits = jnp.zeros((segments.shape[0], 0), jnp.bool_)
        return masks, hits

    rep = tuple(P(*([None] * a.ndim))
                for a in table._resident_arrays())
    # check_vma off: masks are row-elementwise per shard, nothing
    # is replicated on the way out
    fn = jax.shard_map(local, mesh=mesh, in_specs=(row,) + rep,
                       out_specs=(row, row), check_vma=False)
    return DeviceProgram(jax.jit(fn), "dfa_mesh_sieve")


def _build_sieve(table: DfaTable, kind: str, run_specs: tuple,
                 platform: str):
    from .runs import run_hits_impl
    masks_fn = _masks_fn(table, platform)

    K = table.n_patterns

    # argnum 0 (the per-batch segment buffer) is DONATED: each
    # dispatch uploads a fresh buffer, the kernel may free/reuse its
    # HBM immediately, and collect frees the slot for the next
    # upload (docs/performance.md §8). The band/table arrays ride in
    # *dev and are NEVER donated — they are the resident state every
    # dispatch of this rule-set generation shares. Callers must not
    # reuse a segment buffer after the call (the >CAP full-fetch
    # fallback re-uploads, secret/batch._decode).
    def full(segments, *dev):
        masks = masks_fn(segments, dev).astype(jnp.uint16)
        B = segments.shape[0]
        if run_specs:
            hits = run_hits_impl(segments, run_specs)
        else:
            hits = jnp.zeros((B, 0), jnp.bool_)
        return masks, hits

    if kind == "full":
        return DeviceProgram(jax.jit(full, donate_argnums=(0,)),
                             "dfa_full_sieve")

    def fused(segments, *dev):
        masks = masks_fn(segments, dev).astype(jnp.uint16)
        B = segments.shape[0]
        cap = min(SIEVE_CAP, B)
        seg_any = (masks != 0).any(axis=1) if K else \
            jnp.zeros((B,), bool)
        nhit = seg_any.sum(dtype=jnp.int32)
        idx = jnp.nonzero(seg_any, size=cap, fill_value=0)[0]
        cmasks = masks[idx]
        if run_specs:
            hits = run_hits_impl(segments, run_specs)
        else:
            hits = jnp.zeros((B, 0), jnp.bool_)
        return nhit, idx, cmasks, hits

    return DeviceProgram(jax.jit(fused, donate_argnums=(0,)),
                         "dfa_fused_sieve")


__all__ = [
    "MAX_LIT_BYTES", "MAX_CHAIN_LEN", "DfaTable", "build_table",
    "best_fixed_chain", "chain_units", "chain_len",
    "dfa_masks_host", "dfa_masks_impl", "pad_batch",
]
