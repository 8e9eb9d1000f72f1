"""Multi-literal substring matching on TPU — the stage-1 sieve.

The reference gates every rule on a per-file substring prefilter
(MatchKeywords, pkg/fanal/secret/scanner.go:164-177) before running its
regex over the whole file. The TPU re-design extends that idea: ONE
kernel scans every segment for (a) the rules' gate keywords and (b) the
anchor literals proven mandatory-in-match by trivy_tpu.secret.rx.anchor
— returning, per (segment, code), a 16-block position bitmask. The host
then regexes only small windows around anchor hits.

This is pure elementwise work — no gathers, which do not vectorize on
the TPU VPU (the gather-DFA measured 2.3 MB/s; these compares run at
HBM rate). Each sliding 8-byte window of the lowercased input is packed
into two uint32 words; a literal of length m ≤ 8 is one masked compare
against its code; longer literals match on their first 8 bytes (a
superset — exactness is restored by host verification).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

N_BLOCKS = 16          # position resolution: L/16 bytes per block
CODE_CHUNK = 8         # literals matched per scan step
MAX_CODE_LEN = 8       # two uint32 words per window


@dataclass(frozen=True)
class CodeTable:
    """Packed literal codes (shared by gate keywords and anchors)."""

    lo: np.ndarray        # [K] uint32 — window bytes 0-3
    hi: np.ndarray        # [K] uint32 — window bytes 4-7
    lo_mask: np.ndarray   # [K] uint32
    hi_mask: np.ndarray   # [K] uint32
    literals: tuple       # K lowercased byte-strings (≤8B, dedup, sorted)

    @property
    def n_codes(self) -> int:
        return len(self.literals)

    def index(self, literal: bytes) -> int:
        return self.literals.index(_truncate(literal))


def _truncate(literal: bytes) -> bytes:
    return literal.lower()[:MAX_CODE_LEN]


def pack_code(literal: bytes) -> tuple:
    """(lo, hi, lo_mask, hi_mask) for one ≤8-byte lowercased literal."""
    b = _truncate(literal)
    m = len(b)
    assert 0 < m <= MAX_CODE_LEN
    lo = int.from_bytes(b[:4].ljust(4, b"\0"), "little")
    hi = int.from_bytes(b[4:].ljust(4, b"\0"), "little")
    lo_mask = (1 << (8 * min(m, 4))) - 1
    hi_mask = ((1 << (8 * (m - 4))) - 1) if m > 4 else 0
    return lo, hi, lo_mask, hi_mask


def build_code_table(literals) -> CodeTable:
    """Dedup + pack a set of byte-string literals."""
    uniq = sorted({_truncate(x) for x in literals if x})
    packed = [pack_code(x) for x in uniq]
    if not packed:
        packed = [(0, 0, 0xFFFFFFFF, 0xFFFFFFFF)]  # matches nothing
        uniq = [b"\x00\x00\x00\x00"]
    arr = np.array(packed, np.uint64).astype(np.uint32)
    return CodeTable(lo=arr[:, 0].copy(), hi=arr[:, 1].copy(),
                     lo_mask=arr[:, 2].copy(), hi_mask=arr[:, 3].copy(),
                     literals=tuple(uniq))


def _window_words(segments: jax.Array) -> tuple:
    """[B, L] uint8 → (lo, hi) [B, L] uint32 sliding 8-byte windows,
    zero-padded past the segment end, ASCII-lowercased."""
    x = segments.astype(jnp.uint32)
    is_upper = (x >= 65) & (x <= 90)
    x = jnp.where(is_upper, x + 32, x)

    def shifted(i):
        if i == 0:
            return x
        return jnp.pad(x[:, i:], ((0, 0), (0, i)))

    lo = (shifted(0) | (shifted(1) << 8) | (shifted(2) << 16)
          | (shifted(3) << 24))
    hi = (shifted(4) | (shifted(5) << 8) | (shifted(6) << 16)
          | (shifted(7) << 24))
    return lo, hi


def _pad_codes(arrs: tuple) -> tuple:
    K = arrs[0].shape[0]
    Kp = ((K + CODE_CHUNK - 1) // CODE_CHUNK) * CODE_CHUNK
    if Kp == K:
        return arrs
    out = []
    for i, a in enumerate(arrs):
        pad = np.zeros(Kp - K, a.dtype)
        if i >= 2:            # masks: full masks + nonzero code ⇒ no match
            pad = pad + np.uint32(0xFFFFFFFF)
        out.append(np.concatenate([np.asarray(a), pad]))
    # padded codes are 0 with full masks: only a window of 8 NULs would
    # match; NUL never appears in lowercased text windows except final
    # padding, where a hit is harmless (killed by host verify).
    return tuple(out)


def code_blockmask_impl(segments: jax.Array, lo_c: jax.Array,
                        hi_c: jax.Array, lo_m: jax.Array,
                        hi_m: jax.Array) -> jax.Array:
    """[B, L] segments × K codes → [B, K] uint32 position bitmasks
    (bit j = code hit inside block j of N_BLOCKS equal slices)."""
    B, L = segments.shape
    lo, hi = _window_words(segments)
    blk = L // N_BLOCKS
    bits = (jnp.uint32(1) << jnp.arange(N_BLOCKS, dtype=jnp.uint32))

    chunks = lo_c.shape[0] // CODE_CHUNK

    def step(_, kw):
        klo, khi, mlo, mhi = kw               # each [CODE_CHUNK]
        hit = (((lo[:, :, None] & mlo) == klo)
               & ((hi[:, :, None] & mhi) == khi))     # [B, L, C]
        hb = hit.reshape(B, N_BLOCKS, blk, CODE_CHUNK).any(axis=2)
        mask = jnp.sum(
            jnp.where(hb, bits[None, :, None], jnp.uint32(0)),
            axis=1, dtype=jnp.uint32)                 # [B, C]
        return None, mask

    xs = tuple(a.reshape(chunks, CODE_CHUNK) for a in
               (lo_c, hi_c, lo_m, hi_m))
    _, masks = lax.scan(step, None, xs)               # [chunks, B, C]
    return masks.transpose(1, 0, 2).reshape(B, -1)    # [B, Kp]


code_blockmask = jax.jit(code_blockmask_impl)


def code_blockmask_host(segments, lo_c, hi_c, lo_m, hi_m):
    """NumPy reference (differential testing)."""
    B, L = segments.shape
    x = segments.astype(np.uint32)
    x = np.where((x >= 65) & (x <= 90), x + 32, x)
    pads = [np.pad(x[:, i:], ((0, 0), (0, i))) for i in range(8)]
    lo = pads[0] | pads[1] << 8 | pads[2] << 16 | pads[3] << 24
    hi = pads[4] | pads[5] << 8 | pads[6] << 16 | pads[7] << 24
    K = lo_c.shape[0]
    blk = L // N_BLOCKS
    out = np.zeros((B, K), np.uint32)
    for k in range(K):
        hit = (((lo & lo_m[k]) == lo_c[k])
               & ((hi & hi_m[k]) == hi_c[k]))         # [B, L]
        hb = hit.reshape(B, N_BLOCKS, blk).any(axis=2)
        out[:, k] = (hb.astype(np.uint32)
                     << np.arange(N_BLOCKS, dtype=np.uint32)).sum(axis=1)
    return out


def run_blockmask(segments: np.ndarray, table: CodeTable,
                  backend: str = "tpu", mesh=None) -> np.ndarray:
    """Dispatch helper: pads codes to the chunk size and the batch to a
    shape bucket (jit-cache friendly), slices padding back off."""
    K = table.n_codes
    codes = _pad_codes((table.lo, table.hi, table.lo_mask,
                        table.hi_mask))
    if backend == "cpu-ref":
        return code_blockmask_host(segments, *codes)[:, :K]
    B = segments.shape[0]
    segments = pad_batch(segments)
    if mesh is not None:
        from ..parallel.secret_shard import sharded_blockmask
        return sharded_blockmask(mesh, segments, codes)[:B, :K]
    import jax
    if jax.default_backend() != "cpu":
        # Pallas kernel: one HBM pass per tile instead of one per code
        # chunk (the XLA scan re-reads window words every step)
        from .keywords_pallas import code_blockmask_pallas
        out = code_blockmask_pallas(jnp.asarray(segments),
                                    *(jnp.asarray(c) for c in codes))
    else:
        out = code_blockmask(jnp.asarray(segments),
                             *(jnp.asarray(c) for c in codes))
    return np.asarray(out)[:B, :K]


SIEVE_CAP = 4096       # compacted-fetch capacity (hit segments)


def _sieve_blockmask_fn(literals: tuple, platform: str):
    """Shared setup for the fused/full sieve factories: one place
    builds the code table, pads it, picks the pallas vs XLA kernel,
    and stages device constants — so the compacted and fallback
    paths cannot drift apart. Returns (n_codes, blockmask_fn)."""
    table = build_code_table(literals)
    codes = _pad_codes((table.lo, table.hi, table.lo_mask,
                        table.hi_mask))
    cdev = tuple(jnp.asarray(c) for c in codes)
    if platform != "cpu":
        from .keywords_pallas import code_blockmask_pallas

        def blockmask(segments):
            return code_blockmask_pallas(segments, *cdev)
    else:
        def blockmask(segments):
            return code_blockmask_impl(segments, *cdev)
    return table.n_codes, blockmask


@functools.lru_cache(maxsize=8)
def make_fused_sieve(literals: tuple, run_specs: tuple,
                     platform: str):
    """ONE jit dispatch for both sieve stages over a device-resident
    segment buffer: literal blockmask + class-run hits.

    The segment buffer crosses to the device ONCE, both kernels
    read the resident copy, and the fetch is COMPACTED on device:
    only the rows of segments with ≥1 code hit come back (as
    uint16 — N_BLOCKS = 16 bits used — gathered at fixed capacity
    SIEVE_CAP so shapes stay static under jit). Run hits are
    [B, n_specs] bool and come back whole: a file's mandatory
    class-run can sit in a segment with no keyword hit.

    Returns (per jit call over [B, L] segments):
      nhit   — i32 scalar, segments with ≥1 code hit
      idx    — [CAP] i32, their row indices (first nhit valid,
               ascending; CAP = min(SIEVE_CAP, B))
      cmasks — [CAP, K] uint16 blockmask rows for those segments
      hits   — [B, n_specs] bool class-run presence

    When nhit > CAP the compacted fetch is insufficient — callers
    fall back to the full-mask variant (make_full_sieve).

    Cached on (literals, run_specs, platform) so scanner instances
    share the compile."""
    n_codes, blockmask = _sieve_blockmask_fn(literals, platform)
    from .runs import run_hits_impl

    @jax.jit
    def fused(segments: jax.Array) -> tuple:
        masks = blockmask(segments)
        # slice off pad codes BEFORE seg_any: pad entries (0 with
        # full masks) hit 8-NUL windows, so counting their columns
        # would mark every zero-padded tail segment as a hit and
        # defeat the compaction whenever n_codes < padded width
        masks = masks[:, :n_codes].astype(jnp.uint16)
        B = segments.shape[0]
        cap = min(SIEVE_CAP, B)
        seg_any = (masks != 0).any(axis=1)
        nhit = seg_any.sum(dtype=jnp.int32)
        idx = jnp.nonzero(seg_any, size=cap, fill_value=0)[0]
        cmasks = masks[idx]
        if run_specs:
            hits = run_hits_impl(segments, run_specs)
        else:
            hits = jnp.zeros((B, 0), jnp.bool_)
        return nhit, idx, cmasks, hits

    return fused


@functools.lru_cache(maxsize=8)
def make_full_sieve(literals: tuple, platform: str):
    """Full-mask variant of make_fused_sieve for the rare batch
    where more than SIEVE_CAP segments hit: returns the whole
    [B, K] uint16 mask array. Run hits are NOT recomputed — the
    fused dispatch already produced them and callers keep that
    array."""
    n_codes, blockmask = _sieve_blockmask_fn(literals, platform)

    @jax.jit
    def full(segments: jax.Array) -> jax.Array:
        # drop pad-code columns
        return blockmask(segments)[:, :n_codes].astype(jnp.uint16)

    return full


def _bucket(n: int, base: int = 256, cap: int = 4096) -> int:
    """Round batch sizes up to a small set of shapes so jit caches
    stay warm (pad rows are zeros — they match nothing real).
    Powers of two from ``base`` up to ``cap``, then ``cap``-steps
    (a 40k-segment batch should not pad to 64k). The defaults are
    the segment-buffer ladder; detect/batch.py reuses this with a
    64/8192 ladder for pair rows."""
    b = base
    while b < n and b < cap:
        b *= 2
    if n <= b:
        return b
    return ((n + cap - 1) // cap) * cap


# One part of a streamed tree (runtime/batch.submit_tree): candidate
# files are cut, whole, into sieve batches of at most this many segment
# rows, the ladder's top rung that ``runtime/aot.warm_ladders`` warms
# (about 16 MB of text), so a tree of any size dispatches shapes the
# ladder already has. A file with more rows rides alone at the
# ``cap``-steps above it.
PART_ROWS = 8192


def pad_batch(segments: np.ndarray) -> np.ndarray:
    B = segments.shape[0]
    Bp = _bucket(B)
    if Bp == B:
        return segments
    return np.concatenate(
        [segments, np.zeros((Bp - B, segments.shape[1]),
                            segments.dtype)])
