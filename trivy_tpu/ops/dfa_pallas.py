"""Pallas TPU kernel for the banded multi-pattern DFA sieve.

Same banded-table evaluation as ops/dfa.dfa_masks_impl, but one HBM
pass per segment tile: the tile loads into VMEM once, the sliding
lowered window words build in registers, then every pattern — the
literal groups AND the chain patterns' band (membership + erosion +
static rolls) — evaluates against the resident tile. The XLA scan
formulation re-reads the window-word arrays from HBM per code chunk;
here HBM traffic is 1 × L×B bytes regardless of pattern count
(the keywords_pallas.py lesson, extended to the full engine).

Layout:
  grid            = (B // TILE_B,)
  segments block  = [TILE_B, L] uint8 in VMEM
  band arrays     = 4 × [c, Kg128] uint32 per literal group,
                    scalar-prefetched to SMEM
  chain structure = STATIC (unrolled into the kernel — the chain
                    band is part of the compiled program, uploaded
                    implicitly with it; the literal band rides HBM)
  outputs         = per literal group [TILE_B, Kg128] uint32 and one
                    [TILE_B, Kc128] uint32 chain block — 128-code
                    groups accumulate in registers via lane-select
                    (dynamic lane stores must be 128-aligned), one
                    store per group

Out bit j of word [b, k] = pattern k hit inside block j of segment b
(N_BLOCKS = 16 blocks; start positions for literals, end positions
for chains — ops/dfa.py documents why decode doesn't care for
chains)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .keywords import CODE_CHUNK, MAX_CODE_LEN, N_BLOCKS, pack_code
from .dfa import chain_len

TILE_B = 32     # smaller than keywords_pallas: up to 4 shifted
                # word-pair levels live in VMEM alongside the tile,
                # and shard_map blocks can be as small as 32 rows


def _pad128(a, fill_masks: bool):
    """Pad the code axis of a [c, K] band array to a 128 multiple;
    pad columns carry match-nothing codes (0 under a full mask)."""
    c, K = a.shape
    Kp = -(-K // 128) * 128
    if Kp == K:
        return a
    pad = jnp.zeros((c, Kp - K), jnp.uint32)
    if fill_masks:
        first = jnp.full((1, Kp - K), 0xFFFFFFFF, jnp.uint32)
        pad = jnp.concatenate([first, pad[1:]], axis=0) \
            if c > 1 else first
    return jnp.concatenate([a, pad], axis=1)


def _make_kernel(table, L: int):
    groups = table.groups
    chains = table.chains
    nch = table.max_chunks
    blk = L // N_BLOCKS
    kc128 = max(1, -(-max(1, len(chains)) // 128)) * 128

    def kernel(*refs):
        g_refs = refs[:4 * len(groups)]
        seg_ref = refs[4 * len(groups)]
        out_refs = refs[4 * len(groups) + 1:]

        x = seg_ref[:].astype(jnp.int32)                 # [bT, L]
        bT = x.shape[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (bT, L), 1)
        xl = jnp.where((x >= 65) & (x <= 90), x + 32, x)

        def shl(a, k):
            if k == 0:
                return a
            r = pltpu.roll(a, L - k, 1)      # left-shift by k
            return jnp.where(col < L - k, r, 0)

        def shr(a, k):
            if k == 0:
                return a
            r = pltpu.roll(a, k, 1)          # right-shift by k
            return jnp.where(col >= k, r, 0)

        xs = [shl(xl, i) for i in range(8)]
        xu = [v.astype(jnp.uint32) for v in xs]
        lo0 = xu[0] | (xu[1] << 8) | (xu[2] << 16) | (xu[3] << 24)
        hi0 = xu[4] | (xu[5] << 8) | (xu[6] << 16) | (xu[7] << 24)
        lo_sh = [lo0]
        hi_sh = [hi0]
        for j in range(1, nch):
            lo_sh.append(
                shl(lo0.astype(jnp.int32), 8 * j).astype(jnp.uint32))
            hi_sh.append(
                shl(hi0.astype(jnp.int32), 8 * j).astype(jnp.uint32))

        # block reduction rides the MXU: [bT, L] @ [L, 16] hit
        # counts are exact in f32 (≤ blk ones per block)
        pos_blk = jax.lax.broadcasted_iota(
            jnp.int32, (L, N_BLOCKS), 0) // blk
        blk_id = jax.lax.broadcasted_iota(
            jnp.int32, (L, N_BLOCKS), 1)
        ind = (pos_blk == blk_id).astype(jnp.float32)
        bit_val = (jnp.int32(1) << jax.lax.broadcasted_iota(
            jnp.int32, (bT, N_BLOCKS), 1))
        lane = jax.lax.broadcasted_iota(jnp.int32, (bT, 128), 1)

        def blockmask_col(hit):
            counts = jnp.dot(hit.astype(jnp.float32), ind,
                             preferred_element_type=jnp.float32)
            return jnp.sum(jnp.where(counts > 0, bit_val, 0),
                           axis=1, keepdims=True)     # [bT, 1]

        # --- literal groups (band arrays arrive FLATTENED 1-D:
        # chunk-major, [jc * Kg128 + k] — 1-D dynamic SMEM reads
        # are the pattern keywords_pallas.py established) ---
        for gi, g in enumerate(groups):
            lo_r, hi_r, lom_r, him_r = g_refs[4 * gi:4 * gi + 4]
            Kg128 = out_refs[gi].shape[1]
            for b128 in range(Kg128 // 128):
                def body(j, acc, b128=b128, g=g, Kg128=Kg128,
                         lo_r=lo_r, hi_r=hi_r, lom_r=lom_r,
                         him_r=him_r):
                    k = b128 * 128 + j
                    hit = None
                    for jc in range(g.chunks):
                        f = jc * Kg128 + k
                        h = ((lo_sh[jc] & lom_r[f]) == lo_r[f]) \
                            & ((hi_sh[jc] & him_r[f]) == hi_r[f])
                        hit = h if hit is None else hit & h
                    return jnp.where(lane == j,
                                     blockmask_col(hit), acc)

                acc = jax.lax.fori_loop(
                    0, 128, body, jnp.zeros((bT, 128), jnp.int32))
                out_refs[gi][:, b128 * 128:(b128 + 1) * 128] = \
                    acc.astype(jnp.uint32)

        # --- chain patterns (static unroll — the chain band is part
        # of the compiled program) ---
        if out_refs[len(groups):]:
            memb: dict = {}
            erod: dict = {}

            def membership(ranges):
                m = memb.get(ranges)
                if m is None:
                    m = jnp.zeros((bT, L), jnp.int32)
                    for a, b in ranges:
                        m = m | ((x == a).astype(jnp.int32)
                                 if a == b else
                                 ((x >= a) & (x <= b))
                                 .astype(jnp.int32))
                    memb[ranges] = m
                return m

            def erode(ranges, n):
                e = erod.get((ranges, n))
                if e is None:
                    e = membership(ranges)
                    span = 1
                    while span < n:
                        step = min(span, n - span)
                        e = e & shl(e, step)
                        span += step
                    erod[(ranges, n)] = e
                return e

            def lit_pred(data):
                p = None
                for j in range(-(-len(data) // MAX_CODE_LEN)):
                    part = data[j * MAX_CODE_LEN:
                                (j + 1) * MAX_CODE_LEN]
                    klo, khi, mlo, mhi = (jnp.uint32(v)
                                          for v in pack_code(part))
                    cmp = ((lo_sh[j] & mlo) == klo) \
                        & ((hi_sh[j] & mhi) == khi)
                    p = cmp if p is None else p & cmp
                return p.astype(jnp.int32)

            chain_ref = out_refs[len(groups)]
            for b128 in range(kc128 // 128):
                acc = jnp.zeros((bT, 128), jnp.int32)
                for j, units in enumerate(
                        chains[b128 * 128:(b128 + 1) * 128]):
                    K = chain_len(units)
                    hit = None
                    off = 0
                    for u in units:
                        if u[0] == "lit":
                            pred, ulen = lit_pred(u[1]), len(u[1])
                        else:
                            _, ranges, n = u
                            pred, ulen = erode(ranges, n), n
                        pred = shr(pred, K - 1 - off)
                        hit = pred if hit is None else hit & pred
                        off += ulen
                    acc = jnp.where(lane == j, blockmask_col(hit),
                                    acc)
                chain_ref[:, b128 * 128:(b128 + 1) * 128] = \
                    acc.astype(jnp.uint32)

    return kernel, kc128


def dfa_blockmask_pallas(segments: jax.Array, table,
                         dev_arrays: tuple,
                         interpret: bool = False) -> jax.Array:
    """[B, L] uint8 × resident band arrays → [B, n_patterns] uint32
    blockmasks. B must be a TILE_B multiple and L a multiple of
    N_BLOCKS×128 (callers bucket-pad — ops.keywords.pad_batch)."""
    B, L = segments.shape
    assert B % TILE_B == 0 and L % 128 == 0

    groups = table.groups
    padded = []
    kg128s = []
    for gi in range(len(groups)):
        for f in range(4):
            a = _pad128(dev_arrays[4 * gi + f].astype(jnp.uint32),
                        f >= 2)
            if f == 0:
                kg128s.append(a.shape[1])
            padded.append(a.reshape(-1))

    kernel, kc128 = _make_kernel(table, L)
    out_shapes = [
        jax.ShapeDtypeStruct((B, kg128s[gi]), jnp.uint32)
        for gi in range(len(groups))
    ]
    have_chains = bool(table.chains)
    if have_chains:
        out_shapes.append(jax.ShapeDtypeStruct((B, kc128),
                                               jnp.uint32))
    if not out_shapes:
        return jnp.zeros((B, 0), jnp.uint32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 * len(groups),
        grid=(B // TILE_B,),
        in_specs=[
            pl.BlockSpec((TILE_B, L), lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((TILE_B, s.shape[1]),
                         lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM)
            for s in out_shapes
        ],
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid_spec=grid_spec,
        interpret=interpret,
        # the operation's name in a profiler trace, whatever the
        # jitted program round it is called
        name="dfa_blockmask",
    )(*padded, segments)

    cols = [outs[gi][:, :g.count] for gi, g in enumerate(groups)]
    if have_chains:
        cols.append(outs[len(groups)][:, :len(table.chains)])
    return jnp.concatenate(cols, axis=1) if cols else \
        jnp.zeros((B, 0), jnp.uint32)
