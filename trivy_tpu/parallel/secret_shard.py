"""Mesh-sharded secret sieve: the row layout a mesh dispatch uploads.

A mesh dispatch (secret/batch.py ``_dispatch``) runs the host steps of
a one-chip fused dispatch: files are LPT-assigned to per-shard row
blocks of one buffer (``_layout``, parallel.balance), packed on the
calling thread (``_pack``), uploaded by one ``device_put`` to the row
sharding below, and sieved by ONE shard_map dispatch
(``ops.dfa.DfaTable.mesh_sieve``) in which each chip compacts its own
hit rows; the fetch brings home each chip's hit rows and the fused
decode loop reads them. Only the device program differs from one
chip's.

The DFA band table is tiny (KBs), so every device holds the FULL
table — replicated once per (rule-set generation, device) through
the same ResidentTables machinery as the advisory DB — and the data
axis gets ALL the parallelism; no collective is needed.

The reference analog is the client/server work split (SURVEY.md
§2.6): N thin clients → 1 stateful server over Twirp becomes
N data shards → per-chip resident rule tables over ICI.

``sharded_blockmask`` (the round-5 shard_map literal kernel) is kept
for the ops-level tests and the legacy ``run_blockmask`` path.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.keywords import CODE_CHUNK, _bucket, code_blockmask_impl
from .mesh import (DATA_AXIS, RULES_AXIS, mesh_axis_sizes,
                   pad_to_multiple)


def sieve_rows(rows: int, n_devices: int) -> int:
    """The rows of the buffer a dispatch uploads for ``rows``
    segment rows: their ``ops.keywords._bucket`` rung, a multiple of
    ``n_devices`` pallas tiles (TILE_B 32 rows). On one device, and
    on the meshes ``make_mesh`` builds, the rung itself."""
    return pad_to_multiple(_bucket(rows), n_devices * 32)


def row_sharding(mesh):
    """The placement of a mesh sieve's segment buffer: its rows
    split over every chip, flat."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P((DATA_AXIS, RULES_AXIS), None))


# ---------------------------------------------------------------------
# round-5 shard_map literal kernel (kept for ops-level parity tests)
# ---------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _build_blockmask(mesh, L: int):
    import jax
    from jax.sharding import PartitionSpec as P

    def local(segments, lo_c, hi_c, lo_m, hi_m):
        masks = code_blockmask_impl(segments, lo_c, hi_c, lo_m, hi_m)
        return jax.lax.all_gather(masks, RULES_AXIS, axis=1, tiled=True)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(RULES_AXIS), P(RULES_AXIS),
                  P(RULES_AXIS), P(RULES_AXIS)),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_blockmask(mesh, segments: np.ndarray, codes: tuple)\
        -> np.ndarray:
    """[B, L] segments × padded code arrays → [B, Kp] uint32 masks.

    Codes are padded so each rules-shard holds a CODE_CHUNK multiple;
    pad codes never match real text (zero code + full mask)."""
    d, r = mesh_axis_sizes(mesh)
    B, L = segments.shape
    K = codes[0].shape[0]
    Bp = pad_to_multiple(B, d)
    Kp = pad_to_multiple(K, r * CODE_CHUNK)

    if Bp != B:
        segments = np.concatenate(
            [segments, np.zeros((Bp - B, L), segments.dtype)])
    padded = []
    for i, a in enumerate(codes):
        if Kp != K:
            pad = np.zeros(Kp - K, a.dtype)
            if i >= 2:
                pad = pad + np.uint32(0xFFFFFFFF)
            a = np.concatenate([np.asarray(a), pad])
        padded.append(np.asarray(a))

    fn = _build_blockmask(mesh, L)
    masks = np.asarray(fn(segments, *padded))
    return masks[:B, :K]
