"""Mesh-sharded interval-membership kernels — the vuln half of the
fleet pipeline.

The pair table has no "rules" dimension (each row already names its
advisory), so pairs shard over the FLATTENED mesh — every chip on both
axes takes a slice of the (package, advisory) rows. Advisory tables:

  - dense path (per-dispatch [P, M] tables): sharded with the rows;
  - resident path: the [N, M] compiled-DB tables are REPLICATED to
    every chip (they are the server-held state in the reference's
    client/server split, pkg/rpc/server/server.go:37-48 — each chip
    is a "server" holding the full DB, pairs are the thin-client
    traffic), and each shard gathers only its own candidate rows.

No collective is needed: hits are element-wise per pair, so the
output inherits the input sharding and the host reads it back once
per wave (detect/batch.py enqueues each wave through the lazy calls
below and fetches it at collect).
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.intervals import interval_hits_impl
from ..ops.program import DeviceProgram
from .mesh import (DATA_AXIS, RULES_AXIS, mesh_axis_sizes,
                   pad_to_multiple)

_PAIR_AXES = (DATA_AXIS, RULES_AXIS)


@functools.lru_cache(maxsize=8)
def _build_pair_hits(mesh):
    import jax
    from jax.sharding import PartitionSpec as P

    row = P(_PAIR_AXES)
    tbl = P(_PAIR_AXES, None)

    # check_vma off: hits are row-elementwise, every output is a
    # per-shard value with no replication to verify
    fn = jax.shard_map(
        interval_hits_impl,
        mesh=mesh,
        in_specs=(row, tbl, tbl, tbl, tbl, row),
        out_specs=row,
        check_vma=False,
    )
    return DeviceProgram(jax.jit(fn), "interval_hits_mesh")


@functools.lru_cache(maxsize=8)
def _build_resident_hits(mesh):
    import jax
    from jax.sharding import PartitionSpec as P

    row = P(_PAIR_AXES)
    rep = P(None, None)

    # its name is the program's in a profiler trace
    # (jit_interval_hits_resident_mesh)
    def interval_hits_resident_mesh(pkg_rank, row_idx, v_lo, v_hi,
                                    s_lo, s_hi, flags):
        return interval_hits_impl(
            pkg_rank, v_lo[row_idx], v_hi[row_idx],
            s_lo[row_idx], s_hi[row_idx], flags[row_idx])

    fn = jax.shard_map(
        interval_hits_resident_mesh,
        mesh=mesh,
        in_specs=(row, row, rep, rep, rep, rep, P(None)),
        out_specs=row,
        check_vma=False,
    )
    return DeviceProgram(jax.jit(fn), "interval_hits_resident_mesh")


def shard_rows(mesh, *arrays) -> tuple:
    """A wave's upload to a mesh: pad the rows to a device multiple
    (pad rows are inert, and trimmed from the output) and place them
    sharded over every chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    d, r = mesh_axis_sizes(mesh)
    n = arrays[0].shape[0]
    pad = pad_to_multiple(n, d * r) - n
    return tuple(
        jax.device_put(
            np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            if pad else a,
            NamedSharding(mesh, P(_PAIR_AXES, *([None] * (a.ndim - 1)))))
        for a in arrays)


def sharded_interval_hits(mesh, pkg_rank, v_lo, v_hi, s_lo, s_hi,
                          flags):
    """[P'] ranks × per-pair [P', M] tables, placed by
    :func:`shard_rows` → LAZY [P'] bool, enqueued and not fetched.
    Pad rows are inert; callers trim on materialize."""
    return _build_pair_hits(mesh)(pkg_rank, v_lo, v_hi, s_lo, s_hi,
                                  flags)


def replicate_tables(mesh, tables: tuple) -> tuple:
    """Place compiled-DB advisory tables on every chip of the mesh
    (done once per (db, mesh); reused across dispatches)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = []
    for a in tables:
        spec = P(*([None] * np.ndim(a)))
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


def sharded_interval_hits_resident(mesh, pkg_rank, row_idx,
                                   tables: tuple):
    """[P'] ranks + [P'] candidate-row indices, placed by
    :func:`shard_rows`, against replicated resident tables → LAZY
    [P'] bool (see sharded_interval_hits)."""
    return _build_resident_hits(mesh)(pkg_rank, row_idx, *tables)
