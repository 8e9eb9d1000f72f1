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
per batch dispatch.
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

    def local(pkg_rank, row_idx, v_lo, v_hi, s_lo, s_hi, flags):
        return interval_hits_impl(
            pkg_rank, v_lo[row_idx], v_hi[row_idx],
            s_lo[row_idx], s_hi[row_idx], flags[row_idx])

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row, row, rep, rep, rep, rep, P(None)),
        out_specs=row,
        check_vma=False,
    )
    return DeviceProgram(jax.jit(fn), "interval_hits_resident_mesh")


def _pad_rows(n_devices: int, *arrs):
    """Pad leading dim to a device-count multiple; pads are trimmed
    from the output, so their (harmless) hit values never surface."""
    P_ = arrs[0].shape[0]
    Pp = pad_to_multiple(P_, n_devices)
    if Pp == P_:
        return arrs, P_
    out = []
    for a in arrs:
        pad_shape = (Pp - P_,) + a.shape[1:]
        out.append(np.concatenate([a, np.zeros(pad_shape, a.dtype)]))
    return tuple(out), P_


def sharded_interval_hits(mesh, pkg_rank, v_lo, v_hi, s_lo, s_hi,
                          flags) -> np.ndarray:
    """[P] ranks × per-pair [P, M] tables → [P] bool, pairs sharded
    over every chip in the mesh."""
    n = pkg_rank.shape[0]
    lazy = sharded_interval_hits_async(mesh, pkg_rank, v_lo, v_hi,
                                       s_lo, s_hi, flags)
    return np.asarray(lazy)[:n]


def sharded_interval_hits_async(mesh, pkg_rank, v_lo, v_hi, s_lo,
                                s_hi, flags):
    """Non-blocking variant for the slot runtime: pads + enqueues
    the shard_map dispatch and returns the LAZY device array (rows
    may carry device-multiple padding past the input length — pad
    rows are inert, callers trim on materialize)."""
    d, r = mesh_axis_sizes(mesh)
    (pkg_rank, v_lo, v_hi, s_lo, s_hi, flags), _n = _pad_rows(
        d * r, pkg_rank, v_lo, v_hi, s_lo, s_hi, flags)
    fn = _build_pair_hits(mesh)
    return fn(pkg_rank, v_lo, v_hi, s_lo, s_hi, flags)


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
                                   tables: tuple) -> np.ndarray:
    """[P] ranks + [P] candidate-row indices against replicated
    resident tables → [P] bool."""
    n = pkg_rank.shape[0]
    lazy = sharded_interval_hits_resident_async(
        mesh, pkg_rank, row_idx, tables)
    return np.asarray(lazy)[:n]


def sharded_interval_hits_resident_async(mesh, pkg_rank, row_idx,
                                         tables: tuple):
    """Non-blocking resident variant (see
    sharded_interval_hits_async): enqueue only, caller trims."""
    d, r = mesh_axis_sizes(mesh)
    (pkg_rank, row_idx), _n = _pad_rows(d * r, pkg_rank, row_idx)
    fn = _build_resident_hits(mesh)
    return fn(pkg_rank, row_idx, *tables)
