"""Off-chip compile guard (tier-1): the device programs of the main
path must COMPILE for a TPU v5e — Mosaic lowering, VMEM and tiling
included — without a chip.

libtpu builds a compile-only description of a ``v5e:2x2`` topology
(4 × ``TPU v5 lite``); ``jit(...).lower(...).compile()`` against its
devices runs the real TPU compiler. Nothing executes, so this says
nothing about results — ``chip_smoke.py`` compares those on the
chip — but a VMEM or tiling refusal introduced by a later PR fails
here, for no chip time. Skips cleanly where the description cannot
be built (no libtpu).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SEG_LEN = 2048


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:     # noqa: BLE001 — whatever libtpu (or
        # its absence) raises: this guard is best-effort
        pytest.skip(f"no compile-only TPU topology: {e!r}")
    assert [d.device_kind for d in topo.devices] == \
        ["TPU v5 lite"] * 4
    return topo.devices


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table_specs(table, sharding) -> list:
    return [_spec(a.shape, a.dtype, sharding)
            for a in table._resident_arrays()]


@pytest.fixture(scope="module")
def scanner():
    from trivy_tpu.secret.batch import BatchSecretScanner
    sc = BatchSecretScanner()
    assert sc.table.n_patterns == 160 and sc.seg_len == SEG_LEN
    return sc


@pytest.mark.parametrize("rows", [256, 4096])
def test_fused_sieve_compiles_for_v5e(v5e, scanner, rows):
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e[0])
    fn = scanner.table.fused_sieve(
        tuple(scanner.plan.run_specs), "tpu")
    compiled = fn.lower(_spec((rows, SEG_LEN), jnp.uint8, one),
                        *_table_specs(scanner.table, one)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel


def test_custom_rule_table_compiles_for_v5e(v5e, tmp_path):
    # a trivy-secret.yaml table: another chain unroll, more
    # memoised [32, L] membership/erosion arrays live in VMEM
    import chip_smoke
    from jax.sharding import SingleDeviceSharding
    from trivy_tpu.secret.batch import BatchSecretScanner
    from trivy_tpu.secret.model import load_config
    from trivy_tpu.secret.scanner import new_scanner
    cfg = tmp_path / "trivy-secret.yaml"
    cfg.write_text(chip_smoke.CUSTOM_RULES_YAML)
    sc = BatchSecretScanner(scanner=new_scanner(
        load_config(str(cfg))))
    assert len(sc.table.chains) > 16
    one = SingleDeviceSharding(v5e[0])
    sc.table.fused_sieve(tuple(sc.plan.run_specs), "tpu").lower(
        _spec((256, SEG_LEN), jnp.uint8, one),
        *_table_specs(sc.table, one)).compile()


def test_mesh_sieve_compiles_for_a_2x2_mesh(v5e, scanner):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from trivy_tpu.parallel.mesh import (DATA_AXIS, RULES_AXIS,
                                         make_mesh)
    mesh = make_mesh(4, devices=v5e)
    assert dict(mesh.shape) == {DATA_AXIS: 2, RULES_AXIS: 2}
    rows = NamedSharding(mesh, P((DATA_AXIS, RULES_AXIS), None))
    fn = scanner.table.mesh_sieve(
        mesh, tuple(scanner.plan.run_specs), "tpu")
    tables = [_spec(a.shape, a.dtype,
                    NamedSharding(mesh, P(*([None] * a.ndim))))
              for a in scanner.table._resident_arrays()]
    fn.lower(_spec((4 * 128, SEG_LEN), jnp.uint8, rows),
             *tables).compile()


def test_mesh_sieve_compiles_at_the_ladders_top_rung(v5e, scanner):
    # what runtime/aot.warm_ladders warms last on a mesh: a full
    # scheduled batch (8,192 rows), 2,048 a chip
    from jax.sharding import NamedSharding, PartitionSpec as P
    from trivy_tpu.parallel.mesh import (DATA_AXIS, RULES_AXIS,
                                         make_mesh)
    from trivy_tpu.parallel.secret_shard import sieve_rows
    mesh = make_mesh(4, devices=v5e)
    rows = NamedSharding(mesh, P((DATA_AXIS, RULES_AXIS), None))
    tables = [_spec(a.shape, a.dtype,
                    NamedSharding(mesh, P(*([None] * a.ndim))))
              for a in scanner.table._resident_arrays()]
    fn = scanner.table.mesh_sieve(
        mesh, tuple(scanner.plan.run_specs), "tpu")
    compiled = fn.lower(_spec((sieve_rows(8192, 4), SEG_LEN),
                              jnp.uint8, rows), *tables).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_full_sieve_compiles_at_the_ladders_top_rung(v5e,
                                                          scanner):
    # the whole-mask twin a mesh dispatch falls back to where a
    # chip's hit rows overflow its capacity, warmed at the top rung
    from trivy_tpu.parallel.mesh import make_mesh
    from trivy_tpu.parallel.secret_shard import row_sharding, sieve_rows
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(4, devices=v5e)
    tables = [_spec(a.shape, a.dtype,
                    NamedSharding(mesh, P(*([None] * a.ndim))))
              for a in scanner.table._resident_arrays()]
    fn = scanner.table.mesh_full_sieve(mesh, (), "tpu")
    compiled = fn.lower(_spec((sieve_rows(8192, 4), SEG_LEN),
                              jnp.uint8, row_sharding(mesh)),
                        *tables).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_resident_interval_compiles_for_a_2x2_mesh(v5e):
    # a wave's 4,096 pairs over four chips, against trivy-db's
    # 2,040,000 rows replicated on each (k8s-cluster-4chip)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from trivy_tpu.ops.intervals import MAX_INTERVALS
    from trivy_tpu.parallel.interval_shard import _build_resident_hits
    from trivy_tpu.parallel.mesh import (DATA_AXIS, RULES_AXIS,
                                         make_mesh)
    mesh = make_mesh(4, devices=v5e)
    pairs = _spec((4096,), np.int32,
                  NamedSharding(mesh, P((DATA_AXIS, RULES_AXIS))))
    bounds = _spec((2_040_000, MAX_INTERVALS), np.int32,
                   NamedSharding(mesh, P(None, None)))
    flags = _spec((2_040_000,), np.int32, NamedSharding(mesh, P(None)))
    compiled = _build_resident_hits(mesh).lower(
        pairs, pairs, bounds, bounds, bounds, bounds, flags).compile()
    mem = compiled.memory_analysis()
    # the replicated table is every chip's own: 2,040,000 rows of
    # 4 x 4 + 1 int32, about 139 MB a chip
    assert mem.argument_size_in_bytes >= 2_040_000 * 17 * 4


@pytest.mark.parametrize("table_rows", [1_000_000, 2_000_000])
def test_resident_interval_kernel_compiles_for_v5e(v5e, table_rows):
    from jax.sharding import SingleDeviceSharding
    from trivy_tpu.ops.intervals import (
        MAX_INTERVALS, interval_hits_resident_donated)
    one = SingleDeviceSharding(v5e[0])
    pairs = _spec((8192,), np.int32, one)
    bounds = _spec((table_rows, MAX_INTERVALS), np.int32, one)
    interval_hits_resident_donated.lower(
        pairs, pairs, bounds, bounds, bounds, bounds,
        _spec((table_rows,), np.int32, one)).compile()
