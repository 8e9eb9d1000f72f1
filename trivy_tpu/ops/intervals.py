"""Vectorized version-interval membership — the package→CVE kernel.

The reference compares versions pair-by-pair in Go (compare.go:21-56,
ospkg drivers). TPU re-design: the host parses every version string
once per batch, ranks them within their grammar's total order, and
compiles each advisory's constraints into ≤M half-open intervals in a
DOUBLED rank space (bound = 2·rank, exclusivity = ±1) — after which
"is version v vulnerable to advisory a" is pure int32 compares over a
[P, M] table, identical for every grammar and for both the library
and OS-package detectors.

Semantics bits per pair (flags):
  bit0 has_vulnerable_constraints
  bit1 force (empty-string constraint ⇒ always vulnerable)
  bit2 has_secure_constraints (patched + unaffected)

out = force | (has_vuln ? vuln_any & (has_sec ? ¬sec_any : 1)
                        : (has_sec ? ¬sec_any : 0))
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .program import DeviceProgram

# NOTE: the donated kernels below free their per-batch payload
# buffers as soon as the kernel consumes them (the slot-reuse
# contract of the async runtime). The hit output is bool while the
# payloads are int32, so XLA can never ALIAS input to output and
# emits its "Some donated buffers were not usable" advisory — that
# advisory is expected here, not a bug. The CLI/benchmark entry points
# (and pytest.ini) filter it at the APPLICATION level; this library
# module deliberately does not mutate the process-global warning
# filters, so embedders keep the signal for their own jax code.

MAX_INTERVALS = 4          # per side; host falls back past this
NEG_INF = -(2 ** 31) + 1
POS_INF = 2 ** 31 - 1


def _hits(pkg_rank, vuln_lo, vuln_hi, sec_lo, sec_hi, flags):
    r = pkg_rank[:, None]
    vuln_any = ((vuln_lo <= r) & (r <= vuln_hi)).any(axis=1)
    sec_any = ((sec_lo <= r) & (r <= sec_hi)).any(axis=1)

    has_vuln = (flags & 1).astype(bool)
    force = (flags & 2).astype(bool)
    has_sec = (flags & 4).astype(bool)

    not_sec = jnp.where(has_sec, ~sec_any, True)
    with_vuln = vuln_any & not_sec
    without_vuln = jnp.where(has_sec, ~sec_any, False)
    return force | jnp.where(has_vuln, with_vuln, without_vuln)


# Both implementations run under one named scope, so a profiler
# trace names their operations ``…/interval_hits/…`` whatever jitted
# program (plain, donated, shard_map) they were compiled into.

def interval_hits_impl(pkg_rank: jax.Array, vuln_lo: jax.Array,
                       vuln_hi: jax.Array, sec_lo: jax.Array,
                       sec_hi: jax.Array,
                       flags: jax.Array) -> jax.Array:
    """[P] ranks × [P, M] interval tables → [P] bool vulnerable."""
    with jax.named_scope("interval_hits"):
        return _hits(pkg_rank, vuln_lo, vuln_hi, sec_lo, sec_hi,
                     flags)


interval_hits = DeviceProgram(jax.jit(interval_hits_impl),
                              "interval_hits")

# donated variant for the async slot runtime (docs/performance.md
# "Async device runtime"): every operand is a PER-BATCH payload
# buffer staged into a dispatch-ring slot, so the kernel may reuse
# the slot's HBM for its output — collect frees the slot for the
# next upload instead of holding two copies alive per in-flight
# batch. Callers must device_put fresh buffers per dispatch and
# never touch them again (the arrays are deleted after the call).
interval_hits_donated = DeviceProgram(
    jax.jit(interval_hits_impl, donate_argnums=(0, 1, 2, 3, 4, 5)),
    "interval_hits")


def interval_hits_resident_impl(pkg_rank: jax.Array,
                                row_idx: jax.Array,
                                vuln_lo: jax.Array, vuln_hi: jax.Array,
                                sec_lo: jax.Array, sec_hi: jax.Array,
                                flags: jax.Array) -> jax.Array:
    """Resident-table variant: the [N, M] advisory tables live in HBM
    across scans (compiled once at DB load — SURVEY §7 step 5); each
    dispatch gathers only the candidate rows. [P] pkg ranks + [P] row
    indices → [P] bool."""
    with jax.named_scope("interval_hits"):
        return _hits(pkg_rank, vuln_lo[row_idx], vuln_hi[row_idx],
                     sec_lo[row_idx], sec_hi[row_idx],
                     flags[row_idx])


interval_hits_resident = DeviceProgram(
    jax.jit(interval_hits_resident_impl), "interval_hits_resident")

# resident variant: ONLY the per-batch gather operands (pkg ranks +
# candidate row indices) are donated — argnums 2..6 are the
# HBM-resident advisory tables shared by every dispatch of a DB
# generation, and donating one would free the store under every
# concurrent scanner (the buffer-donation audit's hard rule:
# payload buffers yes, resident tables never).
interval_hits_resident_donated = DeviceProgram(
    jax.jit(interval_hits_resident_impl, donate_argnums=(0, 1)),
    "interval_hits_resident")


def interval_hits_host(pkg_rank, vuln_lo, vuln_hi, sec_lo, sec_hi,
                       flags):
    """NumPy reference (differential testing)."""
    import numpy as np
    r = pkg_rank[:, None]
    vuln_any = ((vuln_lo <= r) & (r <= vuln_hi)).any(axis=1)
    sec_any = ((sec_lo <= r) & (r <= sec_hi)).any(axis=1)
    has_vuln = (flags & 1).astype(bool)
    force = (flags & 2).astype(bool)
    has_sec = (flags & 4).astype(bool)
    not_sec = np.where(has_sec, ~sec_any, True)
    without_vuln = np.where(has_sec, ~sec_any, False)
    return force | np.where(has_vuln, vuln_any & not_sec,
                            without_vuln)
