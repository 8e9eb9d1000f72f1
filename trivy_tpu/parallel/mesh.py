"""Device mesh construction.

A 2-D ``(data, rules)`` mesh over however many chips are visible:
``data`` shards batch items (segments, files, package rows), ``rules``
shards automaton/advisory tables. On a single chip both axes are 1 and
every sharded kernel degenerates to its local form — same code path.

The reference analog is the client/server work split (SURVEY.md §2.6):
N thin clients → 1 stateful server over Twirp becomes controller →
per-chip shards over ICI.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

DATA_AXIS = "data"
RULES_AXIS = "rules"


def make_mesh(n_devices: Optional[int] = None,
              rules_shards: Optional[int] = None,
              devices: Optional[Sequence] = None):
    """Build a ``Mesh`` with axes ``("data", "rules")``.

    ``rules_shards`` defaults to 2 when the device count allows a
    non-trivial split (≥4 and even), else 1 — rule-group tables are
    small, so the data axis gets the bulk of the parallelism.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(
            f"requested {n_devices} devices, have {len(devices)}")
    devices = list(devices)[:n_devices]

    if rules_shards is None:
        rules_shards = 2 if (n_devices >= 4 and n_devices % 2 == 0) else 1
    if n_devices % rules_shards:
        raise ValueError(
            f"n_devices={n_devices} not divisible by "
            f"rules_shards={rules_shards}")
    data = n_devices // rules_shards
    grid = np.asarray(devices, dtype=object).reshape(data, rules_shards)
    return Mesh(grid, (DATA_AXIS, RULES_AXIS))


def mesh_axis_sizes(mesh) -> tuple:
    """(data, rules) axis sizes of a mesh built by make_mesh."""
    return (mesh.shape[DATA_AXIS], mesh.shape[RULES_AXIS])


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is ≥ max(n, 1)."""
    n = max(n, 1)
    return ((n + m - 1) // m) * m
