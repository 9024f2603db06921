"""Production mesh construction.

Functions, not module-level constants — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before init).

Every mesh of the system is built by ``make_mesh``, with ``Auto`` axes:
the GSPMD semantics the sharding rules, ``shard_hint`` constraints and
worker vmaps are written for.  ``jax.make_mesh`` alone defaults to
``Explicit`` axes (sharding in types), under which the embedding gather
and the payload stacking reject their operands.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ("data","model"); multi_pod prepends a
    2-way "pod" axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(devices=None):
    """A ("data", "model") = (n, 1) mesh over ``devices`` (default: every
    device of this host) — the data-parallel trainer's mesh."""
    devices = jax.devices() if devices is None else list(devices)
    return make_mesh((len(devices), 1), ("data", "model"), devices=devices)


def n_workers(mesh) -> int:
    """DCGD worker count = product of data-like axes (pod x data)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)
