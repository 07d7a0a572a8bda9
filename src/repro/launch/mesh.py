"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16 x 16 = 256 chips (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model); the "pod" axis is an
extra data-parallel dimension whose collectives cross the inter-pod (DCN)
links -- the dry-run proves the HLO shards across it.
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """AbstractMesh with Auto axis types (rule logic only needs .shape)."""
    return jax.sharding.AbstractMesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (same axis names)."""
    return make_mesh((1, 1), ("data", "model"))
