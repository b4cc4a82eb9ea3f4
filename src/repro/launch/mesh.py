"""Production mesh construction (assignment §MULTI-POD DRY-RUN).

Defined as functions (not module constants) so importing never touches jax
device state. Single pod: 16x16 = 256 chips (data, model); multi-pod:
2x16x16 = 512 chips (pod, data, model) — 'pod' is the slow DCI axis carrying
the outer data-parallel dimension.
"""

from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis explicitly ``Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
