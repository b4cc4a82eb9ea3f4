"""Ambient mesh context.

Model code calls ``shard(x, axes...)`` for activation sharding constraints; on
a single device (smoke tests) these are no-ops, under ``use_mesh`` they become
``with_sharding_constraint`` with the ambient mesh (MaxText-style). Axis names
that don't exist on the active mesh are dropped (so the same model code runs
on (data, model) and (pod, data, model) meshes).
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

_ACTIVE_MESH: Optional[jax.sharding.Mesh] = None

AxisName = Union[str, Sequence[str], None]


@contextlib.contextmanager
def use_mesh(mesh: Optional[jax.sharding.Mesh]):
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield mesh
        else:
            yield None
    finally:
        _ACTIVE_MESH = prev


def get_mesh() -> Optional[jax.sharding.Mesh]:
    return _ACTIVE_MESH


def axis_size(name: str) -> int:
    mesh = _ACTIVE_MESH
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def _filter_axes(axes, shape=None) -> Optional[P]:
    """Drop axis names not on the active mesh; widen 'data' to ('pod','data')
    (batch-like dims span both data-parallel axes — constraining to 'data'
    alone forces XLA to reshard pod-sharded inputs, a multi-pod bug the
    dry-run exposed as per-token KV-cache collective-permutes); drop axes
    whose product doesn't divide the dim."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return None
    names = set(mesh.axis_names)

    def keep(i: int, a: AxisName):
        if a is None:
            return None
        if a == "data" or (isinstance(a, tuple) and a == ("data",)):
            a = ("pod", "data")
        if isinstance(a, str):
            a = (a,)
        kept = tuple(x for x in a if x in names)
        if not kept:
            return None
        if shape is not None:
            size = 1
            for x in kept:
                size *= mesh.shape[x]
            if shape[i] % size != 0:
                # try the suffix (e.g. batch=16 divisible by data but not
                # pod*data)
                while kept and shape[i] % size != 0:
                    size //= mesh.shape[kept[0]]
                    kept = kept[1:]
                if not kept:
                    return None
        return kept if len(kept) > 1 else kept[0]

    return P(*[keep(i, a) for i, a in enumerate(axes)])


def shard(x, *axes: AxisName):
    """Apply a sharding constraint if a mesh is active, else no-op."""
    spec = _filter_axes(axes, getattr(x, "shape", None))
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def pspec(*axes: AxisName) -> P:
    """PartitionSpec filtered to the active mesh (P() when no mesh)."""
    spec = _filter_axes(axes)
    return spec if spec is not None else P()
