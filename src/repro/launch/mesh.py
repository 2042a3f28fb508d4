"""Production mesh builders (DESIGN.md §6).

Functions (not module-level constants) so importing never touches jax device
state. The dry-run sets XLA_FLAGS for 512 host devices *before* any jax
import; smoke tests and benches see the 1 real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the step functions place work through shard_map and
    # NamedSharding, not through explicit-sharding types
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests/examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The batch/client axes: ("pod","data") on the multi-pod mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_data_shards(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
