"""JAX runtime helpers (JAX >= 0.9, pinned in pyproject): the variant mesh,
``shard_map`` carry typing, and the persistent compilation cache.

Inside ``jax.shard_map`` every value carries the set of manual mesh axes it
varies over, and ``lax.scan`` requires a carry to keep that set from one
iteration to the next. A carry seeded from constants or replicated inputs
starts invariant and turns varying after one step of per-shard work, so
:func:`vary_like` types it up front. A ``pallas_call`` cannot infer that
set for its results: :func:`match_vma` aligns its operands, whose set the
results then declare.
"""
from __future__ import annotations

import os
from typing import Any, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "match_vma", "use_compile_cache", "vary_like"]

# <checkout>/.jax_cache (gitignored): a fixed path, so reruns hit the cache
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def make_mesh(devices, axis_name: str) -> Mesh:
    """1-D mesh over an explicit device sequence. The axis is ``Auto``
    (``jax.make_mesh`` now defaults to ``Explicit``): the engine places work
    on it only through ``shard_map``."""
    return Mesh(np.asarray(list(devices)), (axis_name,),
                axis_types=(AxisType.Auto,))


def _vary_over(x: jax.Array, axes: frozenset) -> jax.Array:
    missing = tuple(sorted(axes - jax.typeof(x).vma))
    return lax.pcast(x, missing, to="varying") if missing else x


def vary_like(tree: Any, ref: jax.Array) -> Any:
    """Cast every leaf of ``tree`` to vary over the manual axes ``ref``
    varies over. Outside ``shard_map`` (no manual axes) it returns ``tree``
    unchanged."""
    axes = jax.typeof(ref).vma
    return jax.tree.map(lambda x: _vary_over(x, axes), tree) if axes else tree


def match_vma(*arrays: jax.Array) -> Tuple[jax.Array, ...]:
    """Cast ``arrays`` to vary over every manual axis any of them varies
    over: the operands of one ``pallas_call`` must agree, and its results
    then declare the same axes (``vma=jax.typeof(operand).vma``)."""
    axes = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    return tuple(_vary_over(a, axes) for a in arrays)


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache for this process and return
    its directory. Entry points call this before their first compile; it is
    never called at import time. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing else is set; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.normpath(_CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
