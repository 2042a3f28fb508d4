"""Error accumulation / error feedback (paper §II.A.4, Alg. 3 & 6).

    c_t = comp(x_t + e_t)          (eq. 20)
    e_{t+1} = (x_t + e_t) - c_t    (eq. 21)

Generic over any compressor ``comp(x) -> (compressed, meta)``; works on flat
arrays or whole gradient pytrees (leaf-wise). The same wrapper implements the
PS-side (downlink) EF of Alg. 3 lines 16-20 — it is the identical recursion
applied to the aggregated message.

Fleet-scale state: :class:`SparseEF` stores the per-client EF matrix as
``(N, S)`` top-magnitude (value, index) pairs instead of a dense ``(N, D)``
matrix — O(N·S) memory for the top-k compressor family, where a handful of
residual slots per client captures most of the EF mass. Truncation makes
this an *approximate* EF mode (the exact eq. 21 residual of a top-k message
is dense); the truncation is per-row, so it is exactly chunk-invariant and
the engine's chunked/unchunked bitwise parity still holds within the mode.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp

Compressor = Callable[[jnp.ndarray], Tuple[jnp.ndarray, Any]]


class SparseEF(NamedTuple):
    """Top-S sparse EF state: per row, S (value, index) pairs."""
    values: jnp.ndarray    # (N, S) state dtype (fp32 or bf16)
    indices: jnp.ndarray   # (N, S) int32 coordinates into the D-dim message


def init_sparse_error(n: Union[int, Tuple[int, ...]], d: int, slots: int,
                      dtype=jnp.float32) -> SparseEF:
    """``n`` rows, or a row shape such as the chunked pass's (m, chunk)."""
    if not 1 <= slots <= d:
        raise ValueError(f"sparse EF needs 1 <= slots <= d, got "
                         f"slots={slots}, d={d}")
    rows = n if isinstance(n, tuple) else (n,)
    return SparseEF(jnp.zeros((*rows, slots), dtype),
                    jnp.zeros((*rows, slots), jnp.int32))


def densify_rows(ef: SparseEF, d: int) -> jnp.ndarray:
    """(N, S) sparse EF -> dense (N, D) fp32 (scatter per row)."""
    def one(vals, idx):
        return jnp.zeros(d, jnp.float32).at[idx].set(vals.astype(jnp.float32))
    return jax.vmap(one)(ef.values, ef.indices)


def sparsify_rows(resid: jnp.ndarray, slots: int, dtype=jnp.float32
                  ) -> SparseEF:
    """Dense (N, D) residual -> top-|.| (N, S) sparse EF (truncated).

    Per-row ``lax.top_k`` on |resid|, so the result depends only on each
    row's own values — chunk-invariant by construction.
    """
    def one(r):
        _, idx = jax.lax.top_k(jnp.abs(r), slots)
        return r[idx].astype(dtype), idx.astype(jnp.int32)
    vals, idx = jax.vmap(one)(resid.astype(jnp.float32))
    return SparseEF(vals, idx)


def init_error_state(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros_like(x, dtype=jnp.float32)


def ef_compress(comp: Compressor, x: jnp.ndarray, e: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Returns (compressed, new_error, meta)."""
    corrected = x.astype(jnp.float32) + e
    c, meta = comp(corrected.astype(x.dtype))
    e_new = corrected - c.astype(jnp.float32)
    return c, e_new, meta


def tree_init_error(tree: Any) -> Any:
    return jax.tree.map(init_error_state, tree)


def tree_ef_compress(comp: Compressor, tree: Any, e_tree: Any
                     ) -> Tuple[Any, Any]:
    """Leaf-wise EF over a gradient pytree. Returns (compressed_tree, new_e)."""
    flat, treedef = jax.tree.flatten(tree)
    e_flat = jax.tree.leaves(e_tree)
    outs, errs = [], []
    for x, e in zip(flat, e_flat):
        c, e_new, _ = ef_compress(comp, x, e)
        outs.append(c)
        errs.append(e_new)
    return jax.tree.unflatten(treedef, outs), jax.tree.unflatten(treedef, errs)


def is_k_contraction(comp: Compressor, x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Check Def. 1 (eq. 22): E||x - comp(x)||^2 <= (1 - k/d) ||x||^2.

    Returns the boolean for one realization (property tests average over
    seeds for randomized compressors).
    """
    c, _ = comp(x)
    lhs = jnp.sum((x.astype(jnp.float32) - c.astype(jnp.float32)) ** 2)
    rhs = (1.0 - k / x.size) * jnp.sum(x.astype(jnp.float32) ** 2)
    return lhs <= rhs + 1e-5 * jnp.maximum(rhs, 1.0)
