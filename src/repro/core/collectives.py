"""Compressed collectives: the paper's §II applied to the gradient all-reduce.

The uplink (device -> PS) becomes the reduce phase of an all-reduce over the
``data`` mesh axis; the downlink (PS -> device) becomes the broadcast phase.
We implement them explicitly inside ``shard_map`` so the *wire format* is
compressed (visible in the compiled HLO as s8/u8 all-to-all / all-gather):

  uplink:   quantize local grad -> all_to_all chunks -> local fp32 reduce
  downlink: requantize own chunk -> all_gather -> dequantize

Methods: none (fp32/bf16 psum), int8 (symmetric per-leaf scale, ~4x), sign
(scaled-sign, bit-packed, ~32x; EF strongly recommended [38]).
Client-side error feedback (eq. 20-21) wraps any method; the PS-side EF of
Alg. 3 is exercised at simulation scale in fl/server.py (DESIGN.md §9).

Small leaves (< ``min_size``) use a plain psum — their bytes are negligible
and the chunking overhead isn't worth it.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any

_POW2 = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], dtype=jnp.uint8)


# ---------------------------------------------------------------------------
# bit packing (sign mode): 8 signs per byte along axis 0
# ---------------------------------------------------------------------------
def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """bits: bool (d0, ...) with d0 % 8 == 0 -> uint8 (d0/8, ...)."""
    d0 = bits.shape[0]
    grouped = bits.reshape(d0 // 8, 8, *bits.shape[1:]).astype(jnp.uint8)
    pw = _POW2.reshape(1, 8, *([1] * (bits.ndim - 1)))
    return jnp.sum(grouped * pw, axis=1, dtype=jnp.uint8)


def unpack_bits(packed: jnp.ndarray) -> jnp.ndarray:
    """uint8 (c, ...) -> bool (8c, ...)."""
    pw = _POW2.reshape(1, 8, *([1] * (packed.ndim - 1)))
    bits = (packed[:, None] & pw) > 0
    return bits.reshape(packed.shape[0] * 8, *packed.shape[1:])


def _pad_dim0(x: jnp.ndarray, multiple: int) -> Tuple[jnp.ndarray, int]:
    d0 = x.shape[0]
    pad = (-d0) % multiple
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, d0


def _a2a_chunks(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """x: (n*c, ...) -> received (n, c, ...) — the reduce-scatter wire phase."""
    n = lax.axis_size(axis)
    chunks = x.reshape(n, x.shape[0] // n, *x.shape[1:])
    return lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0, tiled=False)


def _scale_chunks(full: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """full: (n*c, ...) gathered chunks; scales: (n,) -> chunk i times
    scales[i]."""
    n = scales.shape[0]
    chunks = full.reshape(n, full.shape[0] // n, *full.shape[1:])
    return (chunks * scales.reshape(n, *([1] * (chunks.ndim - 1)))
            ).reshape(full.shape)


# ---------------------------------------------------------------------------
# leaf-level compressed all-reduce
# ---------------------------------------------------------------------------
def compressed_allreduce_leaf(
    g: jnp.ndarray, axis: str, method: str = "none",
    e: Optional[jnp.ndarray] = None, min_size: int = 65_536,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """All-reduce-mean of ``g`` over ``axis`` with a compressed wire format.

    Returns (g_hat identical on all shards of ``axis``, new error state).
    """
    n = lax.axis_size(axis)
    gf = g.astype(jnp.float32)
    if method == "none" or g.size < min_size:
        if e is not None:
            gf = gf + e
        out = lax.pmean(gf, axis)
        return out, (gf - gf if e is not None else None)  # exact: no error
    if method == "bf16":
        if e is not None:
            gf = gf + e
        sent = gf.astype(jnp.bfloat16)
        out = lax.pmean(sent, axis).astype(jnp.float32)  # wire stays bf16
        return out, (gf - sent.astype(jnp.float32) if e is not None else None)

    corrected = gf + e if e is not None else gf
    # flatten to 2D so dim-0 padding to a multiple of n stays negligible
    # (padding the raw leading dim inflates stacked-layer leaves up to 100x —
    # measured and logged in EXPERIMENTS.md §Perf before this fix)
    last = g.shape[-1] if g.ndim > 1 else 1
    corrected2d = corrected.reshape(-1, last)

    if method == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(corrected)), 1e-20) / 127.0
        q = jnp.clip(jnp.round(corrected2d / scale), -127, 127).astype(jnp.int8)
        local_deq = (q.astype(jnp.float32) * scale).reshape(g.shape)
        e_new = corrected - local_deq if e is not None else None
        # uplink: int8 chunks + per-shard scales
        qp, d0 = _pad_dim0(q, n)
        recv = _a2a_chunks(qp, axis)                          # (n, c, ...) s8
        scales = lax.all_gather(scale, axis)                  # (n,)
        sview = scales.reshape(n, *([1] * (recv.ndim - 1)))
        mean_chunk = jnp.mean(recv.astype(jnp.float32) * sview, axis=0)
        # downlink: requantized int8 chunk + scalar scale
        scale2 = jnp.maximum(jnp.max(jnp.abs(mean_chunk)), 1e-20) / 127.0
        q2 = jnp.clip(jnp.round(mean_chunk / scale2), -127, 127).astype(jnp.int8)
        full = lax.all_gather(q2, axis, tiled=True)           # (n*c, ...) s8
        scales2 = lax.all_gather(scale2, axis)                # (n,)
        out = _scale_chunks(full.astype(jnp.float32), scales2)[:d0]
        return out.reshape(g.shape).astype(jnp.float32), e_new

    if method == "sign":
        # scaled sign (eq. 29): c = mean|x| * sign(x)
        scale = jnp.mean(jnp.abs(corrected))
        local_c = scale * jnp.sign(corrected)
        e_new = corrected - local_c if e is not None else None
        cp, d0 = _pad_dim0(corrected2d, 8 * n)
        packed = pack_bits(cp >= 0)                           # (d0p/8, ...)
        recv = _a2a_chunks(packed, axis)                      # (n, c8, ...) u8
        scales = lax.all_gather(scale, axis)                  # (n,)
        # unpack each shard's chunk to +-1 and take the scale-weighted mean
        def unpack_one(p):
            return unpack_bits(p).astype(jnp.float32) * 2.0 - 1.0
        signs = jax.vmap(unpack_one)(recv)                    # (n, c, ...)
        sview = scales.reshape(n, *([1] * (signs.ndim - 1)))
        mean_chunk = jnp.mean(signs * sview, axis=0)
        # downlink: scaled sign again (biased without PS-side EF; see docstring)
        scale2 = jnp.mean(jnp.abs(mean_chunk))
        packed2 = pack_bits(mean_chunk >= 0)
        full_packed = lax.all_gather(packed2, axis, tiled=True)
        scales2 = lax.all_gather(scale2, axis)                # (n,)
        full_signs = unpack_bits(full_packed).astype(jnp.float32) * 2.0 - 1.0
        out = _scale_chunks(full_signs, scales2)[:d0]
        return out.reshape(g.shape).astype(jnp.float32), e_new

    raise ValueError(f"unknown method {method!r}")


# ---------------------------------------------------------------------------
# tree-level API (+ hierarchical composition over several axes)
# ---------------------------------------------------------------------------
def tree_compressed_allreduce(tree: PyTree, axis: str, method: str = "none",
                              e_tree: Optional[PyTree] = None,
                              min_size: int = 65_536
                              ) -> Tuple[PyTree, Optional[PyTree]]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    e_leaves = jax.tree_util.tree_leaves(e_tree) if e_tree is not None else [None] * len(leaves)
    outs, errs = [], []
    for g, e in zip(leaves, e_leaves):
        o, en = compressed_allreduce_leaf(g, axis, method, e, min_size)
        outs.append(o)
        errs.append(en)
    out_tree = jax.tree_util.tree_unflatten(treedef, outs)
    err_tree = (jax.tree_util.tree_unflatten(treedef, errs)
                if e_tree is not None else None)
    return out_tree, err_tree


def hierarchical_allreduce(tree: PyTree, axes: Tuple[str, ...],
                           method: str = "none",
                           e_tree: Optional[PyTree] = None,
                           inner_method: Optional[str] = None,
                           min_size: int = 65_536
                           ) -> Tuple[PyTree, Optional[PyTree]]:
    """HFL collective schedule (Alg. 9 on the mesh): reduce over axes[-1]
    (intra-pod `data`, fast ICI) with ``method``, then over axes[:-1] (the
    `pod` axis, slow DCN) with ``inner_method`` (defaults to method).
    EF applies to the first (intra) stage only."""
    inner_method = inner_method or method
    e_out = e_tree
    first = True
    for ax in reversed(axes):
        if first:
            tree, e_out = tree_compressed_allreduce(tree, ax, method, e_tree,
                                                    min_size)
        else:
            tree, _ = tree_compressed_allreduce(tree, ax, inner_method, None,
                                                min_size)
        first = False
    return tree, e_out
