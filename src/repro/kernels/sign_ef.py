"""Fused blockwise scaled-sign + error-feedback kernel (eqs. 29 + 20-21).

One pass over HBM computes BOTH the compressed message c = scale*sign(x+e)
(per-row L1 scale, blockwise scaled sign [39]) and the new error state
e' = (x+e) - c. Unfused this is 3 HBM reads + 2 writes; fused it is 2 reads
(x, e) + 2 writes (c, e') with the reduction kept in VMEM. The row variant
takes the per-row scale as an operand (one XLA reduction over x + e, 2 more
reads), so its blocks can tile the columns of rows wider than VMEM.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compat import match_vma


def _sign_ef_kernel(x_ref, e_ref, c_ref, e_out_ref):
    corrected = x_ref[...].astype(jnp.float32) + e_ref[...]
    scale = jnp.mean(jnp.abs(corrected), axis=1, keepdims=True)
    c = scale * jnp.sign(corrected)
    c_ref[...] = c.astype(c_ref.dtype)
    e_out_ref[...] = (corrected - c).astype(e_out_ref.dtype)


def sign_ef_pallas(x: jnp.ndarray, e: jnp.ndarray, *, block_rows: int = 8,
                   interpret: bool = False):
    """x: (rows, cols) grads; e: (rows, cols) fp32 error state.
    Returns (c fp32, e_new fp32)."""
    rows, cols = x.shape
    assert rows % block_rows == 0 and cols % 128 == 0
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        _sign_ef_kernel,
        name="sign_ef_compress",
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(e.shape, jnp.float32)),
        interpret=interpret,
    )(x, e)


def _sign_ef_rows_kernel(scale_ref, x_ref, e_ref, c_ref, e_out_ref):
    """Row-message variant: ``scale_ref`` holds each row's L1 scale
    mean|x+e| (computed outside the kernel, over the row's real width), so
    the block may hold any slice of the row."""
    corrected = x_ref[...].astype(jnp.float32) + e_ref[...]
    c = scale_ref[...] * jnp.sign(corrected)
    c_ref[...] = c.astype(c_ref.dtype)
    e_out_ref[...] = (corrected - c).astype(e_out_ref.dtype)


def sign_ef_rows_pallas(x: jnp.ndarray, e: jnp.ndarray, scale: jnp.ndarray,
                        *, block: Tuple[int, int], interpret: bool = False):
    """Per-client-row fused scaled-sign + EF. x, e: (rows, cols); scale:
    (rows, 1) per-row L1 scales; block: the (rows, cols) VMEM block.
    Returns (c fp32, e_new fp32)."""
    rows, cols = x.shape
    br, bc = block
    scale, x, e = match_vma(scale, x, e)
    out = jax.ShapeDtypeStruct(x.shape, jnp.float32, vma=jax.typeof(x).vma)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        _sign_ef_rows_kernel,
        name="sign_ef_rows",
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[pl.BlockSpec((br, 1), lambda i, j: (i, 0)), spec, spec],
        out_specs=(spec, spec),
        out_shape=(out, out),
        interpret=interpret,
    )(scale, x, e)
