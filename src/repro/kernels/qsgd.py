"""QSGD stochastic uniform quantization kernel (paper §II.B.1, eq. 24-25).

Elementwise + per-tile reductions, one HBM pass. The stochastic rounding
noise is passed as an operand (generated by jax.random outside) so the
interpret-mode validation is bit-exact vs the oracle; a production TPU build
would switch to pltpu.prng_random_bits seeded per-tile (noted in DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compat import match_vma


def _qsgd_kernel(x_ref, u_ref, norm_ref, o_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...]
    norm = norm_ref[0, 0]
    scaled = jnp.abs(x) / jnp.maximum(norm, 1e-30) * levels
    lower = jnp.floor(scaled)
    frac = scaled - lower
    q = (lower + (u < frac).astype(jnp.float32)) / levels
    o_ref[...] = (jnp.sign(x) * q * norm).astype(o_ref.dtype)


def qsgd_pallas(x: jnp.ndarray, u: jnp.ndarray, norm: jnp.ndarray,
                levels: int = 256, *, block_rows: int = 8,
                interpret: bool = False) -> jnp.ndarray:
    """x, u: (rows, cols); norm: (1, 1) global L2 norm of x."""
    rows, cols = x.shape
    assert rows % block_rows == 0 and cols % 128 == 0
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_qsgd_kernel, levels=levels),
        name="qsgd_quantize",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, u, norm)


def _qsgd_rows_kernel(levels_ref, x_ref, u_ref, norms_ref, o_ref):
    """Per-row variant: each row is one client's message with its own L2
    norm (computed outside the kernel, so the block may hold any slice of
    the row); ``levels`` arrives as a (1, 1) scalar operand so a traced
    CompressionParams.levels sweep shares one compiled kernel."""
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...]
    levels = jnp.maximum(levels_ref[0, 0], 1.0)
    norms = norms_ref[...]  # (block_rows, 1) per-row L2 norms
    scaled = jnp.abs(x) / jnp.maximum(norms, 1e-30) * levels
    lower = jnp.floor(scaled)
    frac = scaled - lower
    q = (lower + (u < frac).astype(jnp.float32)) / levels
    # a select, not jnp.sign: sign's TPU lowering fails inside shard_map
    o_ref[...] = (jnp.where(x < 0, -q, q) * norms).astype(o_ref.dtype)


def qsgd_rows_pallas(x: jnp.ndarray, u: jnp.ndarray, norms: jnp.ndarray,
                     levels: jnp.ndarray, *, block: Tuple[int, int],
                     interpret: bool = False) -> jnp.ndarray:
    """x, u: (rows, cols); norms: (rows, 1) per-row L2 norms; levels: traced
    scalar quantization-level count; block: the (rows, cols) VMEM block."""
    rows, cols = x.shape
    assert norms.shape == (rows, 1), norms.shape
    br, bc = block
    levels = jnp.asarray(levels, jnp.float32).reshape(1, 1)
    levels, x, u, norms = match_vma(levels, x, u, norms)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        _qsgd_rows_kernel,
        name="qsgd_rows",
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            spec,
            spec,
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       vma=jax.typeof(x).vma),
        interpret=interpret,
    )(levels, x, u, norms)
