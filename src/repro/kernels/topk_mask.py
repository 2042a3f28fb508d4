"""Block-local top-k sparsification kernel (paper §II.A.3, TPU-adapted).

Global top-k needs a full sort — MXU/VPU-hostile and serializing. The TPU
adaptation (DESIGN.md §3) selects the top-k *per VMEM-resident block row*
via threshold bisection: ~24 VPU reduction sweeps over the tile, no sort,
no data movement beyond one HBM read + one write. Same Θ(k) message size;
bounded skew vs exact top-k (tested against the oracle).

Tiling: :func:`block_topk_pallas` holds (8, cols) row groups in VMEM;
:func:`topk_rows_pallas` tiles columns too, for rows of any width.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import match_vma

N_BISECT = 24


def _topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...]  # (block_rows, cols) in VMEM
    absx = jnp.abs(x.astype(jnp.float32))
    hi = jnp.max(absx, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((absx >= mid).astype(jnp.int32), axis=1, keepdims=True)
        take_hi = cnt > k
        lo = jnp.where(take_hi, mid, lo)
        hi = jnp.where(take_hi, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, N_BISECT, body, (lo, hi))
    o_ref[...] = jnp.where(absx >= lo, x, jnp.zeros_like(x))


def block_topk_pallas(x: jnp.ndarray, k: int, *, block_rows: int = 8,
                      interpret: bool = False) -> jnp.ndarray:
    """x: (rows, cols) fp32/bf16; keeps ~k largest-|.| entries per row."""
    rows, cols = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    assert cols % 128 == 0, cols
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        name="block_topk",
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


def _topk_rows_kernel(k_ref, hi_ref, x_ref, o_ref, lo_s, hi_s, cnt_s, *,
                      cols: int):
    """Column-tiled form of :func:`_topk_kernel` for rows wider than VMEM.

    Grid ``(row block i, phase s, column block j)``. Phases
    ``0..N_BISECT-1`` are bisection steps: each sweeps the row's column
    blocks, accumulating the per-row count ``#{|x| >= mid}`` in ``cnt_s``,
    and the last block moves ``lo_s``/``hi_s``. Phase ``N_BISECT`` writes
    the keep-mask. ``k`` arrives as a (1, 1) scalar operand, so a *traced*
    keep-budget compiles into one kernel; ``hi_ref`` holds the row maxima.
    Counts are int32, exact at any width."""
    s, j = pl.program_id(1), pl.program_id(2)
    x = x_ref[...]
    absx = jnp.abs(x.astype(jnp.float32))

    @pl.when((s == 0) & (j == 0))
    def _init():
        lo_s[...] = jnp.zeros_like(lo_s)
        hi_s[...] = hi_ref[...]

    @pl.when(s < N_BISECT)
    def _count():
        mid = 0.5 * (lo_s[...] + hi_s[...])
        hit = absx >= mid
        bc = absx.shape[1]
        if cols % bc:  # ragged last column block: its tail is not the row's
            col = j * bc + jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
            hit = hit & (col < cols)

        @pl.when(j == 0)
        def _():
            cnt_s[...] = jnp.zeros_like(cnt_s)

        cnt_s[...] += jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            take_hi = cnt_s[...].astype(jnp.float32) > k_ref[0, 0]
            lo, hi = lo_s[...], hi_s[...]
            lo_s[...] = jnp.where(take_hi, mid, lo)
            hi_s[...] = jnp.where(take_hi, hi, mid)

    @pl.when(s == N_BISECT)
    def _write():
        o_ref[...] = jnp.where(absx >= lo_s[...], x, jnp.zeros_like(x))


def topk_rows_pallas(x: jnp.ndarray, k: jnp.ndarray, hi: jnp.ndarray, *,
                     block: Tuple[int, int], interpret: bool = False
                     ) -> jnp.ndarray:
    """Per-row top-k with a traced budget. x: (rows, cols); k: () or (1, 1)
    float — the per-row keep count (same for every row); hi: (rows, 1) row
    maxima of |x|; block: the (rows, cols) VMEM block."""
    rows, cols = x.shape
    br, bc = block
    k = jnp.asarray(k, jnp.float32).reshape(1, 1)
    k, hi, x = match_vma(k, hi, x)
    grid = (pl.cdiv(rows, br), N_BISECT + 1, pl.cdiv(cols, bc))
    return pl.pallas_call(
        functools.partial(_topk_rows_kernel, cols=cols),
        name="topk_rows",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, s, j: (0, 0)),
            pl.BlockSpec((br, 1), lambda i, s, j: (i, 0)),
            pl.BlockSpec((br, bc), lambda i, s, j: (i, j)),
        ],
        # the output block stays at column 0 until the write phase, so the
        # bisection phases cause no write-back
        out_specs=pl.BlockSpec(
            (br, bc), lambda i, s, j: (i, jnp.where(s == N_BISECT, j, 0))),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       vma=jax.typeof(x).vma),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32),
                        pltpu.VMEM((br, 1), jnp.float32),
                        pltpu.VMEM((br, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(k, hi, x)
