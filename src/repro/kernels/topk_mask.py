"""Top-k sparsification kernels (paper §II.A.3, TPU-adapted).

Global top-k needs a full sort — MXU/VPU-hostile and serializing. Neither
kernel sorts:

* :func:`block_topk_pallas` (the flat API) keeps ~k per VMEM-resident
  (8, cols) row group by threshold bisection: ~24 VPU reduction sweeps
  over the tile, one HBM read + one write; bounded skew vs exact top-k
  (tested against the oracle).
* :func:`topk_rows_pallas` (one row = one client's message, rows of any
  width) keeps *exactly* every coordinate at or above the row's k-th
  largest ``|x|``. The threshold is found by a radix select over the int32
  bit pattern of ``|x|`` (sign cleared, so it orders like the values):
  ``DIGIT_BITS`` bits a pass, ``N_PASSES`` counting sweeps over the row
  and one write sweep. Integer compares on the bits, so the threshold is
  the bisection ``bench/compressors/topk.py`` computes, bit for bit.
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

# radix select of topk_rows: the digit width of a counting pass, the passes
# it takes over bits 30..0 of |x|, and the candidates a pass counts. On a
# v5e at (2, 124,402,944), 3-bit digits (11 passes, 7 candidates) keep pace
# with HBM; 4-bit digits (8 passes, 15 candidates) are VPU-bound and slower
# (19.2 against 22.6 ms a call, PERF.md)
DIGIT_BITS = 3
N_PASSES = -(-31 // DIGIT_BITS)
_N_CAND = 2 ** DIGIT_BITS - 1
_ABS = 0x7FFFFFFF
_UNROLL = 4  # vreg chunks per loop step of the streaming kernel


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


def abs_bits(x: jnp.ndarray) -> jnp.ndarray:
    """The int32 bit pattern of ``|x|``: non-negative, ordered like the
    values (NaN above inf)."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                        jnp.int32) & _ABS


def _pass_shift(p):
    """Lowest bit of pass ``p``'s digit: passes take bits 30..0 from the
    top, ``DIGIT_BITS`` at a time, and the last one takes what is left."""
    return jnp.maximum(31 - DIGIT_BITS * (p + 1), 0)


def pass_candidates(prefix, p):
    """The thresholds pass ``p`` counts: ``prefix`` with digit c = 1 ..
    ``_N_CAND`` in the pass's bits."""
    s = _pass_shift(p)
    return [prefix | jnp.left_shift(jnp.int32(c), s)
            for c in range(1, _N_CAND + 1)]


def next_prefix(prefix, counts, k, p):
    """``prefix`` with pass ``p``'s digit set: the largest c with
    ``counts[c - 1] = #{bits >= candidate c} >= k``. Counts fall as c
    grows, so that digit is the number of such c. In the narrower last
    pass, the c that do not fit its bits never count. Counts are compared
    as float32, like the reference's."""
    s = _pass_shift(p)
    n_valid = jnp.left_shift(jnp.int32(1), 31 - DIGIT_BITS * p - s) - 1
    digit = sum(((cnt.astype(jnp.float32) >= k) & (c <= n_valid))
                .astype(jnp.int32) for c, cnt in enumerate(counts, 1))
    return prefix | jnp.left_shift(digit, s)


def _topk_rows_kernel(k_ref, x_ref, o_ref, prefix_s, cnt_s, *, cols: int):
    """Radix select on row blocks, for rows of any width and dtype.

    Grid ``(row block i, pass p, column block j)``. Passes ``0 ..
    N_PASSES - 1`` count, for each candidate c, ``#{bits >= candidate}``
    per row over the row's column blocks into lane c of ``cnt_s``; the
    last block sets the row's digit in ``prefix_s``. Pass ``N_PASSES``
    writes the rows. ``k`` arrives as a (1, 1) scalar operand, so a
    *traced* keep-budget compiles into one kernel. Counts are int32, exact
    at any width."""
    p, j = pl.program_id(1), pl.program_id(2)
    x = x_ref[...]
    bits = abs_bits(x)
    bc = bits.shape[1]
    if cols % bc:  # ragged last column block: its tail is not the row's
        col = j * bc + jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
        bits = jnp.where(col < cols, bits, -1)  # below every candidate

    @pl.when((p == 0) & (j == 0))
    def _init():
        prefix_s[...] = jnp.zeros_like(prefix_s)

    @pl.when(p < N_PASSES)
    def _count():
        @pl.when(j == 0)
        def _():
            cnt_s[...] = jnp.zeros_like(cnt_s)

        lane = jax.lax.broadcasted_iota(jnp.int32, cnt_s.shape, 1)
        upd = jnp.zeros_like(cnt_s)
        for c, cand in enumerate(pass_candidates(prefix_s[...], p), 1):
            hits = jnp.sum((bits >= cand).astype(jnp.int32), axis=1,
                           keepdims=True)
            upd += jnp.where(lane == c, hits, 0)
        cnt_s[...] += upd

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            cnt = cnt_s[...]
            counts = [jnp.sum(jnp.where(lane == c, cnt, 0), axis=1,
                              keepdims=True) for c in range(1, _N_CAND + 1)]
            prefix_s[...] = next_prefix(prefix_s[...], counts, k_ref[0, 0],
                                        p)

    @pl.when(p == N_PASSES)
    def _write():
        o_ref[...] = jnp.where(bits >= prefix_s[...], x, jnp.zeros_like(x))


def _topk_wide_kernel(k_ref, x_ref, o_ref, prefix_s, cnt_s, *, cols: int,
                      lanes: int):
    """Radix select on a few float32 rows much wider than a block.

    The operand keeps the layout XLA gives it, whose tiles hold all of the
    few rows ((2, 128) tiles for two rows), so a ``(rows, lanes)`` chunk of
    the block is one whole vreg. Each step streams its block chunk by
    chunk, read through an int32 view of the block (a value bitcast would
    relayout to (8, 128) tiles), and adds each candidate's hits into one
    vreg of ``cnt_s`` per candidate; the lanes are summed once per pass,
    at the row's last column block. Grid and phases as
    :func:`_topk_rows_kernel`, with one row block."""
    p, j = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1
    rows, bc = x_ref.shape
    bits_ref = x_ref.bitcast(jnp.int32)

    def chunk(t):
        return pl.ds(pl.multiple_of(t * lanes, lanes), lanes)

    def chunk_bits(t, ragged):
        bits = bits_ref[:, chunk(t)] & _ABS
        if ragged:
            col = (j * bc + t * lanes
                   + jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1))
            bits = jnp.where(col < cols, bits, -1)
        return bits

    @pl.when((p == 0) & (j == 0))
    def _init():
        prefix_s[...] = jnp.zeros_like(prefix_s)

    @pl.when(p < N_PASSES)
    def _count():
        @pl.when(j == 0)
        def _():
            cnt_s[...] = jnp.zeros_like(cnt_s)

        cands = pass_candidates(prefix_s[...], p)

        def count(ragged):
            def body(t, accs):
                for u in range(_UNROLL):
                    bits = chunk_bits(t * _UNROLL + u, ragged)
                    accs = tuple(acc + (bits >= cand).astype(jnp.int32)
                                 for acc, cand in zip(accs, cands))
                return accs

            zero = jnp.zeros((rows, lanes), jnp.int32)
            accs = jax.lax.fori_loop(0, bc // (lanes * _UNROLL), body,
                                     (zero,) * _N_CAND)
            for c, acc in enumerate(accs):
                cnt_s[c] += acc

        if cols % bc:
            pl.when(j < last)(lambda: count(False))
            pl.when(j == last)(lambda: count(True))
        else:
            count(False)

        @pl.when(j == last)
        def _():
            counts = [jnp.sum(cnt_s[c], axis=1, keepdims=True)
                      for c in range(_N_CAND)]
            prefix_s[...] = next_prefix(prefix_s[...], counts, k_ref[0, 0],
                                        p)

    @pl.when(p == N_PASSES)
    def _write():
        prefix = prefix_s[...]

        def body(t, carry):
            for u in range(_UNROLL):
                at = chunk(t * _UNROLL + u)
                x = x_ref[:, at]
                keep = (bits_ref[:, at] & _ABS) >= prefix
                o_ref[:, at] = jnp.where(keep, x, jnp.zeros_like(x))
            return carry

        jax.lax.fori_loop(0, bc // (lanes * _UNROLL), body, 0)


def topk_rows_pallas(x: jnp.ndarray, k: jnp.ndarray, *,
                     block: Tuple[int, int], lanes: int = 0,
                     interpret: bool = False) -> jnp.ndarray:
    """Exact per-row top-k with a traced budget: keeps every coordinate
    with ``|x|`` at or above the row's k-th largest. x: (rows, cols); k: ()
    or (1, 1) float — the per-row keep count (same for every row); block:
    the (rows, cols) VMEM block. ``lanes`` > 0 picks the streaming kernel
    for a few wide float32 rows: ``block`` then spans every row, and a
    (rows, lanes) chunk is one vreg."""
    rows, cols = x.shape
    br, bc = block
    k = jnp.asarray(k, jnp.float32).reshape(1, 1)
    k, x = match_vma(k, x)
    if lanes:
        assert br == rows and bc % (lanes * _UNROLL) == 0, (x.shape, block, lanes)
        kernel = functools.partial(_topk_wide_kernel, cols=cols, lanes=lanes)
        scratch = [pltpu.VMEM((br, lanes), jnp.int32),
                   pltpu.VMEM((_N_CAND, br, lanes), jnp.int32)]
    else:
        kernel = functools.partial(_topk_rows_kernel, cols=cols)
        scratch = [pltpu.VMEM((br, 1), jnp.int32),
                   pltpu.VMEM((br, 128), jnp.int32)]
    grid = (pl.cdiv(rows, br), N_PASSES + 1, pl.cdiv(cols, bc))
    return pl.pallas_call(
        kernel,
        name="topk_rows",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, p, j: (0, 0)),
            pl.BlockSpec((br, bc), lambda i, p, j: (i, j)),
        ],
        # the output block stays at column 0 until the write pass, so the
        # counting passes cause no write-back
        out_specs=pl.BlockSpec(
            (br, bc), lambda i, p, j: (i, jnp.where(p == N_PASSES, j, 0))),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       vma=jax.typeof(x).vma),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(k, x)
