"""Jit'd public wrappers for the Pallas compression kernels.

Handle flattening/padding of arbitrary gradient arrays into the (rows, cols)
tile layout, and expose ``interpret=`` for CPU validation (default: interpret
on non-TPU backends).

Row-batched APIs (``topk_rows`` / ``qsgd_rows`` / ``sign_ef_rows``) treat
each row as one client's D-dim message — the layout of the engine's chunked
client pass — and take the compressor parameters (k, levels) as *traced*
scalars. They resolve one of three execution modes:

* ``"pallas"``    — real ``pallas_call`` (Mosaic). TPU only: this jax build
                    raises "Only interpret mode is supported on CPU backend"
                    for non-interpret pallas_call off-TPU.
* ``"interpret"`` — pallas interpreter; the CPU correctness/validation path.
* ``"jit"``       — compiled pure-jnp mirror of the kernel math; the
                    production fallback everywhere pallas can't lower.

``mode=None`` auto-resolves: "pallas" on TPU, "jit" elsewhere — so the same
engine dispatches to real kernels on TPU and never pays interpret-mode cost
on CPU.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.qsgd import qsgd_pallas, qsgd_rows_pallas
from repro.kernels.sign_ef import sign_ef_pallas, sign_ef_rows_pallas
from repro.kernels.topk_mask import (N_BISECT, block_topk_pallas,
                                     topk_rows_pallas)

_COLS = 1024
_ROWS_ALIGN = 8
_ROW_MODES = ("pallas", "interpret", "jit")


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_mode(mode: str | None) -> str:
    """Resolve the row-API execution mode (see module docstring)."""
    if mode is None:
        return "pallas" if jax.default_backend() == "tpu" else "jit"
    if mode not in _ROW_MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; known: {_ROW_MODES}")
    return mode


def _to_tiles(x: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Flatten + zero-pad to (rows, _COLS) with rows % 8 == 0."""
    flat = x.reshape(-1)
    n = flat.size
    per_tile = _COLS * _ROWS_ALIGN
    pad = (-n) % per_tile
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _COLS), n


def _from_tiles(tiles: jnp.ndarray, n: int, shape, dtype) -> jnp.ndarray:
    return tiles.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("k_frac", "interpret"))
def block_topk(x: jnp.ndarray, k_frac: float = 0.01,
               interpret: bool | None = None) -> jnp.ndarray:
    """Keep ~k_frac of entries per 1024-element block (phi in eq. 10)."""
    interpret = _default_interpret() if interpret is None else interpret
    tiles, n = _to_tiles(x)
    k = max(1, int(k_frac * _COLS))
    out = block_topk_pallas(tiles, k, interpret=interpret)
    return _from_tiles(out, n, x.shape, x.dtype)


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def qsgd_quantize(key, x: jnp.ndarray, levels: int = 256,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Unbiased stochastic uniform quantization of x (eq. 24-25)."""
    interpret = _default_interpret() if interpret is None else interpret
    tiles, n = _to_tiles(x)
    u = jax.random.uniform(key, tiles.shape, jnp.float32)
    norm = jnp.linalg.norm(x.astype(jnp.float32).reshape(-1)).reshape(1, 1)
    out = qsgd_pallas(tiles, u, norm, levels, interpret=interpret)
    return _from_tiles(out, n, x.shape, x.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_ef_compress(x: jnp.ndarray, e: jnp.ndarray,
                     interpret: bool | None = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused c = blockscale*sign(x+e), e' = (x+e) - c. e must be fp32 and
    x-shaped. Returns (c, e') with x's shape, fp32."""
    interpret = _default_interpret() if interpret is None else interpret
    tiles_x, n = _to_tiles(x)
    tiles_e, _ = _to_tiles(e)
    c, e_new = sign_ef_pallas(tiles_x, tiles_e, interpret=interpret)
    return (_from_tiles(c, n, x.shape, jnp.float32),
            _from_tiles(e_new, n, x.shape, jnp.float32))


# ---------------------------------------------------------------------------
# Row-batched APIs: one row = one client message (the chunked client pass)
# ---------------------------------------------------------------------------
# A row block holds at most _BLOCK_COLS columns and _BLOCK_BYTES of
# lane-padded operand, so every width compiles within the default scoped
# VMEM; ragged edge blocks are handled by the grid.
_BLOCK_COLS = 16384
_BLOCK_BYTES = 512 * 1024


def _row_block(x: jnp.ndarray) -> Tuple[int, int]:
    """The (rows, cols) VMEM block of the row kernels for operand ``x``: a
    whole dim where it fits the budget, else a multiple of the dtype's
    (sublane, 128) tile."""
    rows, cols = x.shape
    itemsize = x.dtype.itemsize
    sub = 32 // itemsize
    bc = cols if cols <= _BLOCK_COLS else _BLOCK_COLS
    br_cap = max(sub, _BLOCK_BYTES // (max(bc, 128) * itemsize) // sub * sub)
    return (rows if rows <= br_cap else br_cap), bc


def _topk_rows_jnp(x: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Compiled mirror of the bisection kernel (same math, same N_BISECT,
    int32 counts)."""
    absx = jnp.abs(x.astype(jnp.float32))
    hi = jnp.max(absx, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((absx >= mid).astype(jnp.int32), axis=1,
                      keepdims=True)
        take_hi = cnt.astype(jnp.float32) > k
        return jnp.where(take_hi, mid, lo), jnp.where(take_hi, hi, mid)

    lo, _ = jax.lax.fori_loop(0, N_BISECT, body, (lo, hi))
    return jnp.where(absx >= lo, x, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("mode",))
def topk_rows(x: jnp.ndarray, k: jnp.ndarray,
              mode: str | None = None) -> jnp.ndarray:
    """Per-row threshold-bisection top-k. x: (B, D); k: traced scalar keep
    budget shared by every row. Returns (B, D), x.dtype."""
    mode = resolve_mode(mode)
    k = jnp.asarray(k, jnp.float32)
    if mode == "jit":
        return _topk_rows_jnp(x, k)
    hi = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    return topk_rows_pallas(x, k, hi,
                            block=_row_block(x),
                            interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("mode",))
def qsgd_rows(x: jnp.ndarray, u: jnp.ndarray, levels: jnp.ndarray,
              mode: str | None = None) -> jnp.ndarray:
    """Per-row QSGD with per-row L2 norms. x, u: (B, D); u is the caller's
    stochastic-rounding noise (derived from per-client keys, so results are
    independent of how rows are batched); levels: traced scalar."""
    mode = resolve_mode(mode)
    levels = jnp.maximum(jnp.asarray(levels, jnp.float32), 1.0)
    norms = jnp.linalg.norm(x.astype(jnp.float32), axis=1, keepdims=True)
    if mode == "jit":
        xf = x.astype(jnp.float32)
        scaled = jnp.abs(xf) / jnp.maximum(norms, 1e-30) * levels
        lower = jnp.floor(scaled)
        q = (lower + (u < (scaled - lower)).astype(jnp.float32)) / levels
        return (jnp.sign(xf) * q * norms).astype(x.dtype)
    return qsgd_rows_pallas(x, u, norms, levels,
                            block=_row_block(x),
                            interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("mode",))
def sign_ef_rows(x: jnp.ndarray, e: jnp.ndarray, mode: str | None = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused per-row scaled-sign + EF update: c = mean|x+e| * sign(x+e),
    e' = (x+e) - c. x, e: (B, D). Returns (c, e') fp32."""
    mode = resolve_mode(mode)
    corrected = x.astype(jnp.float32) + e.astype(jnp.float32)
    scale = jnp.mean(jnp.abs(corrected), axis=1, keepdims=True)
    if mode == "jit":
        c = scale * jnp.sign(corrected)
        return c, corrected - c
    return sign_ef_rows_pallas(x, e.astype(jnp.float32), scale,
                               block=_row_block(x),
                               interpret=(mode == "interpret"))
