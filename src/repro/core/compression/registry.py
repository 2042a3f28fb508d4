"""First-class compression registry for the compiled simulation engine.

The legacy API passed an opaque ``Callable`` compressor around, which (a)
could not report its bits-on-the-wire to the wireless layer, and (b) poisoned
the engine cache (two equal lambdas hash differently, defeating the
no-retrace property and vmapped sweeps). This registry replaces it:

* the compressor **name** is static (an engine-cache key / Python-loop axis);
* the compressor **parameters** travel in a traced :class:`CompressionParams`
  NamedTuple (continuous, so ``run_sweep`` can vmap a compression-level grid
  exactly like a channel grid);
* every operator is a pure-jnp function ``(CompressionParams, key, flat)
  -> (compressed_flat, bits)`` over the flattened per-client message, and its
  bit cost is *data-independent* given ``(name, params, d)`` — so the engine
  can price the uplink before transmission and feed it to
  ``wireless.comm_latency_jax`` / the scheduling policies inside the scan;
* :func:`uplink_bits_jax` is the standalone bit-cost model, validated against
  the exact Alg. 4 accounting in ``coding.py``
  (``sparse_message_bits`` / ``elias_gamma_bits``) by the test suite.

Operator semantics mirror the reference implementations in ``quantize.py`` /
``sparsify.py`` (which remain the per-leaf, statically-shaped references);
the registry versions accept *traced* k / levels / block so one compiled
engine serves a whole compression sweep.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression.coding import sparse_bits_jax

LOG2_3 = 1.584962500721156  # ternary alphabet cost, log2(3)
SCALE_BITS = 32.0           # one fp32 scale / norm per message


class CompressionParams(NamedTuple):
    """Traceable (vmappable) compressor parameters.

    Continuous on purpose: a sweep stacks these along a leading variant axis
    (see :func:`stack_compression_params`) and the engine vmaps over them.
    ``k`` is the kept-coordinate budget (topk / randk / rtopk), ``levels``
    the QSGD quantization levels, ``block`` the blockwise-scaled-sign block
    length. Unused fields are ignored by a given operator.
    """
    k: jnp.ndarray
    levels: jnp.ndarray
    block: jnp.ndarray


def compression_params(k: float = 1.0, levels: float = 256.0,
                       block: float = 4096.0) -> CompressionParams:
    return CompressionParams(k=jnp.float32(k), levels=jnp.float32(levels),
                             block=jnp.float32(block))


def default_compression_params(d: int) -> CompressionParams:
    """Sensible defaults for a d-dimensional message: 1% top-k, 8-bit QSGD."""
    return compression_params(k=max(1, d // 100), levels=256.0,
                              block=min(4096.0, float(d)))


def stack_compression_params(ps) -> CompressionParams:
    """Stack params along a leading variant axis (``run_sweep``'s vmap)."""
    ps = list(ps)
    return CompressionParams(*(jnp.stack([getattr(p, f) for p in ps])
                               for f in CompressionParams._fields))


# (cparams, key, flat) -> (compressed_flat, bits_on_the_wire)
CompressorFn = Callable[[CompressionParams, jax.Array, jnp.ndarray],
                        Tuple[jnp.ndarray, jnp.ndarray]]


def _nnz(k: jnp.ndarray, d: int) -> jnp.ndarray:
    """Kept-coordinate count for a (possibly fractional, traced) budget."""
    return jnp.clip(jnp.ceil(k), 1.0, float(d))


def _rank(score: jnp.ndarray) -> jnp.ndarray:
    """Dense descending rank (0 = best); stable, so ties break by index."""
    return jnp.argsort(jnp.argsort(-score))


# ---------------------------------------------------------------------------
# Operators — flat (D,) in, flat (D,) dense reconstruction + bits out
# ---------------------------------------------------------------------------
def _none(cp: CompressionParams, key, x):
    return x, jnp.float32(SCALE_BITS * x.size)


def _sign(cp: CompressionParams, key, x):
    return jnp.sign(x), jnp.float32(x.size)


def _scaled_sign(cp: CompressionParams, key, x):
    scale = jnp.mean(jnp.abs(x))
    return scale * jnp.sign(x), jnp.float32(x.size) + SCALE_BITS


def _blockwise_scaled_sign(cp: CompressionParams, key, x):
    d = x.size
    block = jnp.clip(cp.block, 1.0, float(d))
    # traced block length -> segment ids instead of a (static) reshape
    bid = jnp.floor(jnp.arange(d, dtype=jnp.float32) / block).astype(jnp.int32)
    l1 = jax.ops.segment_sum(jnp.abs(x), bid, num_segments=d)
    cnt = jax.ops.segment_sum(jnp.ones(d, jnp.float32), bid, num_segments=d)
    scale = l1 / jnp.maximum(cnt, 1.0)
    n_blocks = jnp.ceil(d / block)
    return scale[bid] * jnp.sign(x), d + SCALE_BITS * n_blocks


def _ternary(cp: CompressionParams, key, x):
    gmax = jnp.max(jnp.abs(x))
    p = jnp.abs(x) / jnp.maximum(gmax, 1e-30)
    b = jax.random.uniform(key, x.shape) < p
    return gmax * jnp.sign(x) * b.astype(jnp.float32), \
        LOG2_3 * x.size + SCALE_BITS


def _qsgd(cp: CompressionParams, key, x):
    levels = jnp.maximum(cp.levels, 1.0)
    norm = jnp.linalg.norm(x)
    scaled = jnp.abs(x) / jnp.maximum(norm, 1e-30)  # in [0, 1]
    t = scaled * levels
    lower = jnp.floor(t)
    up = jax.random.uniform(key, x.shape) < (t - lower)
    q = (lower + up.astype(jnp.float32)) / levels
    bits = (jnp.log2(levels + 1.0) + 1.0) * x.size + SCALE_BITS
    return jnp.sign(x) * q * norm, bits


def _topk(cp: CompressionParams, key, x):
    nnz = _nnz(cp.k, x.size)
    mask = _rank(jnp.abs(x)) < nnz
    return jnp.where(mask, x, 0.0), sparse_bits_jax(x.size, nnz)


def _randk(cp: CompressionParams, key, x):
    nnz = _nnz(cp.k, x.size)
    mask = _rank(jax.random.uniform(key, x.shape)) < nnz
    return jnp.where(mask, x, 0.0), sparse_bits_jax(x.size, nnz)


def _rtopk(cp: CompressionParams, key, x):
    """R-top-K [23] with R = min(4K, d): random K of the top-R coords."""
    nnz = _nnz(cp.k, x.size)
    r = jnp.minimum(4.0 * nnz, float(x.size))
    eligible = _rank(jnp.abs(x)) < r
    score = jnp.where(eligible, jax.random.uniform(key, x.shape), -jnp.inf)
    mask = _rank(score) < nnz
    return jnp.where(mask, x, 0.0), sparse_bits_jax(x.size, nnz)


_REGISTRY: Dict[str, CompressorFn] = {
    "none": _none,
    "qsgd": _qsgd,
    "ternary": _ternary,
    "sign": _sign,
    "scaled_sign": _scaled_sign,
    "blockwise_scaled_sign": _blockwise_scaled_sign,
    "topk": _topk,
    "randk": _randk,
    "rtopk": _rtopk,
}


def get_compressor(name: str) -> CompressorFn:
    """Registry lookup: name -> pure-jnp ``(cparams, key, flat) ->
    (compressed, bits)`` (the *name* is a static engine argument)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown compressor {name!r}; "
                         f"known: {sorted(_REGISTRY)}") from None


def compressor_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


# ---------------------------------------------------------------------------
# Batched row compression (the engine's chunked client pass) + kernel dispatch
# ---------------------------------------------------------------------------
# Above this many total elements (full client pass N * D, NOT the per-chunk
# block size — so chunked and unchunked runs of the same problem take the
# same code path), kernel-backed operators route to the repro.kernels row
# APIs: real Pallas on TPU, the compiled-jnp kernel mirror elsewhere
# (pl.pallas_call(interpret=False) is TPU-only in this jax build). Below the
# threshold the vmapped registry operator wins — kernel padding/dispatch
# overhead isn't worth it on toy messages.
KERNEL_DISPATCH_MIN_ELEMS = 1 << 20
_KERNEL_BACKED = ("topk", "qsgd", "scaled_sign")


def kernel_dispatch(name: str, total_elems: int) -> bool:
    """Static (trace-time) decision: does this operator run on the kernel
    row path for a client pass of ``total_elems`` = N * D elements?"""
    return name in _KERNEL_BACKED and total_elems >= KERNEL_DISPATCH_MIN_ELEMS


def rows_compressor(name: str, total_elems: int = 0, *,
                    kernel_mode: str | None = None) -> Callable:
    """Batched compressor over client rows: ``(cparams, keys (B, 2),
    rows (B, D)) -> (compressed (B, D), bits (B,))``.

    ``keys`` must be per-*client* keys (``fold_in(key, client_id)``), so the
    result of row i never depends on which rows share its batch — the
    chunk-invariance contract of the fleet engine. Kernel-backed operators
    (exact top-k, QSGD, scaled-sign) dispatch to ``repro.kernels`` when
    :func:`kernel_dispatch` fires; ``kernel_mode`` forces the kernel path's
    execution mode ("pallas"/"interpret"/"jit", see kernels.ops) for
    benchmarks and tests.
    """
    op = get_compressor(name)  # validates the name up front
    if not kernel_dispatch(name, total_elems):
        return jax.vmap(op, in_axes=(None, 0, 0))
    from repro.kernels import ops as kernel_ops  # deferred: keep core import-light

    if name == "topk":
        def rows_fn(cp, keys, rows):
            d = rows.shape[1]
            nnz = _nnz(cp.k, d)
            comp = kernel_ops.topk_rows(rows, nnz, mode=kernel_mode)
            bits = jnp.broadcast_to(sparse_bits_jax(d, nnz), (rows.shape[0],))
            return comp, bits
    elif name == "qsgd":
        def rows_fn(cp, keys, rows):
            u = jax.vmap(lambda k: jax.random.uniform(
                k, (rows.shape[1],), jnp.float32))(keys)
            comp = kernel_ops.qsgd_rows(rows, u, cp.levels, mode=kernel_mode)
            bits = jnp.broadcast_to(
                uplink_bits_jax("qsgd", cp, rows.shape[1]), (rows.shape[0],))
            return comp, bits
    else:  # scaled_sign (the EF-fused variant lives in fl_round)
        def rows_fn(cp, keys, rows):
            comp, _ = kernel_ops.sign_ef_rows(
                rows, jnp.zeros_like(rows, jnp.float32), mode=kernel_mode)
            bits = jnp.broadcast_to(
                uplink_bits_jax("scaled_sign", cp, rows.shape[1]),
                (rows.shape[0],))
            return comp, bits.astype(jnp.float32)
    return rows_fn


def uplink_bits_jax(name: str, cp: CompressionParams, d: int) -> jnp.ndarray:
    """Bits-on-the-wire for one d-dimensional message — the engine's pricing
    model. Data-independent, so it equals the ``bits`` the compressor itself
    returns (asserted by the test suite against ``coding.py``)."""
    if name == "none":
        return jnp.float32(SCALE_BITS * d)
    if name == "sign":
        return jnp.float32(d)
    if name == "scaled_sign":
        return jnp.float32(d) + SCALE_BITS
    if name == "blockwise_scaled_sign":
        block = jnp.clip(cp.block, 1.0, float(d))
        return d + SCALE_BITS * jnp.ceil(d / block)
    if name == "ternary":
        return jnp.float32(LOG2_3 * d) + SCALE_BITS
    if name == "qsgd":
        levels = jnp.maximum(cp.levels, 1.0)
        return (jnp.log2(levels + 1.0) + 1.0) * d + SCALE_BITS
    if name in ("topk", "randk", "rtopk"):
        return sparse_bits_jax(d, _nnz(cp.k, d))
    raise ValueError(f"unknown compressor {name!r}; "
                     f"known: {sorted(_REGISTRY)}")
