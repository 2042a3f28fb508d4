"""Compressor ``topk`` of the plain reference: keep the ``k`` coordinates of
largest magnitude, with error feedback.

A compressor module gives ``ERROR_FEEDBACK`` (whether what a client did
not send is kept for its next round), ``params(spec, d)`` (the numbers
that both the program's ``compression_params`` and the reference take,
from the traffic file's ``compression`` object and the model's size),
``compress(x, p, dtype)`` (the message sent for one client's vector) and
``bits(d, p, model_bits)`` (the payload one client uploads).

Traffic: ``{"name": "topk", "fraction": f}`` keeps ``k = max(1, int(f *
d))``. The message keeps every coordinate at or above the exact k-th
largest magnitude; the payload is block coded (see ``sparse_bits``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ERROR_FEEDBACK = True


def params(spec, d: int) -> dict:
    return {"k": float(max(1, int(float(spec["fraction"]) * d)))}


def kth_largest_abs(x: jnp.ndarray, k) -> jnp.ndarray:
    """The k-th largest ``|x|`` of a vector, exactly: a bisection over the
    bit pattern of non-negative floats, which orders like the values."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(x.astype(jnp.float32)),
                                        jnp.int32)

    def body(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(jnp.sum(bits >= cand) >= k, cand, prefix)

    prefix = jax.lax.fori_loop(0, 31, body, jnp.int32(0))
    return jax.lax.bitcast_convert_type(prefix, jnp.float32)


def compress(x: jnp.ndarray, p: dict, dtype) -> jnp.ndarray:
    thr = kth_largest_abs(x, p["k"]).astype(dtype)
    return jnp.where(jnp.abs(x) >= thr, x, jnp.zeros((), dtype))


def sparse_bits(d: int, nnz: float, value_bits: float = 32.0) -> float:
    """Block-coded size of a message keeping ``nnz`` of ``d`` coordinates:
    blocks of 2^ceil(log2(d / nnz)) coordinates, one flag bit per block,
    and per kept value its offset in the block, a sign-magnitude flag and
    the value."""
    eps = 1e-6
    log_bs = max(0.0, math.ceil(math.log2(d / nnz) - eps))
    n_blocks = math.ceil(d / 2.0 ** log_bs - eps)
    return nnz * (1.0 + log_bs + value_bits) + n_blocks


def bits(d: int, p: dict, model_bits: float) -> float:
    """The sparse message's share of the 32-bit dense payload, applied to
    the configuration's ``model_bits``."""
    return model_bits / (32.0 * d) * sparse_bits(d, p["k"])
