"""Shared layer primitives: norms, RoPE, MLPs, embeddings, inits.

Conventions
-----------
* Params are plain dict pytrees of ``jnp.ndarray``; every init function takes a
  PRNG key and returns a pytree. Layer stacks are built by ``vmap``-ing the
  per-layer init over a key axis so ``lax.scan`` can run over the leading dim.
* Compute dtype is the config dtype (bf16 on TPU); params are stored in the
  same dtype for the dry-run (matching the DESIGN.md memory accounting) and
  fp32 in smoke tests.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(key, d: int, norm_type: str, dtype) -> Params:
    del key
    p = {"scale": jnp.ones((d,), dtype=dtype)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype=dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, norm_type: str, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_frequencies(head_dim: int, theta: float, yarn: Tuple[float, ...]
                     ) -> Tuple[jnp.ndarray, float]:
    """YaRN (Peng et al. 2023, as transformers' ``_compute_yarn_parameters``
    computes it): (inv_freq (hd/2,), the factor on cos and sin).
    ``yarn`` = (factor, original_max_position, beta_fast, beta_slow,
    attention_factor). Dimensions that turn fewer than ``beta_slow`` times
    over the original context are interpolated (frequency / factor), those
    that turn more than ``beta_fast`` times keep their frequency, and a
    linear ramp blends the two between."""
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def dim_of(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    freqs = rope_frequencies(head_dim, theta)
    return freqs / factor * ramp + freqs * (1.0 - ramp), attention_factor


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn: Tuple[float, ...] = ()) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32. With
    ``yarn`` (see :func:`yarn_frequencies`) the YaRN frequencies and scale."""
    head_dim = x.shape[-1]
    if yarn:
        freqs, scale = yarn_frequencies(head_dim, theta, yarn)
    else:
        freqs = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if yarn:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / gated MLPs
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, d_ff: int, mlp_type: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d ** -0.5
    scale_out = d_ff ** -0.5
    if mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": (jax.random.normal(k1, (d, d_ff)) * scale_in).astype(dtype),
            "w_up": (jax.random.normal(k2, (d, d_ff)) * scale_in).astype(dtype),
            "w_down": (jax.random.normal(k3, (d_ff, d)) * scale_out).astype(dtype),
        }
    return {
        "w_up": (jax.random.normal(k1, (d, d_ff)) * scale_in).astype(dtype),
        "b_up": jnp.zeros((d_ff,), dtype=dtype),
        "w_down": (jax.random.normal(k2, (d_ff, d)) * scale_out).astype(dtype),
        "b_down": jnp.zeros((d,), dtype=dtype),
    }


def apply_mlp(p: Params, x: jnp.ndarray, mlp_type: str) -> jnp.ndarray:
    if mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if mlp_type == "swiglu" else (lambda v: jax.nn.gelu(v, approximate=True))
        h = act(x @ p["w_gate"]) * (x @ p["w_up"])
        return h @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_up"] + p["b_up"], approximate=True)
    return h @ p["w_down"] + p["b_down"]


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------
def init_embedding(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d)) * (d ** -0.5)).astype(dtype)


def embed_tokens(table: jnp.ndarray, tokens: jnp.ndarray, scale_by_dim: bool = False) -> jnp.ndarray:
    out = jnp.take(table, tokens, axis=0)
    if scale_by_dim:  # gemma-style embedding scaling
        out = out * jnp.asarray(out.shape[-1] ** 0.5, dtype=out.dtype)
    return out


def sinusoidal_positions(n_pos: int, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal table (fp32)."""
    half = d // 2
    freq = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half) / (half - 1))
    args = jnp.arange(n_pos)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------
def stacked_init(init_fn, key, n: int):
    """vmap an init function over n split keys -> leading stack dim."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def dense_init(key, shape, dtype, scale: float | None = None):
    scale = shape[0] ** -0.5 if scale is None else scale
    return (jax.random.normal(key, shape) * scale).astype(dtype)
