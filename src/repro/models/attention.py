"""Attention: GQA/MQA self-attention (full / sliding / chunked), decode with
KV caches (full or circular sliding-window), and cross-attention.

TPU notes: long-sequence attention is computed in query chunks via ``lax.scan``
so the live score buffer is O(q_chunk * seq) not O(seq^2) — the HBM-friendly
adaptation of flash-style attention (XLA fuses the inner block on TPU; a Pallas
flash kernel is *not* part of this paper's contribution, see DESIGN.md §7).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

Params = Dict[str, jnp.ndarray]

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                   dtype, kv_input_dim: Optional[int] = None) -> Params:
    """q/k/v/o projections. ``kv_input_dim`` overrides the k/v input width
    (cross-attention over vision/encoder states)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    d_kv_in = kv_input_dim if kv_input_dim is not None else d_model
    return {
        "wq": dense_init(kq, (d_model, n_heads * head_dim), dtype),
        "wk": dense_init(kk, (d_kv_in, n_kv_heads * head_dim), dtype),
        "wv": dense_init(kv, (d_kv_in, n_kv_heads * head_dim), dtype),
        "wo": dense_init(ko, (n_heads * head_dim, d_model), dtype),
    }


def project_q(p: Params, x: jnp.ndarray, n_heads: int, head_dim: int) -> jnp.ndarray:
    b, s, _ = x.shape
    return (x @ p["wq"]).reshape(b, s, n_heads, head_dim)


def project_kv(p: Params, x: jnp.ndarray, n_kv_heads: int, head_dim: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, _ = x.shape
    k = (x @ p["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(b, s, n_kv_heads, head_dim)
    return k, v


def _block_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  q_pos: jnp.ndarray, k_pos: jnp.ndarray, *,
                  causal: bool, window: Optional[int], softcap: float,
                  k_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One attention block. q: (B,C,K,G,hd); k,v: (B,T,K,hd).
    q_pos: (C,), k_pos: (T,) absolute positions. Returns (B,C,K,G,hd)."""
    head_dim = q.shape[-1]
    scale = head_dim ** -0.5
    scores = jnp.einsum("bckgh,btkh->bkgct", q, k).astype(jnp.float32) * scale
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    if k_valid is not None:
        mask &= k_valid[None, :]
    scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgct,btkh->bckgh", probs, v)


def attention_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   n_kv_heads: int, causal: bool = True,
                   window: Optional[int] = None, softcap: float = 0.0,
                   q_offset: int = 0, q_chunk: int = 1024) -> jnp.ndarray:
    """q: (B,S,H,hd); k,v: (B,T,K,hd). Chunked over queries when S > q_chunk."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    g = h // n_kv_heads
    qg = q.reshape(b, s, n_kv_heads, g, hd)
    k_pos = jnp.arange(t)

    if s <= q_chunk:
        q_pos = q_offset + jnp.arange(s)
        out = _block_attend(qg, k, v, q_pos, k_pos, causal=causal,
                            window=window, softcap=softcap)
        return out.reshape(b, s, h, hd)

    if s % q_chunk != 0:  # e.g. whisper's 1500 frames: largest fitting divisor
        q_chunk = max(c for c in range(1, q_chunk + 1) if s % c == 0)
    n_chunks = s // q_chunk
    q_chunks = qg.reshape(b, n_chunks, q_chunk, n_kv_heads, g, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, inp):
        idx, qc = inp
        q_pos = q_offset + idx * q_chunk + jnp.arange(q_chunk)
        out = _block_attend(qc, k, v, q_pos, k_pos, causal=causal,
                            window=window, softcap=softcap)
        return None, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), q_chunks))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)


def self_attention(p: Params, x: jnp.ndarray, *, n_heads: int, n_kv_heads: int,
                   head_dim: int, use_rope: bool, rope_theta: float,
                   yarn: Tuple[float, ...] = (),
                   window: Optional[int] = None, softcap: float = 0.0,
                   q_chunk: int = 1024,
                   return_kv: bool = False):
    """Training / prefill self-attention. x: (B,S,d). ``yarn``: YaRN
    parameters of the RoPE (``layers.yarn_frequencies``), or none."""
    b, s, _ = x.shape
    q = project_q(p, x, n_heads, head_dim)
    k, v = project_kv(p, x, n_kv_heads, head_dim)
    if use_rope:
        pos = jnp.arange(s)[None, :]
        q = apply_rope(q, pos, rope_theta, yarn)
        k = apply_rope(k, pos, rope_theta, yarn)
    out = attention_core(q, k, v, n_kv_heads=n_kv_heads, causal=True,
                         window=window, softcap=softcap, q_chunk=q_chunk)
    out = out.reshape(b, s, n_heads * head_dim) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def decode_self_attention(p: Params, x: jnp.ndarray, cache_k: jnp.ndarray,
                          cache_v: jnp.ndarray, pos: jnp.ndarray, *,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          use_rope: bool, rope_theta: float,
                          yarn: Tuple[float, ...] = (),
                          circular: bool = False,
                          window: Optional[int] = None, softcap: float = 0.0):
    """One decode step. x: (B,1,d); cache_{k,v}: (B,T,K,hd); pos: scalar int32
    absolute position of the new token. ``window``: keys at most that many
    positions back are seen (a sliding layer over a cache that is longer).

    ``circular=True`` treats the cache as a ring buffer of size T (sliding
    window): keys are stored *with RoPE already applied at their absolute
    position*, so attention is order-invariant over slots and no re-rotation is
    needed on eviction.
    """
    b = x.shape[0]
    t = cache_k.shape[1]
    q = project_q(p, x, n_heads, head_dim)
    k_new, v_new = project_kv(p, x, n_kv_heads, head_dim)
    if use_rope:
        pos_arr = jnp.full((b, 1), pos, dtype=jnp.int32)
        q = apply_rope(q, pos_arr, rope_theta, yarn)
        k_new = apply_rope(k_new, pos_arr, rope_theta, yarn)

    slot = pos % t if circular else jnp.minimum(pos, t - 1)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype), (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype), (0, slot, 0, 0))

    slots = jnp.arange(t)
    if circular:
        # slot j holds a valid key iff the ring has wrapped or j <= pos
        k_valid = jnp.logical_or(pos >= t, slots <= pos)
    else:
        k_valid = slots <= pos
    if window is not None:
        # the position each slot holds: the latest one it took
        k_pos = pos - (pos - slots) % t if circular else slots
        k_valid &= k_pos > pos - window

    g = n_heads // n_kv_heads
    qg = q.reshape(b, 1, n_kv_heads, g, head_dim)
    scale = head_dim ** -0.5
    scores = jnp.einsum("bckgh,btkh->bkgct", qg, cache_k).astype(jnp.float32) * scale
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(k_valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bkgct,btkh->bckgh", probs, cache_v)
    out = out.reshape(b, 1, n_heads * head_dim) @ p["wo"]
    return out, (cache_k, cache_v)


def cross_attention(p: Params, x: jnp.ndarray, kv_k: jnp.ndarray, kv_v: jnp.ndarray, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    q_chunk: int = 1024) -> jnp.ndarray:
    """Cross-attention over precomputed k/v (vision patches / encoder frames).
    No causal mask, no RoPE (absolute context set)."""
    b, s, _ = x.shape
    q = project_q(p, x, n_heads, head_dim)
    out = attention_core(q, kv_k, kv_v, n_kv_heads=n_kv_heads, causal=False,
                         q_chunk=q_chunk)
    return out.reshape(b, s, n_heads * head_dim) @ p["wo"]


def init_kv_cache(batch: int, length: int, n_kv_heads: int, head_dim: int, dtype
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    shape = (batch, length, n_kv_heads, head_dim)
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)
