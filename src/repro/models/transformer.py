"""Composable decoder stacks for all six assigned families.

Families: dense | moe | ssm (mamba) | hybrid (RG-LRU+local attn) | vlm
(cross-attn image layers) | audio (whisper enc-dec).

Design rules (see DESIGN.md):
* params are dict pytrees with a leading stacked-layer axis; ``lax.scan`` runs
  the stack (compile time stays bounded at 126 layers).
* hybrid/vlm use *superblocks* (one block-pattern period) so the scanned
  unit stays homogeneous. A dense/moe trunk with a ``block_pattern`` keeps
  its flat layer stack and scans it a period at a time: its layers are
  "sliding" (windowed, plain RoPE) or "full" (whole context, YaRN RoPE
  where ``cfg.yarn`` is given).
* training loss is computed with a sequence-chunked, rematerialized
  softmax-xent so full (B,S,V) logits are never materialized.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LONG_CONTEXT_WINDOW, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, dense_init,
                                 embed_tokens, init_embedding, init_mlp,
                                 init_norm, sinusoidal_positions, stacked_init)

Params = Dict[str, Any]
PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# Per-layer inits
# ===========================================================================
def _init_attn_layer(key, cfg: ModelConfig, dtype, use_moe: bool) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "norm1": init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
        "attn": attn.init_attention(k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, dtype),
        "norm2": init_norm(k3, cfg.d_model, cfg.norm_type, dtype),
    }
    if use_moe:
        p["mlp"] = moe_mod.init_moe_block(k4, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k4, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _init_cross_layer(key, cfg: ModelConfig, dtype) -> Params:
    """Gated cross-attention layer (llama-3.2-vision style)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "norm1": init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
        "attn": attn.init_attention(k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, dtype, kv_input_dim=cfg.vision_dim),
        "norm2": init_norm(k3, cfg.d_model, cfg.norm_type, dtype),
        "mlp": init_mlp(k4, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
        "gate_attn": jnp.zeros((), dtype=dtype),
        "gate_mlp": jnp.zeros((), dtype=dtype),
    }


def _init_mamba_layer(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm": init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
        "mamba": ssm_mod.init_mamba_block(k2, cfg, dtype),
    }


def _init_rglru_layer(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "norm1": init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
        "rec": rglru_mod.init_rglru_block(k2, cfg, dtype),
        "norm2": init_norm(k3, cfg.d_model, cfg.norm_type, dtype),
        "mlp": init_mlp(k4, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
    }


def _init_dec_layer(key, cfg: ModelConfig, dtype) -> Params:
    """Whisper decoder layer: self-attn + cross-attn + mlp."""
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    return {
        "norm1": init_norm(k1, cfg.d_model, cfg.norm_type, dtype),
        "self_attn": attn.init_attention(k2, cfg.d_model, cfg.n_heads,
                                         cfg.n_kv_heads, cfg.head_dim, dtype),
        "norm2": init_norm(k3, cfg.d_model, cfg.norm_type, dtype),
        "cross_attn": attn.init_attention(k4, cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.head_dim, dtype),
        "norm3": init_norm(k5, cfg.d_model, cfg.norm_type, dtype),
        "mlp": init_mlp(k6, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
    }


# ===========================================================================
# init_params
# ===========================================================================
def init_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {"embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
                      "final_norm": init_norm(keys[1], cfg.d_model, cfg.norm_type, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[2], (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.pos_embed == "learned":
        params["pos_embed"] = dense_init(keys[3], (cfg.max_position, cfg.d_model),
                                         dtype, scale=0.02)

    fam = cfg.family
    if fam in ("dense", "moe"):
        params["blocks"] = stacked_init(
            lambda k: _init_attn_layer(k, cfg, dtype, fam == "moe"),
            keys[4], cfg.n_layers)
    elif fam == "ssm":
        params["blocks"] = stacked_init(
            lambda k: _init_mamba_layer(k, cfg, dtype), keys[4], cfg.n_layers)
    elif fam == "hybrid":
        pat = cfg.block_pattern
        n_super, rem = divmod(cfg.n_layers, len(pat))
        super_p = {}
        for i, kind in enumerate(pat):
            if kind == "rglru":
                super_p[f"p{i}_rglru"] = stacked_init(
                    lambda k: _init_rglru_layer(k, cfg, dtype), jax.random.fold_in(keys[4], i), n_super)
            else:
                super_p[f"p{i}_attn"] = stacked_init(
                    lambda k: _init_attn_layer(k, cfg, dtype, False), jax.random.fold_in(keys[4], i), n_super)
        params["blocks"] = super_p
        rest = []
        for j in range(rem):
            kind = pat[j]
            kj = jax.random.fold_in(keys[5], j)
            rest.append(_init_rglru_layer(kj, cfg, dtype) if kind == "rglru"
                        else _init_attn_layer(kj, cfg, dtype, False))
        params["rest"] = rest
    elif fam == "vlm":
        n_self_per = cfg.cross_attn_every - 1
        n_super = cfg.n_layers // cfg.cross_attn_every
        params["blocks"] = {
            "self": stacked_init(
                lambda k: stacked_init(
                    lambda kk: _init_attn_layer(kk, cfg, dtype, False), k, n_self_per),
                keys[4], n_super),
            "cross": stacked_init(
                lambda k: _init_cross_layer(k, cfg, dtype), keys[5], n_super),
        }
    elif fam == "audio":
        params["encoder"] = {
            "blocks": stacked_init(
                lambda k: _init_attn_layer(k, cfg, dtype, False), keys[4],
                cfg.n_encoder_layers),
            "final_norm": init_norm(keys[6], cfg.d_model, cfg.norm_type, dtype),
        }
        params["blocks"] = stacked_init(
            lambda k: _init_dec_layer(k, cfg, dtype), keys[5], cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


# ===========================================================================
# Block applications (single layer)
# ===========================================================================
def _attn_block_fwd(p: Params, x, cfg: ModelConfig, *, window, yarn=(),
                    return_kv=False, q_chunk=1024, causal=True):
    h = apply_norm(p["norm1"], x, cfg.norm_type)
    with jax.named_scope("model.attn.sliding" if window else
                         "model.attn.full"):
        res = attn.self_attention(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, use_rope=cfg.use_rope,
            rope_theta=cfg.rope_theta, yarn=yarn, window=window,
            softcap=cfg.logit_softcap, q_chunk=q_chunk,
            return_kv=return_kv) if causal else _bidir_attn(p, h, cfg,
                                                             q_chunk)
    if return_kv:
        res, kv = res
    x = x + res
    h2 = apply_norm(p["norm2"], x, cfg.norm_type)
    if cfg.family == "moe" and "router" in p["mlp"]:
        out, aux = moe_mod.moe_forward(p["mlp"], h2, cfg)
    else:
        out, aux = apply_mlp(p["mlp"], h2, cfg.mlp_type), jnp.zeros((), jnp.float32)
    x = x + out
    if return_kv:
        return x, aux, kv
    return x, aux


def _bidir_attn(p, h, cfg: ModelConfig, q_chunk):
    """Whisper encoder: bidirectional self-attention (no mask, no rope)."""
    b, s, _ = h.shape
    q = attn.project_q(p["attn"], h, cfg.n_heads, cfg.head_dim)
    k, v = attn.project_kv(p["attn"], h, cfg.n_kv_heads, cfg.head_dim)
    out = attn.attention_core(q, k, v, n_kv_heads=cfg.n_kv_heads, causal=False,
                              q_chunk=q_chunk)
    return out.reshape(b, s, cfg.n_heads * cfg.head_dim) @ p["attn"]["wo"]


def _attn_block_decode(p: Params, x, ck, cv, pos, cfg: ModelConfig, *, circular,
                       window=None, yarn=()):
    h = apply_norm(p["norm1"], x, cfg.norm_type)
    res, (ck, cv) = attn.decode_self_attention(
        p["attn"], h, ck, cv, pos, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, use_rope=cfg.use_rope, rope_theta=cfg.rope_theta,
        yarn=yarn, circular=circular, window=window, softcap=cfg.logit_softcap)
    x = x + res
    h2 = apply_norm(p["norm2"], x, cfg.norm_type)
    if cfg.family == "moe" and "router" in p["mlp"]:
        out, _ = moe_mod.moe_forward(p["mlp"], h2, cfg)
    else:
        out = apply_mlp(p["mlp"], h2, cfg.mlp_type)
    return x + out, ck, cv


def _cross_block_fwd(p: Params, x, vis_k, vis_v, cfg: ModelConfig, q_chunk=1024):
    h = apply_norm(p["norm1"], x, cfg.norm_type)
    res = attn.cross_attention(p["attn"], h, vis_k, vis_v, n_heads=cfg.n_heads,
                               n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                               q_chunk=q_chunk)
    x = x + jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(x.dtype) * res
    h2 = apply_norm(p["norm2"], x, cfg.norm_type)
    out = apply_mlp(p["mlp"], h2, cfg.mlp_type)
    return x + jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * out


def _rglru_block_fwd(p: Params, x, cfg: ModelConfig, *, state=None, return_state=False):
    h = apply_norm(p["norm1"], x, cfg.norm_type)
    if return_state:
        res, st = rglru_mod.rglru_forward(p["rec"], h, cfg, state=state, return_state=True)
    else:
        res = rglru_mod.rglru_forward(p["rec"], h, cfg, state=state)
    x = x + res
    h2 = apply_norm(p["norm2"], x, cfg.norm_type)
    x = x + apply_mlp(p["mlp"], h2, cfg.mlp_type)
    if return_state:
        return x, st
    return x


def _rglru_block_decode(p: Params, x, state, cfg: ModelConfig):
    h = apply_norm(p["norm1"], x, cfg.norm_type)
    res, state = rglru_mod.rglru_decode_step(p["rec"], h, state, cfg)
    x = x + res
    h2 = apply_norm(p["norm2"], x, cfg.norm_type)
    x = x + apply_mlp(p["mlp"], h2, cfg.mlp_type)
    return x, state


def _mamba_block_fwd(p: Params, x, cfg: ModelConfig, *, state=None, return_state=False):
    h = apply_norm(p["norm"], x, cfg.norm_type)
    if return_state:
        res, st = ssm_mod.mamba_forward(p["mamba"], h, cfg, state=state, return_state=True)
        return x + res, st
    return x + ssm_mod.mamba_forward(p["mamba"], h, cfg, state=state)


def _dec_layer_fwd(p: Params, x, enc_k, enc_v, cfg: ModelConfig, *,
                   q_chunk=1024, return_kv=False):
    h = apply_norm(p["norm1"], x, cfg.norm_type)
    res = attn.self_attention(
        p["self_attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, use_rope=cfg.use_rope, rope_theta=cfg.rope_theta,
        q_chunk=q_chunk, return_kv=return_kv)
    if return_kv:
        res, kv = res
    x = x + res
    h2 = apply_norm(p["norm2"], x, cfg.norm_type)
    x = x + attn.cross_attention(p["cross_attn"], h2, enc_k, enc_v,
                                 n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                                 head_dim=cfg.head_dim, q_chunk=q_chunk)
    h3 = apply_norm(p["norm3"], x, cfg.norm_type)
    x = x + apply_mlp(p["mlp"], h3, cfg.mlp_type)
    if return_kv:
        return x, kv
    return x


# ===========================================================================
# Embedding / unembedding
# ===========================================================================
def _embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, pos_offset=0):
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.tie_embeddings)
    if cfg.pos_embed == "learned":
        s = tokens.shape[1]
        idx = (pos_offset + jnp.arange(s)) % params["pos_embed"].shape[0]
        x = x + params["pos_embed"][idx][None, :, :]
    return x


def unembed(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = apply_norm(params["final_norm"], h, cfg.norm_type)
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return h @ table


# ===========================================================================
# Layer periods of a dense / moe trunk
# ===========================================================================
def _layer_kinds(cfg: ModelConfig, window):
    """(window, yarn) of each layer of one period of a dense/moe trunk: one
    layer of ``window`` and plain RoPE, or the layers of
    ``cfg.block_pattern``: "sliding" (``cfg.sliding_window``, plain RoPE)
    and "full" (the whole context, ``cfg.yarn``)."""
    if not cfg.block_pattern:
        return ((window, ()),)
    if cfg.n_layers % len(cfg.block_pattern):
        raise ValueError(f"{cfg.name}: {cfg.n_layers} layers are not whole "
                         f"periods of {cfg.block_pattern}")
    kinds = {"sliding": (cfg.sliding_window, ()), "full": (None, cfg.yarn)}
    return tuple(kinds[k] for k in cfg.block_pattern)


def _periods(tree, period: int):
    """Layer stacks (L, ...) as (L / period, period, ...)."""
    if period == 1:
        return tree
    return jax.tree.map(lambda a: a.reshape(-1, period, *a.shape[1:]), tree)


def _flat_layers(tree, period: int):
    """The inverse of :func:`_periods`."""
    if period == 1:
        return tree
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), tree)


def _layer_of(tree, i: int, period: int):
    """Layer ``i`` of one period's slice of :func:`_periods`."""
    return tree if period == 1 else jax.tree.map(lambda a: a[i], tree)


def _stack_period(per_layer):
    """Per-layer outputs of one period, stacked as :func:`_layer_of` reads
    them."""
    if len(per_layer) == 1:
        return per_layer[0]
    return jax.tree.map(lambda *a: jnp.stack(a), *per_layer)


# ===========================================================================
# Forward (train / prefill trunk)
# ===========================================================================
def forward_trunk(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  extras: Optional[Dict[str, jnp.ndarray]] = None, *,
                  collect_cache: bool = False, remat: bool = True,
                  q_chunk: int = 1024):
    """Run embedding + all blocks; returns (hidden (B,S,d), aux, cache|None)."""
    extras = extras or {}
    x = _embed(params, cfg, tokens)
    window = cfg.sliding_window if cfg.attn_type == "sliding" else None
    fam = cfg.family
    aux0 = jnp.zeros((), jnp.float32)
    cache = None

    if fam in ("dense", "moe"):
        kinds = _layer_kinds(cfg, window)
        period = len(kinds)
        remat_each = remat and not collect_cache

        def layer(p_l, x, w, yarn):
            return _attn_block_fwd(p_l, x, cfg, window=w, yarn=yarn,
                                   return_kv=collect_cache, q_chunk=q_chunk)
        if remat_each and period > 1:
            # each layer of a period rematerialized on its own
            layer = jax.checkpoint(layer, static_argnums=(2, 3))

        def body(carry, p_per):
            x, aux = carry
            kvs = []
            for i, (w, yarn) in enumerate(kinds):
                x, a, *kv = layer(_layer_of(p_per, i, period), x, w, yarn)
                aux = aux + a
                kvs += kv
            return (x, aux), (_stack_period(kvs) if collect_cache else None)
        body_fn = jax.checkpoint(body) if remat_each and period == 1 else body
        (x, aux), kvs = jax.lax.scan(body_fn, (x, aux0),
                                     _periods(params["blocks"], period))
        if collect_cache:
            kvs = _flat_layers(kvs, period)
            cache = {"k": kvs[0], "v": kvs[1]}  # (L,B,S,K,hd)
        return x, aux, cache

    if fam == "ssm":
        def body(x, p_l):
            if collect_cache:
                x, st = _mamba_block_fwd(p_l, x, cfg, return_state=True)
                return x, st
            return _mamba_block_fwd(p_l, x, cfg), None
        body_fn = jax.checkpoint(body) if (remat and not collect_cache) else body
        x, sts = jax.lax.scan(body_fn, x, params["blocks"])
        if collect_cache:
            cache = {"conv": sts[0], "ssm": sts[1]}  # (L,B,...)
        return x, aux0, cache

    if fam == "hybrid":
        pat = cfg.block_pattern

        def body(x, p_super):
            outs = {}
            for i, kind in enumerate(pat):
                if kind == "rglru":
                    pl = p_super[f"p{i}_rglru"]
                    if collect_cache:
                        x, st = _rglru_block_fwd(pl, x, cfg, return_state=True)
                        outs[f"p{i}_conv"], outs[f"p{i}_h"] = st
                    else:
                        x = _rglru_block_fwd(pl, x, cfg)
                else:
                    pl = p_super[f"p{i}_attn"]
                    if collect_cache:
                        x, _, kv = _attn_block_fwd(pl, x, cfg, window=window,
                                                   return_kv=True, q_chunk=q_chunk)
                        outs[f"p{i}_k"], outs[f"p{i}_v"] = kv
                    else:
                        x, _ = _attn_block_fwd(pl, x, cfg, window=window, q_chunk=q_chunk)
            return x, (outs if collect_cache else None)
        body_fn = jax.checkpoint(body) if (remat and not collect_cache) else body
        x, sup_cache = jax.lax.scan(body_fn, x, params["blocks"])
        rest_cache = []
        for p_l in params["rest"]:
            if "rec" in p_l:
                if collect_cache:
                    x, st = _rglru_block_fwd(p_l, x, cfg, return_state=True)
                    rest_cache.append(st)
                else:
                    x = _rglru_block_fwd(p_l, x, cfg)
            else:
                if collect_cache:
                    x, _, kv = _attn_block_fwd(p_l, x, cfg, window=window,
                                               return_kv=True, q_chunk=q_chunk)
                    rest_cache.append(kv)
                else:
                    x, _ = _attn_block_fwd(p_l, x, cfg, window=window, q_chunk=q_chunk)
        if collect_cache:
            cache = {"super": sup_cache, "rest": rest_cache}
        return x, aux0, cache

    if fam == "vlm":
        vis = extras["vision_embeds"].astype(x.dtype)  # (B, n_vis, vision_dim)

        def body(x, p_super):
            def inner(xx, p_l):
                if collect_cache:
                    xx, _, kv = _attn_block_fwd(p_l, xx, cfg, window=window,
                                                return_kv=True, q_chunk=q_chunk)
                    return xx, kv
                xx, _ = _attn_block_fwd(p_l, xx, cfg, window=window, q_chunk=q_chunk)
                return xx, None
            x, self_kv = jax.lax.scan(inner, x, p_super["self"])
            pc = p_super["cross"]
            vk, vv = attn.project_kv(pc["attn"], vis, cfg.n_kv_heads, cfg.head_dim)
            x = _cross_block_fwd(pc, x, vk, vv, cfg, q_chunk=q_chunk)
            return x, ((self_kv, (vk, vv)) if collect_cache else None)
        body_fn = jax.checkpoint(body) if (remat and not collect_cache) else body
        x, ys = jax.lax.scan(body_fn, x, params["blocks"])
        if collect_cache:
            self_kv, cross_kv = ys
            cache = {"k": self_kv[0], "v": self_kv[1],
                     "cross_k": cross_kv[0], "cross_v": cross_kv[1]}
        return x, aux0, cache

    if fam == "audio":
        enc_h = encode_audio(params, cfg, extras["audio_embeds"], q_chunk=q_chunk)

        def body(x, p_l):
            ek, ev = attn.project_kv(p_l["cross_attn"], enc_h, cfg.n_kv_heads,
                                     cfg.head_dim)
            if collect_cache:
                x, kv = _dec_layer_fwd(p_l, x, ek, ev, cfg, q_chunk=q_chunk,
                                       return_kv=True)
                return x, (kv, (ek, ev))
            return _dec_layer_fwd(p_l, x, ek, ev, cfg, q_chunk=q_chunk), None
        body_fn = jax.checkpoint(body) if (remat and not collect_cache) else body
        x, caches = jax.lax.scan(body_fn, x, params["blocks"])
        if collect_cache:
            (kvs, enc_kvs) = caches
            cache = {"k": kvs[0], "v": kvs[1],
                     "cross_k": enc_kvs[0], "cross_v": enc_kvs[1]}
        return x, aux0, cache

    raise ValueError(f"unknown family {fam}")


def encode_audio(params: Params, cfg: ModelConfig, audio_embeds, q_chunk=1024):
    """Whisper encoder over stub frame embeddings (B, frames, d)."""
    x = audio_embeds.astype(_dtype(cfg))
    pos = sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
    x = x + pos[None]

    def body(x, p_l):
        x, _ = _attn_block_fwd(p_l, x, cfg, window=None, q_chunk=q_chunk, causal=False)
        return x, None
    x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm_type)


# ===========================================================================
# Loss (sequence-chunked, remat'ed softmax-xent)
# ===========================================================================
def chunked_xent(params: Params, cfg: ModelConfig, h: jnp.ndarray,
                 labels: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    """Mean token cross-entropy without materializing (B,S,V) logits."""
    b, s, d = h.shape
    if s % chunk or s <= chunk:
        chunk = s
    n_chunks = s // chunk
    hc = h.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(tot, inp):
        hh, ll = inp
        logits = unembed(params, cfg, hh).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
    return tot / (b * s)


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            remat: bool = True) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full forward + loss. batch: tokens, labels (+ vision/audio extras)."""
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    h, aux, _ = forward_trunk(params, cfg, batch["tokens"], extras, remat=remat)
    xent = chunked_xent(params, cfg, h, batch["labels"])
    loss = xent + cfg.router_aux_weight * aux
    return loss, {"xent": xent, "aux": aux}


# ===========================================================================
# Prefill / decode
# ===========================================================================
def init_decode_cache(cfg: ModelConfig, batch: int, length: int, *,
                      sliding: bool = False) -> PyTree:
    """Zeroed cache pytree for decode. ``length`` = context size; sliding
    caps attention caches at LONG_CONTEXT_WINDOW (ring buffers)."""
    dtype = _dtype(cfg)
    t_attn = min(length, LONG_CONTEXT_WINDOW) if sliding else length
    if cfg.attn_type == "sliding":
        t_attn = min(t_attn, cfg.sliding_window)
    fam = cfg.family

    def kv(n, t):
        shape = (n, batch, t, cfg.n_kv_heads, cfg.head_dim)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    if fam in ("dense", "moe"):
        k, v = kv(cfg.n_layers, t_attn)
        return {"k": k, "v": v}
    if fam == "ssm":
        return {"conv": jnp.zeros((cfg.n_layers, batch, cfg.d_conv - 1, cfg.d_inner), dtype),
                "ssm": jnp.zeros((cfg.n_layers, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)}
    if fam == "hybrid":
        pat = cfg.block_pattern
        n_super, rem = divmod(cfg.n_layers, len(pat))
        sup = {}
        for i, kind in enumerate(pat):
            if kind == "rglru":
                sup[f"p{i}_conv"] = jnp.zeros((n_super, batch, cfg.d_conv - 1, cfg.lru_width), dtype)
                sup[f"p{i}_h"] = jnp.zeros((n_super, batch, cfg.lru_width), jnp.float32)
            else:
                sup[f"p{i}_k"], sup[f"p{i}_v"] = kv(n_super, t_attn)
        rest = []
        for j in range(rem):
            if pat[j] == "rglru":
                rest.append((jnp.zeros((batch, cfg.d_conv - 1, cfg.lru_width), dtype),
                             jnp.zeros((batch, cfg.lru_width), jnp.float32)))
            else:
                kk, vv = kv(1, t_attn)
                rest.append((kk[0], vv[0]))
        return {"super": sup, "rest": rest}
    if fam == "vlm":
        n_super = cfg.n_layers // cfg.cross_attn_every
        n_self = n_super * (cfg.cross_attn_every - 1)
        k, v = kv(n_self, t_attn)
        ck = jnp.zeros((n_super, batch, cfg.n_vision_tokens, cfg.n_kv_heads, cfg.head_dim), dtype)
        return {"k": k.reshape(n_super, cfg.cross_attn_every - 1, *k.shape[1:]),
                "v": v.reshape(n_super, cfg.cross_attn_every - 1, *v.shape[1:]),
                "cross_k": ck, "cross_v": ck}
    if fam == "audio":
        k, v = kv(cfg.n_layers, t_attn)
        ck = jnp.zeros((cfg.n_layers, batch, cfg.n_audio_frames, cfg.n_kv_heads, cfg.head_dim), dtype)
        return {"k": k, "v": v, "cross_k": ck, "cross_v": ck}
    raise ValueError(fam)


def decode_step(params: Params, cfg: ModelConfig, cache: PyTree,
                token: jnp.ndarray, pos: jnp.ndarray, *,
                circular: bool = False):
    """One decode step. token: (B,1) int32; pos: scalar int32 absolute
    position. Returns (logits (B,1,V), new cache)."""
    x = _embed(params, cfg, token, pos_offset=pos)
    fam = cfg.family
    # attention caches are circular when they are ring buffers (sliding decode
    # or architecturally-local attention)
    circ = circular or cfg.attn_type == "sliding"

    if fam in ("dense", "moe"):
        kinds = _layer_kinds(cfg, None)
        period = len(kinds)

        def body(x, inp):
            p_per, c_per = inp
            out = []
            for i, (w, yarn) in enumerate(kinds):
                ck, cv = _layer_of(c_per, i, period)
                x, ck, cv = _attn_block_decode(
                    _layer_of(p_per, i, period), x, ck, cv, pos, cfg,
                    circular=circ, window=w, yarn=yarn)
                out.append((ck, cv))
            return x, _stack_period(out)
        x, kvs = jax.lax.scan(body, x, _periods(
            (params["blocks"], (cache["k"], cache["v"])), period))
        ks, vs = _flat_layers(kvs, period)
        cache = {"k": ks, "v": vs}

    elif fam == "ssm":
        def body(x, inp):
            p_l, cs, hs = inp
            h = apply_norm(p_l["norm"], x, cfg.norm_type)
            res, (cs, hs) = ssm_mod.mamba_decode_step(p_l["mamba"], h, (cs, hs), cfg)
            return x + res, (cs, hs)
        x, (convs, ssms) = jax.lax.scan(body, x, (params["blocks"], cache["conv"], cache["ssm"]))
        cache = {"conv": convs, "ssm": ssms}

    elif fam == "hybrid":
        pat = cfg.block_pattern

        def body(x, inp):
            p_super, c_super = inp
            outs = {}
            for i, kind in enumerate(pat):
                if kind == "rglru":
                    st = (c_super[f"p{i}_conv"], c_super[f"p{i}_h"])
                    x, st = _rglru_block_decode(p_super[f"p{i}_rglru"], x, st, cfg)
                    outs[f"p{i}_conv"], outs[f"p{i}_h"] = st
                else:
                    x, ck, cv = _attn_block_decode(
                        p_super[f"p{i}_attn"], x, c_super[f"p{i}_k"], c_super[f"p{i}_v"],
                        pos, cfg, circular=True)
                    outs[f"p{i}_k"], outs[f"p{i}_v"] = ck, cv
            return x, outs
        x, sup = jax.lax.scan(body, x, (params["blocks"], cache["super"]))
        rest = []
        for p_l, c_l in zip(params["rest"], cache["rest"]):
            if "rec" in p_l:
                x, st = _rglru_block_decode(p_l, x, c_l, cfg)
                rest.append(st)
            else:
                x, ck, cv = _attn_block_decode(p_l, x, c_l[0], c_l[1], pos, cfg,
                                               circular=True)
                rest.append((ck, cv))
        cache = {"super": sup, "rest": rest}

    elif fam == "vlm":
        def body(x, inp):
            p_super, ks, vs, cks, cvs = inp

            def inner(xx, inp2):
                p_l, ck, cv = inp2
                xx, ck, cv = _attn_block_decode(p_l, xx, ck, cv, pos, cfg, circular=circ)
                return xx, (ck, cv)
            x, (ks, vs) = jax.lax.scan(inner, x, (p_super["self"], ks, vs))
            pc = p_super["cross"]
            h = apply_norm(pc["norm1"], x, cfg.norm_type)
            res = attn.cross_attention(pc["attn"], h, cks, cvs, n_heads=cfg.n_heads,
                                       n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim)
            x = x + jnp.tanh(pc["gate_attn"].astype(jnp.float32)).astype(x.dtype) * res
            h2 = apply_norm(pc["norm2"], x, cfg.norm_type)
            out = apply_mlp(pc["mlp"], h2, cfg.mlp_type)
            x = x + jnp.tanh(pc["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * out
            return x, (ks, vs)
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        cache = dict(cache, k=ks, v=vs)

    elif fam == "audio":
        def body(x, inp):
            p_l, ck, cv, ek, ev = inp
            h = apply_norm(p_l["norm1"], x, cfg.norm_type)
            res, (ck, cv) = attn.decode_self_attention(
                p_l["self_attn"], h, ck, cv, pos, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                use_rope=cfg.use_rope, rope_theta=cfg.rope_theta, circular=circ)
            x = x + res
            h2 = apply_norm(p_l["norm2"], x, cfg.norm_type)
            x = x + attn.cross_attention(p_l["cross_attn"], h2, ek, ev,
                                         n_heads=cfg.n_heads,
                                         n_kv_heads=cfg.n_kv_heads,
                                         head_dim=cfg.head_dim)
            h3 = apply_norm(p_l["norm3"], x, cfg.norm_type)
            x = x + apply_mlp(p_l["mlp"], h3, cfg.mlp_type)
            return x, (ck, cv)
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        cache = dict(cache, k=ks, v=vs)
    else:
        raise ValueError(fam)

    logits = unembed(params, cfg, x)
    return logits, cache


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            extras: Optional[Dict[str, jnp.ndarray]] = None, *,
            q_chunk: int = 1024):
    """Prefill: full forward, returns (last-token logits, populated cache).

    For attention families the per-layer (k, v) from the forward pass *is* the
    cache; recurrent families carry their final state.
    """
    h, _, cache = forward_trunk(params, cfg, tokens, extras,
                                collect_cache=True, remat=False, q_chunk=q_chunk)
    logits = unembed(params, cfg, h[:, -1:, :])
    return logits, cache
