"""Mixture-of-Experts FFN with capacity-based dispatch (qwen2-moe, kimi-k2).

Dispatch is sort-free: positions-in-expert come from a cumsum over one-hot
assignments; tokens beyond capacity are *dropped* (standard TPU MoE semantics,
a la GShard/Switch). Expert weight stacks carry a leading expert axis that is
sharded over the ``model`` mesh axis (expert parallelism); under pjit the
scatter/gather lowers to the all-to-all-equivalent collectives.

Experts are padded up to a multiple of the model-axis size (qwen 60 -> 64);
padded experts receive -inf router logits and are never selected.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from jax import shard_map
from repro.models.layers import dense_init

Params = Dict[str, jnp.ndarray]


def _constrain(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """Best-effort sharding constraint (no-op without a mesh, e.g. smoke
    tests). Keeps the dispatch buffers expert-sharded so XLA reshard uses
    all-to-all instead of full-buffer all-reduces (EXPERIMENTS.md §Perf)."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # noqa: BLE001 - no mesh / axis not in mesh
        return x


_EP_MESH = None  # set by launch builders; None -> auto-partitioned path


def set_expert_parallel_mesh(mesh) -> None:
    """Enable nested-shard_map expert parallelism (launch/steps.py calls this
    with the production mesh; smoke tests leave it unset)."""
    global _EP_MESH
    _EP_MESH = mesh if (mesh is not None and "model" in mesh.axis_names) else None


def padded_n_experts(cfg: ModelConfig, multiple: int = 16) -> int:
    e = cfg.n_experts
    return -(-e // multiple) * multiple


def init_moe_block(key, cfg: ModelConfig, dtype, expert_pad_multiple: int = 16) -> Params:
    d, dff = cfg.d_model, cfg.d_ff_expert
    e_pad = padded_n_experts(cfg, expert_pad_multiple)
    keys = jax.random.split(key, 8)

    def stack(k, shape, scale):
        return (jax.random.normal(k, (e_pad,) + shape) * scale).astype(dtype)

    p = {
        "router": dense_init(keys[0], (d, cfg.n_experts), jnp.float32),
        "w_gate": stack(keys[1], (d, dff), d ** -0.5),
        "w_up": stack(keys[2], (d, dff), d ** -0.5),
        "w_down": stack(keys[3], (dff, d), dff ** -0.5),
    }
    if cfg.n_shared_experts:
        sd = cfg.n_shared_experts * dff
        p["shared_gate"] = dense_init(keys[4], (d, sd), dtype)
        p["shared_up"] = dense_init(keys[5], (d, sd), dtype)
        p["shared_down"] = dense_init(keys[6], (sd, d), dtype)
    return p


def moe_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                expert_pad_multiple: int = 16) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    if _EP_MESH is not None:
        return moe_forward_ep(p, x, cfg, _EP_MESH, expert_pad_multiple)
    bsz, s, d = x.shape
    t = bsz * s
    e_real, k = cfg.n_experts, cfg.moe_top_k
    e_pad = padded_n_experts(cfg, expert_pad_multiple)
    cap = int(max(k, -(-k * t // e_real) * cfg.capacity_factor))

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ p["router"])  # (T,E_real)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # --- aux load-balance loss (Switch-style) ---
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    assign_onehot = jax.nn.one_hot(top_e, e_real, dtype=jnp.float32)  # (T,k,E)
    fe = jnp.mean(jnp.sum(assign_onehot, axis=1), axis=0) / k  # fraction per expert
    aux = e_real * jnp.sum(me * fe)

    # --- positions within expert (cumsum over flattened (T*k) choices) ---
    flat_e = top_e.reshape(t * k)
    onehot = jax.nn.one_hot(flat_e, e_pad, dtype=jnp.int32)  # (T*k, E_pad)
    pos_all = jnp.cumsum(onehot, axis=0) - 1  # position if assigned
    flat_pos = jnp.sum(pos_all * onehot, axis=-1)  # (T*k,)
    overflow = flat_pos >= cap
    flat_pos = jnp.where(overflow, cap, flat_pos)  # cap slot == dropped (mode=drop)

    # --- dispatch: (E_pad, cap, d) ---
    xk = jnp.repeat(xf[:, None, :], k, axis=1).reshape(t * k, d)
    buf = jnp.zeros((e_pad, cap, d), dtype=x.dtype)
    buf = buf.at[flat_e, flat_pos].add(xk, mode="drop")
    buf = _constrain(buf, P("model", None, None))

    # --- expert compute (stacked einsum; expert axis sharded over `model`) ---
    act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
        lambda v: jax.nn.gelu(v, approximate=True))
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = _constrain(h, P("model", None, None))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # (E_pad, cap, d)
    out_buf = _constrain(out_buf, P("model", None, None))

    # --- combine: gather back, weight, drop overflows ---
    gathered = out_buf.at[flat_e, flat_pos].get(mode="fill", fill_value=0)  # (T*k, d)
    w = (top_p.reshape(t * k) * (~overflow)).astype(x.dtype)
    out = jnp.sum((gathered * w[:, None]).reshape(t, k, d), axis=1)

    if cfg.n_shared_experts:
        hs = act(xf @ p["shared_gate"]) * (xf @ p["shared_up"])
        out = out + hs @ p["shared_down"]
    return out.reshape(bsz, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel MoE via nested shard_map over the model axis.
#
# The auto-partitioned scatter/gather dispatch above lets XLA all-reduce the
# full (T*k, d) cotangent buffer over the model axis in fp32 every layer
# (measured 36.8 s collective term on kimi-k2 x train_4k — EXPERIMENTS.md
# §Perf). Here dispatch/combine are shard-LOCAL: tokens are replicated across
# the model axis already (post attention all-reduce), each shard routes them
# to its own expert slice, and only the combined (T, d) bf16 partial output
# crosses the wire as a psum.
# ---------------------------------------------------------------------------
def moe_forward_ep(p: Params, x: jnp.ndarray, cfg: ModelConfig, mesh,
                   expert_pad_multiple: int = 16,
                   axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    bsz, s, d = x.shape
    t = bsz * s
    e_real, k = cfg.n_experts, cfg.moe_top_k
    e_pad = padded_n_experts(cfg, expert_pad_multiple)
    cap = int(max(k, -(-k * t // e_real) * cfg.capacity_factor))

    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    assign_onehot = jax.nn.one_hot(top_e, e_real, dtype=jnp.float32)
    fe = jnp.mean(jnp.sum(assign_onehot, axis=1), axis=0) / k
    aux = e_real * jnp.sum(me * fe)

    flat_e = top_e.reshape(t * k)
    onehot = jax.nn.one_hot(flat_e, e_pad, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    flat_pos = jnp.sum(pos_all * onehot, axis=-1)
    overflow = flat_pos >= cap
    weights = (top_p.reshape(t * k) * (~overflow)).astype(x.dtype)

    def local_block(xf_, flat_e_, flat_pos_, weights_, my_id, wg, wu, wd):
        # my_id: (1,) this shard's model-axis index, delivered as a sharded
        # iota input (lax.axis_index lowers to a partition-id computation
        # that re-binds the outer manual axes — sdy verifier rejects it)
        e_local = wg.shape[0]
        lo = my_id[0] * e_local
        le = flat_e_ - lo
        mine = (le >= 0) & (le < e_local) & (flat_pos_ < cap)
        le = jnp.clip(le, 0, e_local - 1)
        pos = jnp.where(mine, flat_pos_, cap)  # cap slot == dropped
        xk = jnp.repeat(xf_[:, None, :], k, axis=1).reshape(t * k, d)
        buf = jnp.zeros((e_local, cap, d), dtype=xf_.dtype)
        buf = buf.at[le, pos].add(xk, mode="drop")
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        gathered = out_buf.at[le, pos].get(mode="fill", fill_value=0)
        gathered = gathered * (weights_ * mine).astype(gathered.dtype)[:, None]
        contrib = jnp.sum(gathered.reshape(t, k, d), axis=1)
        return jax.lax.psum(contrib, axis)

    # inside an outer shard_map the context mesh (with its Manual axis types)
    # must be used; under plain jit fall back to the concrete mesh
    try:
        ctx = jax.sharding.get_abstract_mesh()
        use_mesh = ctx if (ctx is not None and axis in ctx.axis_names) else mesh
    except Exception:  # noqa: BLE001
        use_mesh = mesh
    shard_ids = jnp.arange(use_mesh.shape[axis], dtype=jnp.int32)
    out = shard_map(
        local_block, mesh=use_mesh,
        in_specs=(P(), P(), P(), P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(), axis_names={axis}, check_vma=False,
    )(xf, flat_e, flat_pos, weights, shard_ids,
      p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        hs = act(xf @ p["shared_gate"]) * (xf @ p["shared_up"])
        out = out + hs @ p["shared_down"]
    return out.reshape(bsz, s, d), aux
