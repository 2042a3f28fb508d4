"""Mixture-of-experts FFN (qwen2-moe, kimi-k2, mellum2): dropless.

The router scores all ``n_experts`` and each token takes its ``moe_top_k``
best (softmax, renormalized over the chosen). A layer's weight stacks hold
experts ``first_expert ..``; ``routed_experts`` computes their part of the
output for every token routed to them: the token-expert assignments are
sorted by expert, each held expert's rows go through grouped products
(``lax.ragged_dot``), and the rows come back weighted by their gates.
Nothing is dropped, however unevenly the tokens route.

Under expert parallelism (``set_expert_parallel_mesh``) the stacks are
sharded over the ``model`` axis; each shard runs ``routed_experts`` on its
slice with its own ``first_expert`` and the partial outputs are summed
over the axis. A stack that is one chip's share of the experts
(``n_experts_held`` < ``n_experts``, as in the FL client) runs on its
device alone.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init

Params = Dict[str, jnp.ndarray]


_EP_MESH = None  # set by the launch builders; None: one device holds the stacks

# the production mesh's ``model`` axis: stacks of all the experts are padded
# to a multiple of it so that they shard evenly over it (qwen 60 -> 64)
EXPERT_PAD_MULTIPLE = 16


def set_expert_parallel_mesh(mesh) -> None:
    """Run the routed experts expert-parallel over ``mesh``'s ``model`` axis
    (``launch/steps.py`` and ``launch/specs.py`` call this with the
    production mesh; the FL engine and the smoke tests leave it unset)."""
    global _EP_MESH
    _EP_MESH = mesh if (mesh is not None and "model" in mesh.axis_names) else None


def expert_stack_size(cfg: ModelConfig) -> int:
    """Experts in a layer's weight stacks: a chip's share as held; all the
    experts padded to a multiple of ``EXPERT_PAD_MULTIPLE``, the padding
    never routed to."""
    if cfg.experts_held < cfg.n_experts:
        return cfg.experts_held
    return -(-cfg.n_experts // EXPERT_PAD_MULTIPLE) * EXPERT_PAD_MULTIPLE


def init_moe_block(key, cfg: ModelConfig, dtype) -> Params:
    d, dff, n_stack = cfg.d_model, cfg.d_ff_expert, expert_stack_size(cfg)
    keys = jax.random.split(key, 8)

    def stack(k, shape, scale):
        return (jax.random.normal(k, (n_stack,) + shape) * scale).astype(dtype)

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


# ---------------------------------------------------------------------------
# Grouped products. ``lax.ragged_dot`` batches only where every operand has
# its batch axis first, and the TPU's ragged dot takes no batch axis at all;
# a client axis (the engine vmaps its local update) is therefore unrolled
# into one ragged product per client. The backward pass is written out with
# the same two products.
# ---------------------------------------------------------------------------
_RAGGED_CONTRACTING = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _one_at_a_time(fn):
    def rule(axis_size, in_batched, *args):
        outs = [fn(*[a[i] if b else a for a, b in zip(args, in_batched)])
                for i in range(axis_size)]
        return jnp.stack(outs), True
    return rule


@jax.custom_batching.custom_vmap
def _gmm(x, w, sizes):
    """(rows, d) x (groups, d, f) -> (rows, f): row block g by w[g]."""
    return lax.ragged_dot(x, w, sizes)


@jax.custom_batching.custom_vmap
def _tgmm(x, y, sizes):
    """(rows, d), (rows, f) -> (groups, d, f): x_g^T y_g per row block."""
    return lax.ragged_dot_general(x, y, sizes, _RAGGED_CONTRACTING)


_gmm.def_vmap(_one_at_a_time(_gmm))
_tgmm.def_vmap(_one_at_a_time(_tgmm))


def _in_groups(y, sizes):
    """``y`` with the rows past the last group zeroed: what the ragged
    product leaves there is not specified."""
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(sizes), y, jnp.zeros((), y.dtype))


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """Rows of ``x`` in consecutive groups of ``sizes``, group g times
    ``w[g]``; rows past the last group give zeros."""
    return _in_groups(_gmm(x, w, sizes), sizes)


def _grouped_fwd(x, w, sizes):
    return _in_groups(_gmm(x, w, sizes), sizes), (x, w, sizes)


def _grouped_bwd(res, ct):
    x, w, sizes = res
    return (_in_groups(_gmm(ct, jnp.swapaxes(w, 1, 2), sizes), sizes),
            _tgmm(x, ct, sizes).astype(w.dtype), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def routed_experts(p: Params, xf: jnp.ndarray, cfg: ModelConfig,
                   first_expert=0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xf: (T,d) -> (the held routed experts' part of the output (T,d), the
    router's load-balance loss). ``p``'s stacks hold experts
    ``first_expert ..`` of the router's ``n_experts``; ``first_expert`` may
    be traced."""
    t, d = xf.shape
    k, held = cfg.moe_top_k, p["w_gate"].shape[0]
    with jax.named_scope("model.moe.route"):
        probs = jax.nn.softmax(xf.astype(jnp.float32) @ p["router"], axis=-1)
        top_p, top_e = lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        # Switch-style load balance over all experts
        fe = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts), axis=1),
                      axis=0) / k
        aux = cfg.n_experts * jnp.sum(jnp.mean(probs, axis=0) * fe)
        # sort the (token, choice) pairs by held expert, the rest last; a
        # token picks distinct experts, so at most t * min(k, held) pairs
        # are held and the first that many rows hold all of them
        local = top_e.reshape(t * k) - first_expert
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)[:t * min(k, held)]
        sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        token = order // k
        xs = xf[token]
    with jax.named_scope("model.moe.experts"):
        act = _activation(cfg)
        h = (act(grouped_matmul(xs, p["w_gate"], sizes))
             * grouped_matmul(xs, p["w_up"], sizes))
        ys = grouped_matmul(h, p["w_down"], sizes)
    with jax.named_scope("model.moe.combine"):
        gate = jnp.where(mine, top_p.reshape(t * k), 0.0)[order]
        out = jnp.zeros_like(xf).at[token].add(ys * gate[:, None].astype(
            ys.dtype))
    return out, aux


def _activation(cfg: ModelConfig):
    return jax.nn.silu if cfg.mlp_type == "swiglu" else (
        lambda v: jax.nn.gelu(v, approximate=True))


# ---------------------------------------------------------------------------
# Expert parallelism: a shard_map over the ``model`` axis. Tokens are
# replicated across it already (after the attention's all-reduce); each
# shard routes them to the slice of the expert stacks it holds, and only
# the (T, d) partial outputs cross the wire, as one psum. Letting XLA
# partition the dispatch instead all-reduces the (T*k, d) cotangents in
# float32 every layer (a measured 36.8 s collective term on kimi-k2 x
# train_4k).
# ---------------------------------------------------------------------------
def _expert_parallel(p: Params, xf: jnp.ndarray, cfg: ModelConfig, mesh,
                     axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    # inside an outer shard_map the context mesh (with its manual axes)
    # must be used; under plain jit the concrete mesh
    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = ctx if axis in ctx.axis_names else mesh
    per_shard = p["w_gate"].shape[0] // use_mesh.shape[axis]

    def local(xf_, shard, router, wg, wu, wd):
        # ``shard``: this shard's index along ``axis``, delivered as a
        # sharded iota (lax.axis_index lowers to a partition-id computation
        # that re-binds the outer manual axes, which the sdy verifier
        # rejects)
        out, aux = routed_experts(
            {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}, xf_,
            cfg, first_expert=shard[0] * per_shard)
        return lax.psum(out, axis), aux

    return shard_map(
        local, mesh=use_mesh,
        in_specs=(P(), P(axis), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()), axis_names={axis}, check_vma=False,
    )(xf, jnp.arange(use_mesh.shape[axis], dtype=jnp.int32), p["router"],
      p["w_gate"], p["w_up"], p["w_down"])


def moe_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,d) -> (out (B,S,d), the router's load-balance loss): the
    routed experts of ``p``'s stacks, over the ``model`` axis where expert
    parallelism is set and the stacks divide it, plus the shared
    experts."""
    bsz, s, d = x.shape
    xf = x.reshape(bsz * s, d)
    mesh = _EP_MESH
    if mesh is not None and p["w_gate"].shape[0] % mesh.shape["model"] == 0:
        out, aux = _expert_parallel(p, xf, cfg, mesh)
    else:
        out, aux = routed_experts(p, xf, cfg)
    if cfg.n_shared_experts:
        with jax.named_scope("model.moe.experts"):
            act = _activation(cfg)
            hs = act(xf @ p["shared_gate"]) * (xf @ p["shared_up"])
            shared = hs @ p["shared_down"]
        with jax.named_scope("model.moe.combine"):
            out = out + shared
    return out.reshape(bsz, s, d), aux
