"""Step builders: train (PSSGD / local-SGD / FSDP), prefill, decode.

The paper's technique is first-class here:
* ``pssgd``   — Alg. 1 at pod scale: per-data-shard grads, *explicitly*
  compressed all-reduce (core/collectives.py) built with shard_map manual
  over the data axes and auto over ``model`` (TP stays XLA-managed).
* ``localsgd`` — Alg. 6/7: params carry a client axis (one replica per data
  shard), H local steps between compressed delta-consensus rounds; pod-axis
  sync is a separate (dense bf16) step — the HFL schedule of Alg. 9.
* ``fsdp``    — beyond-paper memory mode: 2D-sharded params, XLA-native
  reduce-scatter gradients (required for llama3-405b on 256 chips).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import LONG_CONTEXT_WINDOW, ModelConfig, ShapeSpec
from repro.core.collectives import hierarchical_allreduce
from jax import shard_map
from repro.launch.mesh import data_axes, n_data_shards
from repro.launch import sharding as shard_rules
from repro.models import transformer as tf
from repro.optim.optimizers import OptState, apply_updates, init_opt_state
from repro.optim.schedules import get_schedule

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainPolicy:
    mode: str = "pssgd"           # pssgd | localsgd | fsdp
    compression: str = "none"     # none | bf16 | int8 | sign
    error_feedback: bool = False
    local_steps: int = 1          # H (localsgd)
    sync_pods: bool = True        # reduce over the pod axis this step
    pod_sync_dense: bool = True   # pod sync uses dense bf16 (fast fronthaul)
    optimizer: str = "adamw"
    opt_state_dtype: str = "float32"
    remat: bool = True
    lr: float = 3e-4
    total_steps: int = 10_000

    def tag(self) -> str:
        ef = "+ef" if self.error_feedback else ""
        h = f"+H{self.local_steps}" if self.mode == "localsgd" else ""
        return f"{self.mode}/{self.compression}{ef}{h}"


# ===========================================================================
# State construction (eval_shape friendly: no allocation in the dry-run)
# ===========================================================================
def make_init_fn(cfg: ModelConfig, policy: TrainPolicy, mesh):
    """Returns init(key) -> state dict. Use jax.eval_shape(init, key) for SDS."""
    n_dp = n_data_shards(mesh)
    sdtype = jnp.dtype(policy.opt_state_dtype)

    def init(key):
        params = tf.init_params(cfg, key)
        if policy.mode == "localsgd":
            params = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (n_dp,) + p.shape), params)
            opt = init_opt_state(jax.tree.map(lambda p: p[0], params),
                                 policy.optimizer, sdtype)
            opt = OptState(opt.step,
                           _stack(opt.m, n_dp), _stack(opt.v, n_dp))
        else:
            opt = init_opt_state(params, policy.optimizer, sdtype)
        state = {"params": params, "opt": opt,
                 "step": jnp.zeros((), jnp.int32)}
        if policy.error_feedback and policy.compression not in ("none",):
            base = params if policy.mode != "localsgd" else jax.tree.map(
                lambda p: p[0], params)
            state["ef"] = jax.tree.map(
                lambda p: jnp.zeros((n_dp,) + p.shape, jnp.float32), base)
        return state
    return init


def _stack(tree, n):
    if tree is None:
        return None
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def state_shardings(cfg: ModelConfig, policy: TrainPolicy, mesh,
                    state_sds: PyTree) -> PyTree:
    dp = data_axes(mesh)

    def params_sh(tree):
        if policy.mode == "localsgd":
            return shard_rules.stacked_client_shardings(cfg, tree, mesh)
        return shard_rules.param_shardings(cfg, tree, mesh,
                                           fsdp=(policy.mode == "fsdp"))

    out: Dict[str, Any] = {"params": params_sh(state_sds["params"])}
    m = state_sds["opt"].m
    v = state_sds["opt"].v
    out["opt"] = OptState(
        NamedSharding(mesh, P()),
        params_sh(m) if m is not None else None,
        params_sh(v) if v is not None else None)
    out["step"] = NamedSharding(mesh, P())
    if "ef" in state_sds:
        # leading client axis over data; inner dims follow TP rules
        leaves, treedef = jax.tree_util.tree_flatten_with_path(state_sds["ef"])
        shs = []
        for path, leaf in leaves:
            inner = shard_rules.param_spec(path, leaf.shape[1:], cfg, mesh,
                                           fsdp=False)
            shs.append(NamedSharding(mesh, P(dp, *inner)))
        out["ef"] = jax.tree_util.tree_unflatten(treedef, shs)
    return out


# ===========================================================================
# Train steps
# ===========================================================================
def make_train_step(cfg: ModelConfig, policy: TrainPolicy, mesh):
    if cfg.n_experts:
        import os as _os
        from repro.models.moe import set_expert_parallel_mesh
        set_expert_parallel_mesh(
            None if _os.environ.get("REPRO_DISABLE_EP") else mesh)
    if policy.mode == "fsdp":
        return _make_fsdp_step(cfg, policy)
    if policy.mode == "localsgd":
        return _make_localsgd_step(cfg, policy, mesh)
    return _make_pssgd_step(cfg, policy, mesh)


def _loss_fn(cfg: ModelConfig, policy: TrainPolicy):
    def f(params, batch):
        return tf.lm_loss(params, cfg, batch, remat=policy.remat)
    return f


def _reduction_axes(mesh, policy: TrainPolicy) -> Tuple[str, ...]:
    dp = data_axes(mesh)
    if not policy.sync_pods:
        dp = tuple(a for a in dp if a != "pod")
    return dp


def _make_pssgd_step(cfg: ModelConfig, policy: TrainPolicy, mesh):
    dp = data_axes(mesh)
    red = _reduction_axes(mesh, policy)
    schedule = get_schedule(cfg.lr_schedule, policy.lr, policy.total_steps)
    opt_fn = apply_updates(policy.optimizer)
    loss_fn = _loss_fn(cfg, policy)
    use_ef = policy.error_feedback and policy.compression != "none"

    def inner(params, opt, ef, step, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        e = jax.tree.map(lambda x: x[0], ef) if use_ef else None
        grads, e = hierarchical_allreduce(grads, red, policy.compression, e)
        loss = lax.pmean(loss, dp)
        new_params, new_opt = opt_fn(params, grads, opt, schedule(step))
        new_ef = jax.tree.map(lambda x: x[None], e) if use_ef else ef
        return new_params, new_opt, new_ef, step + 1, loss

    batch_spec = P(dp)
    ef_spec = P(dp)

    def train_step(state, batch):
        ef = state.get("ef", jnp.zeros((n_data_shards(mesh),), jnp.float32))
        in_specs = (P(), P(), jax.tree.map(lambda _: ef_spec, ef), P(),
                    jax.tree.map(lambda _: batch_spec, batch))
        out_specs = (P(), P(), jax.tree.map(lambda _: ef_spec, ef), P(), P())
        params, opt, ef, step, loss = shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=set(dp), check_vma=False)(
            state["params"], state["opt"], ef, state["step"], batch)
        new_state = dict(state, params=params, opt=opt, step=step)
        if "ef" in state:
            new_state["ef"] = ef
        return new_state, {"loss": loss}

    return train_step


def _make_localsgd_step(cfg: ModelConfig, policy: TrainPolicy, mesh):
    dp = data_axes(mesh)
    red = _reduction_axes(mesh, policy)
    intra = tuple(a for a in red if a != "pod") or red
    schedule = get_schedule(cfg.lr_schedule, policy.lr, policy.total_steps)
    opt_fn = apply_updates(policy.optimizer)
    loss_fn = _loss_fn(cfg, policy)
    h = policy.local_steps
    use_ef = policy.error_feedback and policy.compression != "none"

    def inner(params, opt_m, opt_v, opt_step, ef, step, batch):
        p0 = jax.tree.map(lambda x: x[0], params)
        m0 = jax.tree.map(lambda x: x[0], opt_m) if opt_m is not None else None
        v0 = jax.tree.map(lambda x: x[0], opt_v) if opt_v is not None else None
        opt = OptState(opt_step, m0, v0)

        # H local steps over microbatch slices (Alg. 7 lines 5-7)
        bsz = jax.tree.leaves(batch)[0].shape[0]
        micro = jax.tree.map(
            lambda x: x.reshape((h, bsz // h) + x.shape[1:]), batch)

        def local(carry, mb):
            p, o = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, mb)
            p, o = opt_fn(p, g, o, schedule(step))
            return (p, o), loss

        (p_h, opt), losses = lax.scan(local, (p0, opt), micro)

        # compressed delta-consensus over the intra axes (Alg. 6 lines 8-14)
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), p_h, p0)
        e = jax.tree.map(lambda x: x[0], ef) if use_ef else None
        delta_hat, e = hierarchical_allreduce(delta, intra, policy.compression, e)
        p_new = jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype), p0, delta_hat)

        # pod sync (inter-cluster averaging, Alg. 9 line 13): dense bf16
        if policy.sync_pods and "pod" in dp:
            p_new = jax.tree.map(
                lambda p: lax.pmean(p.astype(jnp.bfloat16), "pod").astype(p.dtype),
                p_new)

        loss = lax.pmean(jnp.mean(losses), dp)
        new_params = jax.tree.map(lambda x: x[None], p_new)
        new_m = jax.tree.map(lambda x: x[None], opt.m) if opt.m is not None else opt_m
        new_v = jax.tree.map(lambda x: x[None], opt.v) if opt.v is not None else opt_v
        new_ef = jax.tree.map(lambda x: x[None], e) if use_ef else ef
        return new_params, new_m, new_v, opt.step, new_ef, step + 1, loss

    def train_step(state, batch):
        opt = state["opt"]
        ef = state.get("ef", jnp.zeros((n_data_shards(mesh),), jnp.float32))
        cl = P(dp)
        specs = lambda tree: jax.tree.map(lambda _: cl, tree)  # noqa: E731
        in_specs = (specs(state["params"]),
                    specs(opt.m), specs(opt.v), P(), specs(ef), P(),
                    jax.tree.map(lambda _: P(dp), batch))
        out_specs = (specs(state["params"]), specs(opt.m), specs(opt.v), P(),
                     specs(ef), P(), P())
        params, m, v, ostep, ef, step, loss = shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=set(dp), check_vma=False)(
            state["params"], opt.m, opt.v, opt.step, ef, state["step"], batch)
        new_state = dict(state, params=params, opt=OptState(ostep, m, v),
                         step=step)
        if "ef" in state:
            new_state["ef"] = ef
        return new_state, {"loss": loss}

    return train_step


def _make_fsdp_step(cfg: ModelConfig, policy: TrainPolicy):
    schedule = get_schedule(cfg.lr_schedule, policy.lr, policy.total_steps)
    opt_fn = apply_updates(policy.optimizer)
    loss_fn = _loss_fn(cfg, policy)

    def train_step(state, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        new_params, new_opt = opt_fn(state["params"], grads, state["opt"],
                                     schedule(state["step"]))
        return dict(state, params=new_params, opt=new_opt,
                    step=state["step"] + 1), {"loss": loss}

    return train_step


# ===========================================================================
# Serving steps
# ===========================================================================
def make_prefill_step(cfg: ModelConfig, q_chunk: int = 1024):
    def prefill_step(params, batch):
        extras = {k: v for k, v in batch.items() if k != "tokens"}
        return tf.prefill(params, cfg, batch["tokens"], extras, q_chunk=q_chunk)
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, circular: bool):
    def decode_step(params, cache, token, pos):
        return tf.decode_step(params, cfg, cache, token, pos, circular=circular)
    return decode_step
