"""End-to-end training driver.

Two scales:
* ``--cluster`` — pod-scale pjit/shard_map path (the dry-run's step functions)
  on whatever devices exist (meshes down to 1x1 on CPU);
* default       — FL simulation scale: vmapped clients, wireless scheduling,
  compression + EF (the chapter's actual regime).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --steps 20 \
        --reduced --cluster
    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
        --rounds 50 --policy age --compressor topk
"""
from __future__ import annotations

import argparse
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import SHAPES, get_config
from repro.core import compat
from repro.core.algorithms import (algo_params, algorithm_names,
                                   from_server_name)
from repro.core.compression import compression_params, compressor_names
from repro.core.privacy import privacy_names, privacy_params
from repro.data import (FederatedLoader, SyntheticLMDataset, batch_iterator,
                        dirichlet_partition)
from repro.fl import runtime as fl_runtime
from repro.fl.server import flat_dim
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import batch_specs
from repro.launch.steps import TrainPolicy, make_init_fn, make_train_step
from repro.models import transformer as tf


def make_compression(name: str, d: int, k_frac: float = 0.01):
    """CLI name -> (registry name, CompressionParams) for the d-dim model."""
    return name, compression_params(k=max(1, int(k_frac * d)), levels=256)


def run_cluster(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_local_mesh(args.mesh_data, args.mesh_model)
    policy = TrainPolicy(mode=args.mode, compression=args.compression,
                         error_feedback=args.compression not in ("none", "bf16"),
                         local_steps=args.local_steps, lr=args.lr,
                         optimizer=args.optimizer,
                         total_steps=args.steps, remat=not args.reduced)
    ds = SyntheticLMDataset(cfg.vocab_size, args.seq_len, 4096, seed=0)
    it = batch_iterator(ds, args.batch, seed=0)

    with mesh:
        init = make_init_fn(cfg, policy, mesh)
        state = jax.jit(init)(jax.random.PRNGKey(args.seed))
        step_fn = jax.jit(make_train_step(cfg, policy, mesh))
        losses = []
        for step in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            if cfg.family == "vlm":
                batch["vision_embeds"] = jnp.zeros(
                    (args.batch, cfg.n_vision_tokens, cfg.vision_dim), jnp.float32)
            if cfg.family == "audio":
                batch["audio_embeds"] = jnp.zeros(
                    (args.batch, cfg.n_audio_frames, cfg.d_model), jnp.float32)
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % max(1, args.steps // 20) == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({time.time() - t0:.2f}s) [{policy.tag()}]")
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.steps, state["params"])
    assert losses[-1] < losses[0], "training did not reduce loss"
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


def run_federated(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ds = SyntheticLMDataset(cfg.vocab_size, args.seq_len, 8192, seed=0)
    parts = dirichlet_partition(ds.labels_cls, args.n_devices,
                                alpha=args.dirichlet_alpha, seed=0,
                                min_per_client=args.batch)
    loader = FederatedLoader(ds, parts, args.batch, args.local_steps, seed=0)

    def loss_fn(params, batch):
        return tf.lm_loss(params, cfg, batch, remat=False)

    params = tf.init_params(cfg, jax.random.PRNGKey(args.seed))
    d = flat_dim(params)
    comp_name, cparams = make_compression(args.compressor, d)
    algorithm = args.algorithm
    if args.server is not None:
        algorithm = from_server_name(args.server)
        warnings.warn(f"--server is deprecated; use --algorithm {algorithm}",
                      DeprecationWarning, stacklevel=2)
    aparams = algo_params(lr=args.lr, momentum=args.momentum,
                          prox_mu=args.prox_mu, server_lr=args.server_lr,
                          slowmo_beta=args.slowmo_beta)
    sim = fl_runtime.SimConfig(
        n_devices=args.n_devices, n_scheduled=args.n_scheduled,
        rounds=args.rounds, local_steps=args.local_steps,
        algorithm=algorithm, algo_params=aparams,
        policy=args.policy,
        compression=comp_name, compression_params=cparams,
        privacy=args.privacy,
        privacy_params=privacy_params(clip=args.dp_clip, sigma=args.dp_sigma,
                                      field_bits=args.field_bits),
        model_bits=32.0 * d)

    # engine="host" keeps the seed's O(1)-per-round batch memory: the scan
    # engine would stack all rounds' token batches on device, which for real
    # transformer payloads and long runs can exceed accelerator memory.
    logs = fl_runtime.run_simulation(
        sim, loss_fn, params,
        lambda t, n: {k: jnp.asarray(v) for k, v in loader.next_round().items()},
        engine=args.engine)
    for lg in logs[:: max(1, len(logs) // 20)]:
        eps = (f" eps={lg.epsilon:.2f}" if args.privacy != "none"
               and np.isfinite(lg.epsilon) else "")
        print(f"round {lg.round:4d} t={lg.latency_s:9.1f}s loss={lg.loss:.4f} "
              f"sched={lg.n_scheduled}{eps}")
    print(f"final loss {logs[-1].loss:.4f}")
    # DP noise at CLI-chosen sigma can legitimately dominate a short run
    if args.dp_sigma == 0.0 or args.privacy in ("none", "secagg"):
        assert logs[-1].loss < logs[0].loss


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--cluster", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    # cluster args
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mode", default="pssgd",
                    choices=["pssgd", "localsgd", "fsdp"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8", "sign"])
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    # federated args
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--engine", default="host", choices=["scan", "host"],
                    help="simulation engine: 'scan' compiles the whole run "
                         "but stacks all rounds' batches on device "
                         "(O(rounds) memory); 'host' (default) samples "
                         "round-by-round like the seed loop")
    ap.add_argument("--n-devices", type=int, default=16)
    ap.add_argument("--n-scheduled", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--policy", default="random")
    ap.add_argument("--algorithm", default="fedavg",
                    choices=sorted(algorithm_names()),
                    help="optimization algorithm (core.algorithms registry)")
    ap.add_argument("--server", default=None,
                    choices=["avg", "slowmo", "adam", "yogi"],
                    help="deprecated: use --algorithm")
    ap.add_argument("--server-lr", type=float, default=1.0)
    ap.add_argument("--slowmo-beta", type=float, default=0.5)
    ap.add_argument("--prox-mu", type=float, default=0.01)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--compressor", default="none",
                    choices=sorted(compressor_names()),
                    help="uplink compression (registry name; compressed "
                         "bits-on-the-wire drive the simulated latency)")
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5)
    ap.add_argument("--privacy", default="none",
                    choices=sorted(privacy_names()),
                    help="privacy mechanism (core.privacy registry): secure "
                         "aggregation masks and/or DP clip+noise; the mask "
                         "key-agreement bits price the uplink and DP runs "
                         "report the accounted (epsilon, delta)")
    ap.add_argument("--dp-clip", type=float, default=1.0,
                    help="per-client L2 clip (DP sensitivity bound)")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="Gaussian noise multiplier (0 = clip only)")
    ap.add_argument("--field-bits", type=float, default=20.0,
                    help="fixed-point bits per coordinate for the secagg "
                         "finite-field encoding")
    args = ap.parse_args()
    compat.use_compile_cache()
    if args.cluster:
        run_cluster(args)
    else:
        run_federated(args)


if __name__ == "__main__":
    main()
