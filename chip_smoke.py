"""Smoke run of the wireless-FL engine on one TPU chip.

Drives the compiled engine through the entry points users call
(``run_simulation_scan`` and ``run_sweep``) and checks what comes out:

1. device gate: the first JAX device must be a TPU (no CPU fallback);
2. kernels: the Pallas row kernels, reached through ``mode=None``, against
   their jnp mirrors, at the fleet's width and at the full width of the
   phase-4 client (top-k keep-masks must be identical);
3. fleet: the README fleet (N = 1e5 clients, 256 scheduled, chunks of
   4096, on-device data, top-k with dense error feedback) for 3 rounds;
   the compiled program must hold the Pallas kernel, and the logged uplink
   bits must equal the registry's data-independent price;
4. full-width client: the repo's 124.7M-parameter dense transformer
   (``examples/train_fl_100m.py --full-100m``) as every client's model,
   top-k with error feedback, the payload priced from its own size;
5. sweep: 2 policies x 4 seeds x 2 learning rates on the fleet problem in
   one compiled call; variant (random, seed 0, lr 0.1) must match phase 3.

``--chips 4`` runs only the four-chip path: the phase-5 sweep sharded over
a 4-device mesh, against the same sweep on one device.

Each phase prints its result on one line. Times are labelled smoke timings:
one cold run, not benchmark metrics. The last line is one JSON object
naming the device. A failed check exits non-zero.

Run from the repository root: ``python chip_smoke.py [--chips 4]``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Phase 4: N clients of the 124.7M-parameter model in chunks of MODEL_CHUNK.
# Dense EF holds N rows of 0.46 GiB, and each client of a chunk holds about
# ten more copies in its local update and compression. Compiled for a v5e
# chip, memory_analysis() gives 13.6 GiB for N = 8 in chunks of 2 (3.7 GiB
# of it EF) and 19.0 GiB in chunks of 1, so 8 in chunks of 2 is what one
# 16 GiB chip holds; the run prints the analysis.
MODEL_N = 8
MODEL_CHUNK = 2
MODEL_ROUNDS = 3
FLEET_ROUNDS = 3


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def device_gate(n_chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{d0.platform!r} ({d0.device_kind}, {len(devs)} device(s))")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices; "
                 f"JAX found {len(devs)}")
    say(f"phase 1 device: {d0.platform} {d0.device_kind} x{len(devs)}")
    return d0, len(devs)


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------
def fleet_problem(rounds: int = FLEET_ROUNDS):
    """README fleet: (cfg, loss_fn, params)."""
    from benchmarks.common import make_linear_problem
    from repro.data import make_linear_datagen
    from repro.fl import runtime as rt
    params, loss_fn, _, w_star = make_linear_problem()
    cfg = rt.SimConfig(n_devices=100_000, n_scheduled=256, rounds=rounds,
                       policy="random", chunk_size=4096,
                       datagen=make_linear_datagen(w_star),
                       compression="topk", algo_params=rt.algo_params(lr=0.1))
    return cfg, loss_fn, params


def model_problem(n: int = MODEL_N, chunk: int = MODEL_CHUNK,
                  rounds: int = MODEL_ROUNDS, *, abstract: bool = False):
    """The 124.7M-parameter client: (cfg, loss_fn, params, model config).
    ``abstract`` gives the params as shapes (for a compile without a
    chip)."""
    import jax
    from examples.train_fl_100m import model_100m
    from repro.data import make_token_datagen
    from repro.fl import runtime as rt
    from repro.fl.server import flat_dim
    from repro.models import transformer as tf
    mcfg = model_100m(full=True)

    def init(key):
        return tf.init_params(mcfg, key)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(init, key) if abstract else jax.jit(init)(key)

    def loss_fn(p, batch):
        return tf.lm_loss(p, mcfg, batch, remat=True)

    d = flat_dim(params)
    cfg = rt.SimConfig(
        n_devices=n, n_scheduled=n // 2, rounds=rounds, policy="random",
        chunk_size=chunk, compression="topk", model_bits=32.0 * d,
        algo_params=rt.algo_params(lr=0.01),
        datagen=make_token_datagen(mcfg.vocab_size, local_steps=2, batch=4,
                                   seq=128))
    return cfg, loss_fn, params, mcfg


def compiled_engine(cfg, loss_fn, params):
    """The engine ``run_simulation_scan`` runs for ``cfg``, compiled for
    the arguments it passes: (compiled, compile seconds)."""
    import jax
    import jax.numpy as jnp
    from repro.fl import runtime as rt
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._get_engine(cfg, wcfg, loss_fn, False)
    args = (jax.random.PRNGKey(cfg.seed), rt.wireless.channel_params(wcfg),
            rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg),
            params)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype), args)
    t0 = time.perf_counter()
    compiled = engine.lower(*shapes, None, None).compile()
    return compiled, time.perf_counter() - t0


def timed_run(cfg, loss_fn, params):
    """One ``run_simulation_scan`` call, ended by block_until_ready:
    (final params, logs, seconds)."""
    import jax
    from repro.fl import runtime as rt
    t0 = time.perf_counter()
    final, logs = rt.run_simulation_scan(cfg, loss_fn, params)
    jax.block_until_ready(final)
    return final, logs, time.perf_counter() - t0


def sweep_grid():
    from repro.fl import runtime as rt
    return dict(seeds=[0, 1, 2, 3], policies=["random", "best_channel"],
                aparams_grid=[rt.algo_params(lr=lr) for lr in (0.05, 0.1)])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_kernels(d_full: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    check(ops.resolve_mode(None) == "pallas",
          f"mode=None resolved to {ops.resolve_mode(None)!r}, not 'pallas'")
    for rows, d in ((4096, 32), (2, d_full)):
        kx, ku, ke = jax.random.split(jax.random.PRNGKey(d), 3)
        x = jax.random.normal(kx, (rows, d), jnp.float32)
        k = max(1, d // 100)
        tp, tj = ops.topk_rows(x, k), ops.topk_rows(x, k, mode="jit")
        same_mask = bool(jnp.array_equal(tp != 0, tj != 0))
        d_topk = float(jnp.max(jnp.abs(tp - tj)))
        nnz = int(jnp.sum(tp != 0))
        del tp, tj
        u = jax.random.uniform(ku, (rows, d), jnp.float32)
        qp, qj = ops.qsgd_rows(x, u, 256.0), ops.qsgd_rows(x, u, 256.0,
                                                            mode="jit")
        d_qsgd = float(jnp.max(jnp.abs(qp - qj)))
        # one stochastic-rounding flip moves an entry by one level, norm/256
        q_step = float(jnp.max(jnp.linalg.norm(x, axis=1))) / 256.0
        q_finite = bool(jnp.all(jnp.isfinite(qp)))
        del qp, qj, u
        e = 0.1 * jax.random.normal(ke, (rows, d), jnp.float32)
        (cp, ep), (cj, ej) = ops.sign_ef_rows(x, e), ops.sign_ef_rows(
            x, e, mode="jit")
        d_c = float(jnp.max(jnp.abs(cp - cj)))
        d_e = float(jnp.max(jnp.abs(ep - ej)))
        c_mag = float(jnp.max(jnp.abs(cj)))
        del x, e, cp, ep, cj, ej
        say(f"phase 2 kernels ({rows}, {d}): pallas vs jit  topk masks "
            f"identical={same_mask} nnz={nnz} max|diff| topk={d_topk:.3e} "
            f"qsgd={d_qsgd:.3e} (one level={q_step:.3e}) "
            f"sign_ef c={d_c:.3e} e={d_e:.3e}")
        check(same_mask and d_topk == 0.0, "topk keep-masks differ")
        check(q_finite and d_qsgd <= q_step * (1 + 1e-5),
              "qsgd differs by more than one quantization level")
        check(d_c <= 1e-5 * c_mag and math.isfinite(d_e),
              "sign_ef differs beyond f32 rounding")


def phase_fleet():
    import numpy as np
    from repro.fl import runtime as rt
    cfg, loss_fn, params = fleet_problem()
    compiled, t_compile = compiled_engine(cfg, loss_fn, params)
    check("tpu_custom_call" in compiled.as_text(),
          "fleet engine holds no Pallas kernel (tpu_custom_call)")
    timed_run(cfg, loss_fn, params)  # first call: the engine's own compile
    _, logs, dt = timed_run(cfg, loss_fn, params)
    d = rt.fl_server.flat_dim(params)
    price = float(rt.link.message_bits_jax(cfg.compression,
                                           rt._resolve_cparams(cfg, params),
                                           cfg.model_bits, d))
    want = logs.n_scheduled.astype(np.float64) * price
    finite = bool(np.all(np.isfinite(logs.loss)))
    say(f"phase 3 fleet N={cfg.n_devices} scheduled={cfg.n_scheduled} "
        f"chunk={cfg.chunk_size} D={d} topk+EF: tpu_custom_call=True "
        f"loss {logs.loss[0]:.6f} -> {logs.loss[-1]:.6f} "
        f"uplink_bits/round={logs.uplink_bits[0]:.6e} "
        f"(price {price:.6e} x {int(logs.n_scheduled[0])})")
    say(f"phase 3 smoke timings: compile {t_compile:.2f} s, "
        f"{dt / cfg.rounds:.4f} s/round")
    check(finite, "fleet losses not finite")
    check(np.allclose(logs.uplink_bits, want, rtol=1e-6, atol=0.0),
          f"uplink bits {logs.uplink_bits} != registry price {want}")
    return logs


def phase_model() -> int:
    import numpy as np
    cfg, loss_fn, params, mcfg = model_problem()
    from repro.fl.server import flat_dim
    d = flat_dim(params)
    compiled, t_compile = compiled_engine(cfg, loss_fn, params)
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    say(f"phase 4 memory_analysis N={cfg.n_devices} chunk={cfg.chunk_size}:"
        f" arguments {mem.argument_size_in_bytes / gib:.2f} GiB, outputs "
        f"{mem.output_size_in_bytes / gib:.2f} GiB, temp "
        f"{mem.temp_size_in_bytes / gib:.2f} GiB")
    del compiled
    _, logs, dt = timed_run(cfg, loss_fn, params)
    say(f"phase 4 model {mcfg.name} D={d} (param_count() "
        f"{mcfg.param_count()}) N={cfg.n_devices} "
        f"chunk={cfg.chunk_size} rounds={cfg.rounds} topk+EF: loss "
        f"{logs.loss[0]:.6f} -> {logs.loss[-1]:.6f} "
        f"uplink_bits/round={logs.uplink_bits[0]:.6e}")
    say(f"phase 4 smoke timings: compile {t_compile:.2f} s, first call "
        f"{dt:.2f} s for {cfg.rounds} rounds (its compile comes from the "
        "persistent cache when the line above stored it)")
    check(bool(np.all(np.isfinite(logs.loss))), "model losses not finite")
    return d


def run_grid(cfg, loss_fn, params, devices=None):
    """The phase-5 grid through ``run_sweep`` (whose logs come back on the
    host): (logs by policy, engine traces, seconds)."""
    from repro.fl import runtime as rt
    before = rt.ENGINE_STATS["traces"]
    t0 = time.perf_counter()
    out = rt.run_sweep(cfg, loss_fn, params, None, devices=devices,
                       **sweep_grid())
    return out, rt.ENGINE_STATS["traces"] - before, time.perf_counter() - t0


def phase_sweep(fleet_logs) -> None:
    import numpy as np
    cfg, loss_fn, params = fleet_problem()
    out, traces, dt = run_grid(cfg, loss_fn, params)
    grid = sweep_grid()
    n_var = len(grid["seeds"]) * len(grid["aparams_grid"])
    shapes_ok = all(out[p].loss.shape == (n_var, cfg.rounds)
                    for p in grid["policies"])
    finite = all(bool(np.all(np.isfinite(out[p].loss)))
                 for p in grid["policies"])
    # variant order is product(seeds, lrs): (seed 0, lr 0.1) is index 1
    v = out["random"]
    same_sched = bool(np.array_equal(v.participation[1],
                                     fleet_logs.participation))
    d_loss = float(np.max(np.abs(v.loss[1] - fleet_logs.loss)))
    say(f"phase 5 sweep {len(grid['policies'])} policies x "
        f"{len(grid['seeds'])} seeds x {len(grid['aparams_grid'])} lrs: "
        f"traces={traces} losses finite={finite} (random, seed 0, lr 0.1) "
        f"vs phase 3: participation identical={same_sched} "
        f"max|loss diff|={d_loss:.3e}")
    say(f"phase 5 smoke timings: {dt:.2f} s for the sweep, compile "
        "included")
    check(traces == 1, f"sweep traced {traces} engines, expected 1")
    check(shapes_ok and finite, "sweep losses malformed or not finite")
    check(same_sched, "sweep variant scheduled differently from phase 3")
    check(d_loss <= 1e-5 * float(np.max(np.abs(fleet_logs.loss))),
          "sweep variant loss differs from phase 3")


# The sharded sweep compiles a per-chip program for 4 of the 16 variants,
# and XLA may tile a reduction differently at that shape, so the loss (a
# float sum over clients) may round differently: 1 ulp on a TPU v5e. The
# schedule, the bits and the latencies must still match exactly.
LOSS_ULPS = 4


def phase_sharded_sweep() -> None:
    import numpy as np
    from repro.fl import runtime as rt
    cfg, loss_fn, params = fleet_problem()
    mesh = rt._resolve_sweep_mesh(4, None)
    check(mesh is not None and mesh.devices.size == 4,
          f"devices=4 resolved to mesh {mesh}")
    ref, _, t_ref = run_grid(cfg, loss_fn, params)
    shd, _, t_shd = run_grid(cfg, loss_fn, params, devices=4)
    lines, exact, ulps = [], True, 0.0
    for p in sweep_grid()["policies"]:
        for field in ("participation", "n_scheduled", "uplink_bits",
                      "latency_s"):
            same = bool(np.array_equal(getattr(ref[p], field),
                                       getattr(shd[p], field)))
            exact &= same
            lines.append(f"{p}.{field}={'equal' if same else 'DIFFERENT'}")
        a, b = ref[p].loss, shd[p].loss
        ulps = max(ulps, float(np.max(np.abs(a - b) / np.spacing(np.abs(a)))))
        same = bool(np.array_equal(a, b))
        lines.append(f"{p}.loss={'equal' if same else 'close'}")
    say(f"phase 5 sharded sweep mesh={tuple(mesh.devices.shape)} over "
        f"{mesh.axis_names}: " + " ".join(lines)
        + f" max loss diff={ulps:.1f} ulp")
    say(f"phase 5 smoke timings: {t_ref:.2f} s one device, {t_shd:.2f} s "
        "four devices, compile included")
    check(exact, "sharded sweep scheduled, priced or timed differently "
          "from the single-device sweep")
    check(ulps <= LOSS_ULPS, f"sharded sweep loss differs by {ulps} ulp")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep sharded over four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        sys.exit(f"chip_smoke: no repro package under {REPO}/src; run it "
                 "from a checkout of the repository")
    sys.path[:0] = [os.path.join(REPO, "src"), REPO]
    dev, count = device_gate(args.chips)
    from repro.core import compat
    say(f"compile cache: {compat.use_compile_cache()}")
    try:
        if args.chips == 4:
            phase_sharded_sweep()
        else:
            from repro.fl.server import flat_dim
            d_model = flat_dim(model_problem(abstract=True)[2])
            phase_kernels(d_model)
            fleet_logs = phase_fleet()
            phase_model()
            phase_sweep(fleet_logs)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
