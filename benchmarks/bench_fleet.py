"""Fleet-scale engine throughput: chunked client blocks + on-device data.

The headline deliverable of the fleet-scale engine: rounds/s at N = 10^5
clients through the compiled scan engine with clients processed in
power-of-two blocks (``SimConfig.chunk_size``) and batches generated on
device (``SimConfig.datagen``) — so peak temp memory is O(chunk * D) and
data residency O(chunk * H * B), independent of fleet size and round count.
Pre-materializing batches for this config (``stack_batches``) would need
rounds * N * H * B * d * 4 bytes ~ 1.2 GB for 6 rounds; the datagen path
needs none of it.

Rows:

* ``fleet.rounds_per_s@N=1e5`` — headline value row (topk + dense EF, the
  representative config exercising chunking, kernels-dispatch compression
  and error feedback together);
* ``fleet.<config>.us_per_round@N=1e5`` — per-config timings (plain fedavg,
  topk + dense EF, topk + sparse EF in bf16);
* ``fleet.temp_bytes_{chunked,unchunked}@N=1e5`` — XLA
  ``memory_analysis().temp_size_in_bytes`` for the same program with and
  without chunking (the unchunked engine is only *compiled*, never run);
* ``fleet.rounds_per_s@N=1e6`` — best effort, only when the projected cost
  fits a wall-clock cap.

Under ``--fast`` the fleet shrinks to N = 10^4 (keys say ``@N=1e4`` so the
fast baseline never aliases the tracked full-run numbers).
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from benchmarks import common
from benchmarks.common import bench_rounds, emit, make_linear_problem
from repro.data import make_linear_datagen
from repro.fl import runtime as rt

CHUNK = 4096
ROUNDS = 6
N_FULL = 100_000
N_FAST = 10_000
BIG_N = 1_000_000
BIG_CAP_S = 120.0  # skip the 1e6 run when the projected time exceeds this

CONFIGS = [
    ("plain", dict(compression="none")),
    ("topk_ef", dict(compression="topk")),
    ("topk_sparse_bf16", dict(compression="topk", ef_mode="sparse",
                              state_dtype="bfloat16")),
]


def _ntag(n: int) -> str:
    return f"N=1e{int(round(math.log10(n)))}"


def _timed(run) -> float:
    t0 = time.perf_counter()
    out = run()
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return time.perf_counter() - t0


def _make_cfg(n: int, rounds: int, datagen, *, chunk=CHUNK, **kw
              ) -> rt.SimConfig:
    return rt.SimConfig(n_devices=n, n_scheduled=min(256, n), rounds=rounds,
                        policy="random", chunk_size=chunk, datagen=datagen,
                        **kw)


def _temp_bytes(cfg: rt.SimConfig, loss_fn, params) -> int:
    """XLA temp-buffer estimate for the compiled engine (compile only)."""
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._make_sim_fns(cfg, wcfg, loss_fn, False).engine
    lowered = jax.jit(engine).lower(
        jax.random.PRNGKey(cfg.seed), rt.wireless.channel_params(wcfg),
        rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg),
        jax.tree.map(jnp.array, params), None, None)
    return int(lowered.compile().memory_analysis().temp_size_in_bytes)


def _bench_fleet(n: int, rounds: int, datagen, params) -> float:
    """Time every config at fleet size ``n``; returns plain s/round."""
    tag = _ntag(n)
    _, loss_fn, _, _ = make_linear_problem()
    dt_plain = None
    for cname, kw in CONFIGS:
        cfg = _make_cfg(n, rounds, datagen, **kw)

        def run():
            return rt.run_simulation_scan(
                cfg, loss_fn, jax.tree.map(jnp.array, params))

        run()  # compile
        dt = min(_timed(run) for _ in range(2))
        _, logs = run()
        emit(f"fleet.{cname}.us_per_round@{tag}", dt / rounds * 1e6,
             f"loss={logs.loss[-1]:.4f};chunk={CHUNK};"
             f"uplink_bits={logs.uplink_bits[0]:.2e}")
        if cname == "plain":
            dt_plain = dt / rounds
        if cname == "topk_ef":  # headline: the representative fleet config
            emit(f"fleet.rounds_per_s@{tag}", 0.0,
                 f"{n}clients;chunk={CHUNK};topk+EF",
                 value=rounds / dt)
    return dt_plain


def main() -> None:
    n = N_FAST if common.FAST else N_FULL
    rounds = bench_rounds(ROUNDS)
    tag = _ntag(n)
    params, loss_fn, _, w_star = make_linear_problem()
    datagen = make_linear_datagen(w_star)

    dt_round = _bench_fleet(n, rounds, datagen, params)

    # O(chunk * D) memory check: same program with and without chunking.
    # The unchunked engine is compiled but never executed — at fleet scale
    # its temp footprint (full (N, H, B, d) data + (N, D) message temps
    # live at once) is exactly what chunking exists to avoid.
    chunked = _temp_bytes(_make_cfg(n, rounds, datagen, compression="topk"),
                          loss_fn, params)
    unchunked = _temp_bytes(
        _make_cfg(n, rounds, datagen, chunk=None, compression="topk"),
        loss_fn, params)
    emit(f"fleet.temp_bytes_chunked@{tag}", 0.0,
         f"{chunked / 2**20:.0f}MiB;x{unchunked / max(chunked, 1):.1f}"
         "-smaller-than-unchunked", value=float(chunked))
    emit(f"fleet.temp_bytes_unchunked@{tag}", 0.0,
         f"{unchunked / 2**20:.0f}MiB;compile-only", value=float(unchunked))

    # best-effort 10^6-client run: one config, few rounds, under a time cap
    if not common.FAST:
        big_rounds = 2
        projected = dt_round * (BIG_N / n) * big_rounds
        if projected < BIG_CAP_S:
            cfg = _make_cfg(BIG_N, big_rounds, datagen, compression="topk")

            def run_big():
                return rt.run_simulation_scan(
                    cfg, loss_fn, jax.tree.map(jnp.array, params))

            run_big()  # compile
            dt = _timed(run_big)
            emit(f"fleet.rounds_per_s@{_ntag(BIG_N)}", 0.0,
                 f"{BIG_N}clients;chunk={CHUNK};topk+EF",
                 value=big_rounds / dt)
        else:
            print(f"# fleet: skipping N={BIG_N} "
                  f"(projected {projected:.0f}s > cap {BIG_CAP_S:.0f}s)")


if __name__ == "__main__":
    main()
