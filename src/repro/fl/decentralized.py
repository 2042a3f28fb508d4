"""Decentralized learning on the compiled engine (paper §I.B, Alg. 2).

A whole multi-round gossip run is **one** ``lax.scan`` program, built on the
same pattern as the flat/HFL engines in ``fl/runtime.py`` (whose engine
cache, ``ENGINE_STATS`` trace counter, and ``message_bits_jax`` payload
pricing this module shares):

* the mixing matrix ``W`` (eqs. 7-8) is a **traced** argument — topology is
  a sweep axis, so a grid of ring/torus/ER/MH matrices vmaps through
  :func:`run_gossip_sweep` with zero retraces;
* every directed D2D edge is priced through the channel layer: per-edge
  Rayleigh fading (``faults.d2d_fading``; Gauss-Markov when faults are on),
  pairwise path loss from in-program xy geometry, sender bandwidth split
  over its out-degree, and ``wireless.comm_latency_jax`` per edge — the
  synchronous gossip round costs the **slowest active edge**;
* gossip messages go through the compression registry with per-edge-
  *direction* error feedback in the scan carry (an ``(N, N, D)`` residual:
  what i failed to tell j stays between i and j). ``compression="none"``
  reduces the exchange to exactly ``W @ X``;
* time-varying graphs compose with ``core/faults.py``: the Gilbert-Elliott
  availability mask gates edges and ``topology.gate_mixing_jax``
  renormalizes the effective ``W`` in-program — an isolated node's row is
  exactly one-hot, so it keeps its own model bitwise;
* the fog hybrid (PAPERS.md: "From Federated to Fog Learning", 2006.03594)
  composes this with the HFL machinery: cluster members run ``gossip_steps``
  D2D consensus steps per round over an intra-cluster graph built from
  ``hierarchy.hfl_geometry_xy_jax`` geometry (mixing via the jnp twins in
  ``core/topology.py``), and every ``hcfg.inter_cluster_period`` rounds the
  members sync through their SBS up to the MBS over priced uplink/backhaul/
  downlink hops.

``engine="host"`` dispatches the *same* jitted step once per round — the
bitwise parity baseline, same contract as the flat/HFL engines.

The seed-era helpers (``consensus_step``, ``gossip_round``,
``ring_gossip_shard_map``) remain as the numpy-reference-style building
blocks and the TPU-native ``ppermute`` form.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import faults as faults_lib
from repro.core import topology, wireless
from repro.core.algorithms import registry as algo_registry
from repro.core.algorithms.registry import AlgoParams
from jax import shard_map
from repro.core.compression import registry as compression
from repro.core.compression.registry import CompressionParams
from repro.core.faults import FaultParams
from repro.core.hierarchy import HFLConfig, hfl_geometry_xy_jax
from repro.fl import server as fl_server
from repro.fl.link import message_bits_jax
from repro.fl.runtime import (ENGINE_STATS, _ENGINE_CACHE, _cached,
                              stack_batches)

PyTree = Any

# gossip has no server step: only the pure-local client updates make sense
# on the decentralized path (control-variate/staleness algorithms assume a
# coordinator holding global state)
GOSSIP_ALGORITHMS = ("fedavg", "fedavg_m", "fedprox")


# ---------------------------------------------------------------------------
# Config + logs
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """Static shape of a compiled gossip/fog run (the engine-cache key).

    Continuous knobs (channel, compression levels, lr, fault rates, the
    mixing matrix itself) are *traced* arguments of the engine — only the
    fields here change the compiled program.
    """
    n_nodes: int = 16
    rounds: int = 50
    algorithm: str = "fedavg"            # local update from the registry
    algo_params: Optional[AlgoParams] = None
    seed: int = 0
    model_bits: float = 1e6              # simulated payload of one message
    comp_latency_s: float = 0.05         # mean exponential compute time
    compression: str = "none"            # D2D message compressor (registry)
    compression_params: Optional[CompressionParams] = None
    faults: Optional[FaultParams] = None  # None = static graph, no churn
    # --- fog hybrid (run_fog) --------------------------------------------
    gossip_steps: int = 1                # k D2D consensus steps per round
    d2d_radius_m: Optional[float] = None  # None: all same-cluster pairs
    mixing: str = "laplacian"            # in-program builder: laplacian | mh

    def __post_init__(self):
        if self.algorithm not in GOSSIP_ALGORITHMS:
            raise ValueError(
                f"gossip supports server-free algorithms "
                f"{GOSSIP_ALGORITHMS}; got {self.algorithm!r}")
        compression.get_compressor(self.compression)  # raises on unknown
        if self.mixing not in ("laplacian", "mh"):
            raise ValueError(f"mixing must be 'laplacian' or 'mh'; "
                             f"got {self.mixing!r}")
        if self.gossip_steps < 1:
            raise ValueError("gossip_steps must be >= 1")
        if self.n_nodes < 2:
            raise ValueError("need at least 2 nodes to gossip")
        if self.faults is not None and not isinstance(self.faults,
                                                      FaultParams):
            raise TypeError("GossipConfig.faults must be a FaultParams "
                            "(see repro.core.faults.fault_params)")

    def static_key(self) -> Tuple:
        """Hashable engine-cache key: traced leaves (algo/compression/fault
        params) participate only through their *presence*."""
        return (self.n_nodes, self.rounds, self.algorithm, self.seed,
                self.model_bits, self.comp_latency_s, self.compression,
                self.faults is not None, self.gossip_steps,
                self.d2d_radius_m, self.mixing)


@dataclasses.dataclass
class GossipLogs:
    """Per-round engine outputs; leading axes = (variants?, rounds)."""
    loss: np.ndarray            # mean training loss (eval loss with a batch)
    latency_s: np.ndarray       # cumulative simulated wall clock
    comm_s: np.ndarray          # this round's slowest-active-edge airtime
    comp_s: np.ndarray          # this round's slowest node compute
    uplink_bits: np.ndarray     # D2D (+ fog sync) bits on the wire
    backhaul_bits: np.ndarray   # fog SBS<->MBS bits (zero for pure gossip)
    consensus_err: np.ndarray   # RMS deviation of node models from the mean
    n_edges: np.ndarray         # active directed D2D edges this round
    n_online: np.ndarray        # available nodes (== n_nodes, faults off)


def _logs_from_outs(outs) -> GossipLogs:
    return GossipLogs(*(np.asarray(o) for o in outs))


def _resolve_aparams(cfg: GossipConfig) -> AlgoParams:
    if cfg.algo_params is not None:
        return cfg.algo_params
    return algo_registry.default_algo_params()


def _resolve_cparams(cfg: GossipConfig, init_params) -> CompressionParams:
    if cfg.compression_params is not None:
        return cfg.compression_params
    return compression.default_compression_params(
        fl_server.flat_dim(init_params))


def _check_w(w, n: int) -> jnp.ndarray:
    w = jnp.asarray(w, jnp.float32)
    if w.shape != (n, n):
        raise ValueError(f"mixing matrix must be ({n}, {n}) for "
                         f"n_nodes={n}; got {w.shape}")
    if not topology.is_doubly_stochastic(np.asarray(w), tol=1e-5):
        raise ValueError(
            "mixing matrix is not doubly stochastic; build it with "
            "topology.laplacian_mixing / metropolis_hastings_mixing")
    return w


# ---------------------------------------------------------------------------
# Engine internals
# ---------------------------------------------------------------------------
def _edge_keys(key: jax.Array, n: int):
    """(N, N) grid of per-directed-edge subkeys (row = sender)."""
    ks = jax.random.split(key, n * n)
    return ks.reshape((n, n) + ks.shape[1:])


def _exchange(cfg: GossipConfig, compress_fn, w_eff: jnp.ndarray,
              x: jnp.ndarray, ef: jnp.ndarray, key: jax.Array, cparams
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One consensus exchange x_i <- sum_j W_ij m_{j->i} (eq. 7) with
    compressed per-edge messages and per-edge-direction error feedback.

    ``w_eff`` is indexed (dst, src); ``ef`` is (src, dst, D). Returns
    ``(mixed, new_ef, uplink_bits, active_edges)``. With ``"none"``
    compression this is exactly ``w_eff @ x`` (and ``ef`` stays zero), which
    is what the numpy-reference parity tests pin down.
    """
    n, d = x.shape
    eye = jnp.eye(n, dtype=bool)
    act_ds = (w_eff > 0.0) & ~eye            # (dst, src) priced edges
    n_act = jnp.sum(act_ds.astype(jnp.float32))
    if cfg.compression == "none":
        bits_msg = message_bits_jax("none", cparams, cfg.model_bits, d)
        return w_eff @ x, ef, bits_msg * n_act, n_act
    act_sd = act_ds.T                        # (src, dst)
    inp = x[:, None, :] + ef                 # (src, dst, D) EF'd message
    keys = _edge_keys(key, n)
    wire, _ = jax.vmap(jax.vmap(compress_fn, in_axes=(None, 0, 0)),
                       in_axes=(None, 0, 0))(cparams, keys, inp)
    ef = jnp.where(act_sd[:, :, None], inp - wire, ef)
    w_diag = jnp.diag(w_eff)
    w_off = jnp.where(eye, 0.0, w_eff)
    # self term uses the node's own uncompressed model; neighbours get the
    # compressed wire message for their edge direction
    mixed = w_diag[:, None] * x + jnp.einsum("ds,sdk->dk", w_off, wire)
    bits_msg = message_bits_jax(cfg.compression, cparams, cfg.model_bits, d)
    return mixed, ef, bits_msg * n_act, n_act


def _d2d_airtime(cfg: GossipConfig, chan, cparams, dist_nn: jnp.ndarray,
                 fading_nn: jnp.ndarray, act_ds: jnp.ndarray, d: int
                 ) -> jnp.ndarray:
    """Slowest-active-edge airtime of one synchronous exchange. Each sender
    splits its bandwidth over its active out-edges (orthogonal D2D
    subchannels); an outage edge (non-positive rate) costs ``inf``."""
    snr = wireless.snr_jax(dist_nn, fading_nn, chan)          # (dst, src)
    deg_out = jnp.sum(act_ds.astype(jnp.float32), axis=0)     # (src,)
    rates = wireless.shannon_rate_jax(
        snr, chan.bandwidth_hz / jnp.maximum(deg_out, 1.0)[None, :])
    bits_msg = message_bits_jax(cfg.compression, cparams, cfg.model_bits, d)
    lat = wireless.comm_latency_jax(bits_msg, rates)          # (dst, src)
    return jnp.max(jnp.where(act_ds, lat, 0.0))


def _make_gossip_fns(cfg: GossipConfig, loss_fn, has_eval: bool):
    """Build ``(init_carry, step, engine)`` for the compiled gossip run.

    ``engine(key, chan, cparams, aparams, w[, fparams], init_params,
    batches_all, eval_batch)`` scans ``step`` over the pre-sampled rounds;
    the host path dispatches the same jitted ``step`` once per round.
    """
    n = cfg.n_nodes
    algo = algo_registry.get_algorithm(cfg.algorithm)
    comp_active = cfg.compression != "none"
    compress_fn = (compression.get_compressor(cfg.compression)
                   if comp_active else None)
    faults_on = cfg.faults is not None

    def init_carry(init_params):
        x = jnp.tile(algo_registry.flatten_vec(init_params)[None, :], (n, 1))
        ef = jnp.zeros((n, n, x.shape[1]), jnp.float32) if comp_active else ()
        carry = (x, ef, jnp.float32(0.0))
        if faults_on:
            carry += (jnp.ones((n,), bool), jnp.zeros((n * n, 2)))
        return carry

    def step(chan, cparams, aparams, fparams, w, dist_nn, k_rounds,
             template, eval_batch, carry, xs):
        if faults_on:
            x, ef, clock, avail, fad = carry
        else:
            x, ef, clock = carry
            avail = None
        t, batches = xs
        kt = jax.random.fold_in(k_rounds, t)
        kc, kz = jax.random.split(jax.random.fold_in(kt, 1))
        d = x.shape[1]

        # --- time-varying graph: churn gates edges, W renormalizes -------
        if faults_on:
            avail = faults_lib.churn_step(fparams, kt, avail)
            w_eff = topology.gate_mixing_jax(w, avail)
        else:
            w_eff = w
        eye = jnp.eye(n, dtype=bool)
        act_ds = (w_eff > 0.0) & ~eye

        # --- per-directed-edge channel, priced like any other hop --------
        kt_d2d = jax.random.fold_in(kt, faults_lib.D2D_FOLD)
        if faults_on:
            fad, fpow = faults_lib.gauss_markov_fading(fparams, kt_d2d,
                                                       fad, t)
            fading_nn = fpow.reshape(n, n)
        else:
            fading_nn = faults_lib.d2d_fading(kt, n * n).reshape(n, n)
        comm_s = jnp.where(
            jnp.any(act_ds),
            _d2d_airtime(cfg, chan, cparams, dist_nn, fading_nn, act_ds, d),
            0.0)

        # --- consensus exchange (eq. 7) ----------------------------------
        mixed, ef, ubits, n_act = _exchange(cfg, compress_fn, w_eff, x, ef,
                                            kz, cparams)

        # --- local update on the mixed model (Alg. 2 line 5) -------------
        mixed_tree = algo_registry.unflatten_rows(mixed, template)

        def one(p, b):
            return algo.client_update(loss_fn, aparams, p, b, None)

        deltas, _, losses = jax.vmap(one)(mixed_tree, batches)
        delta_flat, _ = fl_server.flatten_clients(deltas)
        comp_lat = cfg.comp_latency_s * jax.random.exponential(kc, (n,))
        if faults_on:
            comp_lat = comp_lat * faults_lib.straggler_multiplier(
                fparams, kt, n)
            # an offline node neither computes nor moves: its mixed row is
            # already bitwise its own model (one-hot W_eff row), and the
            # local delta is withheld
            x = jnp.where(avail[:, None], mixed + delta_flat, x)
            comp_s = jnp.max(jnp.where(avail, comp_lat, 0.0))
            n_online = jnp.sum(avail.astype(jnp.float32))
            loss_train = (jnp.sum(losses * avail)
                          / jnp.maximum(n_online, 1.0))
        else:
            x = mixed + delta_flat
            comp_s = jnp.max(comp_lat)
            n_online = jnp.float32(n)
            loss_train = jnp.mean(losses)
        clock = clock + comm_s + comp_s

        if has_eval:
            avg = algo_registry.unflatten_vec(jnp.mean(x, axis=0), template)
            loss = loss_fn(avg, eval_batch)[0]
        else:
            loss = loss_train
        drift = jnp.sqrt(jnp.mean((x - jnp.mean(x, axis=0)) ** 2))
        outs = (loss, clock, comm_s, comp_s, ubits, jnp.float32(0.0),
                drift, n_act, n_online)
        carry = ((x, ef, clock, avail, fad) if faults_on
                 else (x, ef, clock))
        return carry, outs

    def engine(key, chan, cparams, aparams, w, *rest):
        ENGINE_STATS["traces"] += 1
        if faults_on:
            fparams, init_params, batches_all, eval_batch = rest
        else:
            fparams = None
            init_params, batches_all, eval_batch = rest
        k_pos, k_rounds = jax.random.split(key)
        pos = wireless.sample_positions_xy_jax(k_pos, chan, n)
        dist_nn = wireless.pairwise_dist_jax(pos)

        def body(carry, xs):
            return step(chan, cparams, aparams, fparams, w, dist_nn,
                        k_rounds, init_params, eval_batch, carry, xs)

        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
        carry, outs = lax.scan(body, init_carry(init_params),
                               (ts, batches_all))
        return carry[0], outs

    return init_carry, step, engine


def _gossip_cache_key(cfg: GossipConfig, loss_fn, has_eval: bool,
                      tag: str) -> Tuple:
    return ("gossip", tag, cfg.static_key(), id(loss_fn), has_eval)


def _get_gossip_engine(cfg: GossipConfig, loss_fn, has_eval: bool,
                       vmapped: bool = False) -> Callable:
    def make():
        _, _, engine = _make_gossip_fns(cfg, loss_fn, has_eval)
        if vmapped:
            n_var = 5 + (cfg.faults is not None)
            return jax.jit(jax.vmap(engine,
                                    in_axes=(0,) * n_var + (None,) * 3))
        return jax.jit(engine)
    tag = "vmap" if vmapped else "single"
    return _cached(_ENGINE_CACHE, _gossip_cache_key(cfg, loss_fn, has_eval,
                                                    tag), make)


def _get_gossip_host_step(cfg: GossipConfig, loss_fn,
                          has_eval: bool) -> Callable:
    def make():
        _, step, _ = _make_gossip_fns(cfg, loss_fn, has_eval)

        def host_step(chan, cparams, aparams, fparams, w, dist_nn, k_rounds,
                      template, eval_batch, carry, t, batches):
            ENGINE_STATS["traces"] += 1
            return step(chan, cparams, aparams, fparams, w, dist_nn,
                        k_rounds, template, eval_batch, carry, (t, batches))
        return jax.jit(host_step)
    return _cached(_ENGINE_CACHE,
                   _gossip_cache_key(cfg, loss_fn, has_eval, "host"), make)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def run_gossip(cfg: GossipConfig, loss_fn, init_params: PyTree,
               sample_client_batches, w, *,
               wcfg: Optional[wireless.WirelessConfig] = None,
               eval_batch=None, engine: str = "scan"
               ) -> Tuple[PyTree, GossipLogs]:
    """Run one compiled decentralized (gossip) simulation.

    ``w`` is the doubly-stochastic mixing matrix (a *traced* argument —
    rerunning with a different same-shape W reuses the compiled engine).
    Returns ``(stacked per-node params (leading axis N), GossipLogs)``.
    ``engine="host"`` dispatches the same jitted step round by round (the
    parity baseline).
    """
    wcfg = wcfg or wireless.WirelessConfig(n_devices=cfg.n_nodes)
    w = _check_w(w, cfg.n_nodes)
    chan = wireless.channel_params(wcfg)
    cparams = _resolve_cparams(cfg, init_params)
    aparams = _resolve_aparams(cfg)
    has_eval = eval_batch is not None
    batches_all = stack_batches(sample_client_batches, cfg.rounds,
                                cfg.n_nodes)
    key = jax.random.PRNGKey(cfg.seed)
    if engine == "scan":
        eng = _get_gossip_engine(cfg, loss_fn, has_eval)
        rest = ((cfg.faults,) if cfg.faults is not None else ())
        x_final, outs = eng(key, chan, cparams, aparams, w,
                            *rest, init_params, batches_all, eval_batch)
    elif engine == "host":
        x_final, outs = _run_gossip_host(cfg, loss_fn, init_params,
                                         batches_all, w, chan, cparams,
                                         aparams, eval_batch, key)
    else:
        raise ValueError(f"engine must be 'scan' or 'host'; got {engine!r}")
    node_params = algo_registry.unflatten_rows(np.asarray(x_final),
                                               init_params)
    return node_params, _logs_from_outs(outs)


def _run_gossip_host(cfg, loss_fn, init_params, batches_all, w, chan,
                     cparams, aparams, eval_batch, key):
    """Per-round dispatch of the same jitted step (bitwise parity path)."""
    has_eval = eval_batch is not None
    init_fn, _, _ = _make_gossip_fns(cfg, loss_fn, has_eval)
    host_step = _get_gossip_host_step(cfg, loss_fn, has_eval)
    k_pos, k_rounds = jax.random.split(key)
    pos = wireless.sample_positions_xy_jax(k_pos, chan, cfg.n_nodes)
    dist_nn = wireless.pairwise_dist_jax(pos)
    carry = init_fn(init_params)
    outs = []
    for t in range(cfg.rounds):
        batches = jax.tree.map(lambda a, t=t: a[t], batches_all)
        carry, out = host_step(chan, cparams, aparams, cfg.faults, w,
                               dist_nn, k_rounds, init_params, eval_batch,
                               carry, jnp.int32(t), batches)
        outs.append(out)
    stacked = tuple(jnp.stack([o[i] for o in outs])
                    for i in range(len(outs[0])))
    return carry[0], stacked


def run_gossip_sweep(cfg: GossipConfig, loss_fn, init_params: PyTree,
                     sample_client_batches, *,
                     wgrid: Sequence, seeds: Sequence[int] = (0,),
                     wcfgs: Optional[Sequence] = None,
                     cparams_grid: Optional[Sequence] = None,
                     aparams_grid: Optional[Sequence] = None,
                     fparams_grid: Optional[Sequence] = None,
                     eval_batch=None) -> GossipLogs:
    """Topology (x seed x channel x compression x lr x fault) grid as one
    vmapped engine call — zero retraces across the whole grid.

    The variant axis is the cross product ``seeds x wcfgs x wgrid x
    cparams_grid x aparams_grid x fparams_grid`` in row-major order; logs
    come back with a leading variant axis of that length. ``wgrid`` entries
    must share ``(n_nodes, n_nodes)`` shape (same compiled program).
    """
    wcfgs = list(wcfgs) if wcfgs is not None else [
        wireless.WirelessConfig(n_devices=cfg.n_nodes)]
    ws = [_check_w(w, cfg.n_nodes) for w in wgrid]
    cps = (list(cparams_grid) if cparams_grid is not None
           else [_resolve_cparams(cfg, init_params)])
    aps = (list(aparams_grid) if aparams_grid is not None
           else [_resolve_aparams(cfg)])
    faults_on = cfg.faults is not None or fparams_grid is not None
    if fparams_grid is not None:
        fps = list(fparams_grid)
    elif cfg.faults is not None:
        fps = [cfg.faults]
    else:
        fps = [None]
    if faults_on and cfg.faults is None:
        # the engine's fault machinery keys on cfg.faults being set
        cfg = dataclasses.replace(cfg, faults=fps[0])

    grid = list(itertools.product(range(len(seeds)), range(len(wcfgs)),
                                  range(len(ws)), range(len(cps)),
                                  range(len(aps)), range(len(fps))))
    keys = jnp.stack([jax.random.PRNGKey(seeds[i]) for i, *_ in grid])
    chans = wireless.stack_channel_params([wcfgs[i] for _, i, *_ in grid])
    w_stack = jnp.stack([ws[i] for _, _, i, *_ in grid])
    cp_stack = CompressionParams(*(jnp.stack(
        [getattr(cps[i], f) for *_, i, _, _ in grid])
        for f in CompressionParams._fields))
    ap_stack = AlgoParams(*(jnp.stack(
        [getattr(aps[i], f) for *_, i, _ in grid])
        for f in AlgoParams._fields))
    has_eval = eval_batch is not None
    batches_all = stack_batches(sample_client_batches, cfg.rounds,
                                cfg.n_nodes)
    eng = _get_gossip_engine(cfg, loss_fn, has_eval, vmapped=True)
    var_args = (keys, chans, cp_stack, ap_stack, w_stack)
    if faults_on:
        fp_stack = FaultParams(*(jnp.stack(
            [getattr(fps[i], f) for *_, i in grid])
            for f in FaultParams._fields))
        var_args += (fp_stack,)
    _, outs = eng(*var_args, init_params, batches_all, eval_batch)
    return _logs_from_outs(outs)


# ---------------------------------------------------------------------------
# Fog hybrid: intra-cluster D2D gossip between SBS sync rounds (2006.03594)
# ---------------------------------------------------------------------------
def _make_fog_fns(cfg: GossipConfig, hcfg: HFLConfig, loss_fn,
                  has_eval: bool):
    """Like :func:`_make_gossip_fns`, but the graph comes from in-program
    HFL geometry (same-cluster D2D edges, optionally radius-limited), the
    mixing matrix is built by the jnp topology twins, and every
    ``hcfg.inter_cluster_period`` rounds the clusters sync through SBS ->
    MBS -> broadcast with each hop priced (device uplink over the cluster
    channel, wired backhaul at the traced ``backhaul_rate_bps``, downlink
    broadcast at SBS power).

    Engine signature: ``engine(key, chan, cparams, aparams, bh_rate
    [, fparams], init_params, batches_all, eval_batch)``.
    """
    n = cfg.n_nodes
    algo = algo_registry.get_algorithm(cfg.algorithm)
    comp_active = cfg.compression != "none"
    compress_fn = (compression.get_compressor(cfg.compression)
                   if comp_active else None)
    faults_on = cfg.faults is not None
    mix = (topology.laplacian_mixing_jax if cfg.mixing == "laplacian"
           else topology.metropolis_hastings_mixing_jax)
    period = hcfg.inter_cluster_period

    def init_carry(init_params):
        x = jnp.tile(algo_registry.flatten_vec(init_params)[None, :], (n, 1))
        ef = jnp.zeros((n, n, x.shape[1]), jnp.float32) if comp_active else ()
        carry = (x, ef, jnp.float32(0.0))
        if faults_on:
            carry += (jnp.ones((n,), bool), jnp.zeros((n * n, 2)))
        return carry

    def step(chan, cparams, aparams, fparams, bh_rate, geom, k_rounds,
             template, eval_batch, carry, xs):
        w, dist_nn, cluster_ids, dist_sbs = geom
        if faults_on:
            x, ef, clock, avail, fad = carry
        else:
            x, ef, clock = carry
            avail = None
        t, batches = xs
        kt = jax.random.fold_in(k_rounds, t)
        kc, kz = jax.random.split(jax.random.fold_in(kt, 1))
        d = x.shape[1]

        if faults_on:
            avail = faults_lib.churn_step(fparams, kt, avail)
            w_eff = topology.gate_mixing_jax(w, avail)
        else:
            w_eff = w
        eye = jnp.eye(n, dtype=bool)
        act_ds = (w_eff > 0.0) & ~eye

        # --- k D2D gossip steps, one fading block per round --------------
        kt_d2d = jax.random.fold_in(kt, faults_lib.D2D_FOLD)
        if faults_on:
            fad, fpow = faults_lib.gauss_markov_fading(fparams, kt_d2d,
                                                       fad, t)
            fading_nn = fpow.reshape(n, n)
        else:
            fading_nn = faults_lib.d2d_fading(kt, n * n).reshape(n, n)
        edge_air = jnp.where(
            jnp.any(act_ds),
            _d2d_airtime(cfg, chan, cparams, dist_nn, fading_nn, act_ds, d),
            0.0)
        comm_s = cfg.gossip_steps * edge_air
        ubits = jnp.float32(0.0)
        n_act = jnp.sum(act_ds.astype(jnp.float32))
        mixed = x
        for s in range(cfg.gossip_steps):
            mixed, ef, ub, _ = _exchange(
                cfg, compress_fn, w_eff, mixed, ef,
                jax.random.fold_in(kz, s), cparams)
            ubits = ubits + ub

        # --- local update -------------------------------------------------
        mixed_tree = algo_registry.unflatten_rows(mixed, template)

        def one(p, b):
            return algo.client_update(loss_fn, aparams, p, b, None)

        deltas, _, losses = jax.vmap(one)(mixed_tree, batches)
        delta_flat, _ = fl_server.flatten_clients(deltas)
        comp_lat = cfg.comp_latency_s * jax.random.exponential(kc, (n,))
        if faults_on:
            comp_lat = comp_lat * faults_lib.straggler_multiplier(
                fparams, kt, n)
            x = jnp.where(avail[:, None], mixed + delta_flat, x)
            comp_s = jnp.max(jnp.where(avail, comp_lat, 0.0))
            online = avail.astype(jnp.float32)
        else:
            x = mixed + delta_flat
            comp_s = jnp.max(comp_lat)
            online = jnp.ones((n,), jnp.float32)
        n_online = jnp.sum(online)
        loss_train = jnp.sum(losses * online) / jnp.maximum(n_online, 1.0)

        # --- SBS -> MBS sync every `period` rounds ------------------------
        sync = (t + 1) % period == 0
        # online nodes reset to the global (online-weighted) mean; the
        # sync payload ships the raw model state (EF applies to the D2D
        # deltas, not to absolute-model sync messages), priced below
        gmean = (jnp.sum(x * online[:, None], axis=0)
                 / jnp.maximum(n_online, 1.0))
        x = jnp.where(sync & (online[:, None] > 0.0),
                      gmean[None, :], x)
        # pricing: member uplink over the fading SBS channel with the
        # cluster bandwidth split over its online members, wired SBS<->MBS
        # backhaul both ways, SBS->member broadcast at BS power
        ksync = jax.random.fold_in(kt, faults_lib.DOWNLINK_FOLD)
        fad_up = faults_lib.downlink_fading(ksync, n)
        cnt = jax.ops.segment_sum(online, cluster_ids,
                                  num_segments=hcfg.n_clusters)
        share = chan.bandwidth_hz / jnp.maximum(cnt[cluster_ids], 1.0)
        up_rate = wireless.shannon_rate_jax(
            wireless.snr_jax(dist_sbs, fad_up, chan), share)
        up_lat = wireless.comm_latency_jax(cfg.model_bits, up_rate)
        dl_rate = wireless.shannon_rate_jax(
            wireless.downlink_snr_jax(dist_sbs, faults_lib.d2d_fading(
                ksync, n), chan), chan.bandwidth_hz)
        dl_lat = wireless.comm_latency_jax(cfg.model_bits, dl_rate)
        bh_lat = 2.0 * cfg.model_bits / jnp.maximum(bh_rate, 1.0)
        sync_s = (jnp.max(jnp.where(online > 0.0, up_lat + dl_lat, 0.0))
                  + bh_lat)
        n_clusters_live = jnp.sum((cnt > 0.0).astype(jnp.float32))
        bh_bits = jnp.where(sync,
                            2.0 * cfg.model_bits * n_clusters_live, 0.0)
        sync_bits = jnp.where(sync, cfg.model_bits * n_online, 0.0)
        comm_s = comm_s + jnp.where(sync, sync_s, 0.0)
        ubits = ubits + sync_bits
        clock = clock + comm_s + comp_s

        if has_eval:
            avg = algo_registry.unflatten_vec(
                jnp.sum(x * online[:, None], axis=0)
                / jnp.maximum(n_online, 1.0), template)
            loss = loss_fn(avg, eval_batch)[0]
        else:
            loss = loss_train
        drift = jnp.sqrt(jnp.mean((x - jnp.mean(x, axis=0)) ** 2))
        outs = (loss, clock, comm_s, comp_s, ubits, bh_bits, drift,
                n_act, n_online)
        carry = ((x, ef, clock, avail, fad) if faults_on
                 else (x, ef, clock))
        return carry, outs

    def engine(key, chan, cparams, aparams, bh_rate, *rest):
        ENGINE_STATS["traces"] += 1
        if faults_on:
            fparams, init_params, batches_all, eval_batch = rest
        else:
            fparams = None
            init_params, batches_all, eval_batch = rest
        k_pos, k_rounds = jax.random.split(key)
        pos, cluster_ids, dist_sbs, _, _ = hfl_geometry_xy_jax(
            k_pos, hcfg, n)
        dist_nn = wireless.pairwise_dist_jax(pos)
        same = cluster_ids[:, None] == cluster_ids[None, :]
        adj = same & ~jnp.eye(n, dtype=bool)
        if cfg.d2d_radius_m is not None:
            adj = adj & (dist_nn <= cfg.d2d_radius_m)
        w = mix(adj)
        geom = (w, dist_nn, cluster_ids, dist_sbs)

        def body(carry, xs):
            return step(chan, cparams, aparams, fparams, bh_rate, geom,
                        k_rounds, init_params, eval_batch, carry, xs)

        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
        carry, outs = lax.scan(body, init_carry(init_params),
                               (ts, batches_all))
        return carry[0], outs

    return init_carry, step, engine


def _fog_cache_key(cfg: GossipConfig, hcfg: HFLConfig, loss_fn,
                   has_eval: bool, tag: str) -> Tuple:
    return ("fog", tag, cfg.static_key(), hcfg.static_key(), id(loss_fn),
            has_eval)


def run_fog(cfg: GossipConfig, hcfg: HFLConfig, loss_fn, init_params: PyTree,
            sample_client_batches, *,
            wcfg: Optional[wireless.WirelessConfig] = None,
            eval_batch=None, engine: str = "scan"
            ) -> Tuple[PyTree, GossipLogs]:
    """Fog learning hybrid: every round each node takes a local step and
    runs ``cfg.gossip_steps`` D2D consensus exchanges with its cluster
    peers; every ``hcfg.inter_cluster_period`` rounds the clusters sync
    globally through SBS/MBS with every hop priced. Returns
    ``(stacked per-node params, GossipLogs)``.
    """
    wcfg = wcfg or wireless.WirelessConfig(n_devices=cfg.n_nodes)
    chan = wireless.channel_params(wcfg)
    cparams = _resolve_cparams(cfg, init_params)
    aparams = _resolve_aparams(cfg)
    bh_rate = jnp.float32(hcfg.backhaul_rate_bps)
    has_eval = eval_batch is not None
    batches_all = stack_batches(sample_client_batches, cfg.rounds,
                                cfg.n_nodes)
    key = jax.random.PRNGKey(cfg.seed)
    rest = ((cfg.faults,) if cfg.faults is not None else ())
    if engine == "scan":
        def make():
            _, _, eng = _make_fog_fns(cfg, hcfg, loss_fn, has_eval)
            return jax.jit(eng)
        eng = _cached(_ENGINE_CACHE,
                      _fog_cache_key(cfg, hcfg, loss_fn, has_eval, "scan"),
                      make)
        x_final, outs = eng(key, chan, cparams, aparams, bh_rate, *rest,
                            init_params, batches_all, eval_batch)
    elif engine == "host":
        x_final, outs = _run_fog_host(cfg, hcfg, loss_fn, init_params,
                                      batches_all, chan, cparams, aparams,
                                      bh_rate, eval_batch, key)
    else:
        raise ValueError(f"engine must be 'scan' or 'host'; got {engine!r}")
    node_params = algo_registry.unflatten_rows(np.asarray(x_final),
                                               init_params)
    return node_params, _logs_from_outs(outs)


def _run_fog_host(cfg, hcfg, loss_fn, init_params, batches_all, chan,
                  cparams, aparams, bh_rate, eval_batch, key):
    """Per-round dispatch of the same jitted fog step (parity path)."""
    has_eval = eval_batch is not None
    init_fn, step, _ = _make_fog_fns(cfg, hcfg, loss_fn, has_eval)

    def make():
        def host_step(chan, cparams, aparams, fparams, bh_rate, geom,
                      k_rounds, template, eval_batch, carry, t, batches):
            ENGINE_STATS["traces"] += 1
            return step(chan, cparams, aparams, fparams, bh_rate, geom,
                        k_rounds, template, eval_batch, carry, (t, batches))
        return jax.jit(host_step)
    host_step = _cached(_ENGINE_CACHE,
                        _fog_cache_key(cfg, hcfg, loss_fn, has_eval, "host"),
                        make)
    n = cfg.n_nodes
    k_pos, k_rounds = jax.random.split(key)
    pos, cluster_ids, dist_sbs, _, _ = hfl_geometry_xy_jax(k_pos, hcfg, n)
    dist_nn = wireless.pairwise_dist_jax(pos)
    same = cluster_ids[:, None] == cluster_ids[None, :]
    adj = same & ~jnp.eye(n, dtype=bool)
    if cfg.d2d_radius_m is not None:
        adj = adj & (dist_nn <= cfg.d2d_radius_m)
    mix = (topology.laplacian_mixing_jax if cfg.mixing == "laplacian"
           else topology.metropolis_hastings_mixing_jax)
    geom = (mix(adj), dist_nn, cluster_ids, dist_sbs)
    carry = init_fn(init_params)
    outs = []
    for t in range(cfg.rounds):
        batches = jax.tree.map(lambda a, t=t: a[t], batches_all)
        carry, out = host_step(chan, cparams, aparams, cfg.faults, bh_rate,
                               geom, k_rounds, init_params, eval_batch,
                               carry, jnp.int32(t), batches)
        outs.append(out)
    stacked = tuple(jnp.stack([o[i] for o in outs])
                    for i in range(len(outs[0])))
    return carry[0], stacked


# ---------------------------------------------------------------------------
# Seed-era building blocks (numpy-reference style) + TPU-native ring gossip
# ---------------------------------------------------------------------------
def consensus_step(client_params: PyTree, w: jnp.ndarray) -> PyTree:
    """theta_i <- sum_j W_ij theta_j (eq. 7). client_params leaves: (N, ...)."""
    def leaf(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        mixed = w.astype(jnp.float32) @ flat
        return mixed.reshape(x.shape).astype(x.dtype)
    return jax.tree.map(leaf, client_params)


def gossip_round(client_params: PyTree, w: jnp.ndarray,
                 stacked_batches: Dict[str, jnp.ndarray], loss_fn,
                 lr: float) -> Tuple[PyTree, jnp.ndarray]:
    """Alg. 2: consensus then local SGD step on each device."""
    mixed = consensus_step(client_params, w)

    def one(p, batch):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        p = jax.tree.map(lambda pp, gg: (pp.astype(jnp.float32)
                                         - lr * gg.astype(jnp.float32)).astype(pp.dtype),
                         p, g)
        return p, loss

    new_params, losses = jax.vmap(one)(mixed, stacked_batches)
    return new_params, jnp.mean(losses)


def ring_gossip_shard_map(mesh, axis: str = "data",
                          self_weight: float = 1.0 / 3.0):
    """Returns a pjit-able function mixing each shard's params with its two
    ring neighbours over ``axis``: theta_i <- w*theta_i + w*theta_{i-1} +
    w*theta_{i+1} (the ring Laplacian W of eq. 8 with d_max=2).

    Input/output leaves carry a leading device axis sharded over ``axis``.
    """
    n = mesh.shape[axis]
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]

    def mix_local(local: PyTree) -> PyTree:
        def leaf(x):
            left = jax.lax.ppermute(x, axis, fwd)
            right = jax.lax.ppermute(x, axis, bwd)
            w_n = (1.0 - self_weight) / 2.0
            return (self_weight * x.astype(jnp.float32)
                    + w_n * left.astype(jnp.float32)
                    + w_n * right.astype(jnp.float32)).astype(x.dtype)
        return jax.tree.map(leaf, local)

    def apply(stacked: PyTree) -> PyTree:
        spec = P(axis)
        return shard_map(
            mix_local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: spec, stacked),),
            out_specs=jax.tree.map(lambda _: spec, stacked),
        )(stacked)

    return apply
