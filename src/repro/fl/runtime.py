"""Device-resident wireless FL simulation engine (paper §III experiments).

Architecture
------------
An entire multi-round simulation compiles into **one XLA program**:

* the channel layer is ``jnp`` (``core/wireless.py`` jnp twins) driven by
  ``jax.random`` keys — continuous channel parameters travel as a traced
  :class:`~repro.core.wireless.ChannelParams`, so they can be vmapped;
* the scheduling policy is a pure-``jnp`` function from the registry
  ``scheduling.get_policy(name)`` — the *name* is static, so there is no
  Python branch in the compiled program;
* the optimization **algorithm** is first-class
  (``core/algorithms/registry.py``): ``get_algorithm(name)`` returns the
  pure-jnp ``(client_update, server_update, init_algo_state)`` triple for
  fedavg / fedavg_m / fedprox / scaffold / slowmo / fedadam / fedyogi; the
  *name* is static while every hyperparameter (lr, momentum, prox_mu,
  server_lr, ...) rides the traced :class:`AlgoParams` — so a learning-rate
  grid vmaps instead of retracing. SCAFFOLD's per-client control variates
  are an (N, D) matrix in the scan carry ((m, chunk, D) blocks when the
  client pass is chunked) and its second uplink message doubles the
  priced bits-on-the-wire;
* ``run_simulation_scan`` wraps one round as a ``lax.scan`` body whose carry
  is ``(FLState, LinkState)``; the wireless part of a round — channel draw,
  pricing, retransmissions, the synchronous round clock, fault logs, DP
  accounting and the :class:`~repro.fl.link.RoundOut` logs, which come
  back stacked — is ``fl/link.py``'s, shared with the hierarchical engine;
* compression is first-class (``core/compression/registry.py``): the
  compressor *name* is static, its continuous parameters travel as a traced
  :class:`~repro.core.compression.registry.CompressionParams`, per-client EF
  error state lives in the scan carry (inside ``FLState``), and the
  compressed bits-on-the-wire price the uplink via ``comm_latency_jax``
  *inside* the scan — so compression shortens rounds and interacts with the
  deadline/latency/update-aware policies;
* ``run_sweep`` vmaps the scanned engine over seed x channel-config x
  compression-level x algorithm-hyperparameter x **policy** variants: the
  policy rides as a traced one-hot mixture weight
  (``scheduling.get_policy_mixture`` — the static *set* of enabled names
  keys the engine cache), so a full multi-policy grid is **one** compiled
  call per (compressor-name, algorithm-name) tuple; ``devices=``/``mesh=``
  shards the flattened variant axis over a 1-D device mesh via
  ``jax.shard_map`` (ragged grids are padded to a multiple of the mesh
  and sliced back), and ``hcfg=`` /
  ``hcfgs=`` route the same grid through the hierarchical engine (the
  backhaul rate is traced, so rate grids share one trace);
* hierarchical FL (``run_hfl``) is wireless-aware end to end: per-cluster
  ``ChannelParams`` price the device->SBS uplink of the compressed payload,
  each cluster runs the registry scheduling policy over its members, EF and
  SCAFFOLD ctrl state ride the HFL scan carry, and the periodic SBS->MBS
  sync ships a separately compressed and priced backhaul payload;
* each stage of a round runs under a ``jax.named_scope``
  (``fl.channel``, ``fl.schedule``, ``fl.data``, ``fl.local_update``,
  ``fl.compress``, ``fl.client_state``, ``fl.privacy``, ``fl.aggregate``,
  ``fl.server_update``, ``fl.log``), which names its operations in the
  compiled program's metadata, and the host side of a call under
  ``jax.profiler.TraceAnnotation`` spans (``fl.engine_lookup``,
  ``fl.prepare``, ``fl.dispatch``, ``fl.fetch_logs``), so a profiler
  trace reads per stage; both are inert when nothing traces;
* compiled engines are cached per static config (``_ENGINE_CACHE``, bounded
  FIFO) so repeated calls never re-trace; on the single-run path the initial
  params are donated (they alias the returned final params, letting XLA run
  the scan in-place on the parameter buffers);
* decentralized gossip and the fog hybrid (``fl/decentralized.py``) are
  built on the same pattern and plug into this module's engine cache,
  ``ENGINE_STATS`` trace counter, and ``link.message_bits_jax`` payload
  pricing — their mixing matrix ``W`` is one more *traced* argument, so a
  topology grid is a sweep axis like any other.

``run_simulation`` / ``run_hfl`` keep the legacy host-loop signature as thin
wrappers: ``engine="host"`` (or a host-only ``eval_fn`` with no attached
``eval_batch``) falls back to a per-round dispatch loop built from the *same*
round step, which is also the baseline the benchmarks compare against.
``SimConfig.lr`` / ``SimConfig.server`` are deprecated for one release and
map onto ``algorithm`` + ``algo_params`` with a ``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import warnings
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core import chunking, compat, faults as faults_lib
from repro.core import scheduling, wireless
from repro.core.faults import FaultParams, fault_params, stack_fault_params
from repro.core.algorithms import registry as algo_registry
from repro.core.algorithms.registry import (AlgoParams, algo_params,
                                            stack_algo_params)
from repro.core.compression import registry as compression
from repro.core.compression.registry import CompressionParams
from repro.core.privacy import registry as privacy_lib
from repro.core.privacy.registry import (PrivacyParams, privacy_params,
                                         stack_privacy_params)
from repro.core.hierarchy import (HFLConfig, broadcast_to_clients,
                                  hfl_geometry_jax, inter_cluster_average)
from repro.fl import link
from repro.fl import server as fl_server

PyTree = Any

# trace-time side effect counter: bumped once per engine (re)trace, so tests
# and benchmarks can assert the no-retrace property of the engine cache.
ENGINE_STATS = {"traces": 0}

# domain-separation constant for the on-device data stream: the round key
# kt already feeds five consumers (fading/compute/policy/norms/compression),
# so the datagen key is a fold_in of kt under this tag — adding a datagen
# never shifts the engine's other randomness.
DATAGEN_FOLD = 0x0DA7A


def datagen_round_key(seed: int, t: int) -> jax.Array:
    """The key the scan engine hands ``SimConfig.datagen`` on round ``t`` of
    a run with ``SimConfig.seed == seed`` — so hosts/tests can rebuild any
    round's on-device batches exactly (``datagen(key, ids)``)."""
    _, k_rounds = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.fold_in(jax.random.fold_in(k_rounds, t), DATAGEN_FOLD)


@dataclasses.dataclass
class SimConfig:
    n_devices: int = 40
    # scheduling budget: one global int on the flat engine; on the
    # hierarchical engine (run_hfl) it is the *per-cluster* budget and may
    # be a tuple with one entry per cluster (heterogeneous cell budgets)
    n_scheduled: Any = 8
    rounds: int = 100
    local_steps: int = 1
    # first-class algorithm: a registry *name* (static, engine-cache key)
    # plus traced hyperparameters (vmappable sweep axes — lr, momentum,
    # prox_mu, server_lr, slowmo_beta, beta1, beta2, eps).
    algorithm: str = "fedavg"
    algo_params: Optional[AlgoParams] = None
    policy: str = "random"  # see scheduling.policy_names()
    seed: int = 0
    model_bits: float = 1e6          # uplink payload per round (per message)
    comp_latency_s: float = 0.05     # per-device compute time (mean)
    deadline_s: float = 5.0          # for the P4 policy
    age_alpha: float = 1.0
    # first-class compression: a registry *name* (static, engine-cache key)
    # plus traced continuous parameters (vmappable in sweeps). The simulated
    # uplink payload is model_bits compressed at the registry operator's
    # bits-per-parameter rate; "none" sends exactly model_bits (legacy).
    compression: str = "none"
    compression_params: Optional[CompressionParams] = None
    double_ef: bool = False          # downlink (PS-side) EF too (Alg. 3/6)
    # fleet-scale engine knobs: process clients in power-of-two blocks of
    # chunk_size inside the round (peak temp memory O(chunk*D), bitwise
    # parity with the unchunked pass); generate client batches on device
    # (datagen(key, ids) -> (len(ids), H, ...) leaves — row i must depend
    # only on (key, ids[i])); store per-client message-space state sparsely
    # (top-k family) and/or in bf16.
    chunk_size: Optional[int] = None
    ef_mode: str = "dense"               # "dense" | "sparse" (O(N*slots))
    ef_slots: Optional[int] = None       # sparse-EF slots (default d // 50)
    state_dtype: str = "float32"         # "float32" | "bfloat16" EF/ctrl
    datagen: Optional[Callable] = None   # on-device per-client batch source
    # failure-aware engine: a traced FaultParams (core.faults) switches the
    # scan into fault mode — Gilbert-Elliott churn, mid-round dropout,
    # Pareto stragglers, SNR-threshold decode failure with up to
    # max_retries re-priced retransmissions, and Gauss-Markov correlated
    # fading state in the carry. Only the *presence* of faults and the
    # static retry bound key the engine cache; every fault probability is
    # traced, so a fault grid is one more vmapped sweep axis.
    faults: Optional[FaultParams] = None
    max_retries: int = 0                 # static retransmission bound
    # privacy axis (core.privacy registry): the mechanism *name* is static
    # (engine-cache key) — "none" | "secagg" | "dp" | "secagg_dp" — while
    # clip/sigma/field_bits ride the traced PrivacyParams, so a clip x
    # sigma grid vmaps with zero retraces. Legal (privacy, compression,
    # algorithm) combinations are validated here (see
    # core.privacy.FIELD_COMPATIBLE).
    privacy: str = "none"
    privacy_params: Optional[PrivacyParams] = None
    # deprecated (one release): stringly-typed spellings, mapped onto
    # algorithm/algo_params by __post_init__ with a DeprecationWarning
    lr: Optional[float] = None
    server: Optional[str] = None

    def __post_init__(self):
        if isinstance(self.n_scheduled, list):
            self.n_scheduled = tuple(self.n_scheduled)
        if self.chunk_size is not None and not chunking.is_pow2(
                self.chunk_size):
            raise ValueError(f"SimConfig.chunk_size must be a power of two "
                             f"(canonical-tree alignment), got "
                             f"{self.chunk_size}")
        if self.ef_mode not in ("dense", "sparse"):
            raise ValueError(f"unknown ef_mode {self.ef_mode!r}; use "
                             "'dense'/'sparse'")
        if self.ef_mode == "sparse" and self.compression not in (
                "topk", "randk", "rtopk"):
            raise ValueError(
                "ef_mode='sparse' stores a truncated top-|slots| residual, "
                "which only approximates EF for the sparsifying compressor "
                f"family (topk/randk/rtopk), not {self.compression!r}")
        if self.state_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown state_dtype {self.state_dtype!r}; "
                             "use 'float32'/'bfloat16'")
        if self.max_retries < 0:
            raise ValueError(f"SimConfig.max_retries must be >= 0, got "
                             f"{self.max_retries}")
        if self.faults is not None and not isinstance(self.faults,
                                                      FaultParams):
            raise ValueError(
                "SimConfig.faults must be a core.faults.FaultParams "
                f"(see fault_params(...)), got {type(self.faults).__name__}")
        if self.server is not None:
            mapped = algo_registry.from_server_name(self.server)
            warnings.warn(
                f"SimConfig.server={self.server!r} is deprecated; use "
                f"SimConfig.algorithm={mapped!r} (core.algorithms registry)",
                DeprecationWarning, stacklevel=3)
            if self.algorithm not in ("fedavg", mapped):
                raise ValueError(
                    f"SimConfig sets both algorithm={self.algorithm!r} and "
                    f"the deprecated server={self.server!r} (-> {mapped!r}); "
                    "drop SimConfig.server")
            self.algorithm = mapped
            self.server = None
        if self.lr is not None:
            warnings.warn(
                "SimConfig.lr is deprecated; pass algo_params="
                "algo_params(lr=...) — a traced AlgoParams field, so a "
                "learning-rate sweep vmaps instead of retracing",
                DeprecationWarning, stacklevel=3)
            ap = (self.algo_params if self.algo_params is not None
                  else algo_registry.default_algo_params())
            self.algo_params = ap._replace(lr=jnp.float32(self.lr))
            self.lr = None
        if self.privacy_params is not None and not isinstance(
                self.privacy_params, PrivacyParams):
            raise ValueError(
                "SimConfig.privacy_params must be a core.privacy."
                "PrivacyParams (see privacy_params(...)), got "
                f"{type(self.privacy_params).__name__}")
        # raises on unknown names and on illegal (privacy, compression,
        # algorithm) combinations — after the deprecated-server mapping so
        # the resolved algorithm is what gets checked
        privacy_lib.validate_privacy_config(
            self.privacy, compression=self.compression,
            algorithm=self.algorithm)


@dataclasses.dataclass
class RoundLog:
    round: int
    latency_s: float
    loss: float
    n_scheduled: int
    participation: np.ndarray
    uplink_bits: float = 0.0   # total scheduled uplink payload this round
    comm_s: float = 0.0        # bottleneck device's upload time
    comp_s: float = 0.0        # bottleneck device's compute time
    downlink_bits: float = 0.0  # broadcast payload priced this round
    n_survived: int = 0        # scheduled clients whose update decoded
    n_dropped: int = 0         # scheduled clients lost to faults
    retransmissions: float = 0.0   # extra uplink attempts this round
    staleness_mean: float = 0.0    # mean per-client staleness (fault mode)
    epsilon: float = float("inf")  # cumulative DP epsilon after this round
    delta: float = 1.0             # the delta the epsilon is reported at
    mask_bits: float = 0.0         # secagg key-agreement overhead bits


@dataclasses.dataclass
class SimLogs:
    """Stacked per-round logs. Arrays carry a leading ``(rounds,)`` axis —
    or ``(variants, rounds)`` when produced by :func:`run_sweep`."""
    loss: np.ndarray
    latency_s: np.ndarray
    n_scheduled: np.ndarray
    participation: np.ndarray  # (..., rounds, n_devices) bool
    uplink_bits: np.ndarray    # (..., rounds) scheduled bits-on-the-wire
    comm_s: np.ndarray         # (..., rounds) comm share of the round time
    comp_s: np.ndarray         # (..., rounds) compute share of the round time
    # failure-aware fields (None on logs produced by older callers that
    # construct SimLogs positionally, e.g. persisted tuning studies)
    downlink_bits: Optional[np.ndarray] = None  # (..., rounds) broadcast bits
    n_survived: Optional[np.ndarray] = None     # (..., rounds) decoded
    n_dropped: Optional[np.ndarray] = None      # (..., rounds) lost to faults
    retransmissions: Optional[np.ndarray] = None  # (..., rounds) extra tx
    staleness_mean: Optional[np.ndarray] = None   # (..., rounds)
    # privacy fields (epsilon is +inf and delta 1.0 when no DP mechanism
    # runs; epsilon is monotone non-decreasing in rounds by construction)
    epsilon: Optional[np.ndarray] = None     # (..., rounds) cumulative eps
    delta: Optional[np.ndarray] = None       # (..., rounds) reporting delta
    mask_bits: Optional[np.ndarray] = None   # (..., rounds) secagg overhead

    def to_round_logs(self) -> List[RoundLog]:
        if self.loss.ndim != 1:
            raise ValueError("to_round_logs needs unbatched (rounds,) logs")
        # a field an older caller left None keeps RoundLog's default
        given = {f.name: getattr(self, f.name)
                 for f in dataclasses.fields(self)
                 if getattr(self, f.name) is not None}
        return [RoundLog(t, **{k: v[t] if v.ndim > 1 else v[t].item()
                               for k, v in given.items()})
                for t in range(self.loss.shape[0])]


def stack_batches(sample_client_batches: Callable[[int, int], Dict[str, jnp.ndarray]],
                  rounds: int, n_devices: int) -> PyTree:
    """Pre-sample every round's client batches; leaves get a leading
    ``(rounds,)`` axis (the xs of the scan)."""
    per_round = [sample_client_batches(t, n_devices) for t in range(rounds)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_round)


def _policy_cfg(cfg: SimConfig, wcfg: wireless.WirelessConfig
                ) -> scheduling.PolicyConfig:
    return scheduling.PolicyConfig(
        n_devices=cfg.n_devices, n_scheduled=cfg.n_scheduled,
        model_bits=cfg.model_bits, deadline_s=cfg.deadline_s,
        age_alpha=cfg.age_alpha,
        sub_bw=wcfg.bandwidth_hz / wcfg.n_subchannels,
        n_subchannels=wcfg.n_subchannels)


def _resolve_cparams(cfg: SimConfig, init_params) -> CompressionParams:
    if cfg.compression_params is not None:
        return cfg.compression_params
    return compression.default_compression_params(
        fl_server.flat_dim(init_params))


def _resolve_aparams(cfg: SimConfig) -> AlgoParams:
    if cfg.algo_params is not None:
        return cfg.algo_params
    return algo_registry.default_algo_params()


def _resolve_pparams(cfg: SimConfig) -> PrivacyParams:
    if cfg.privacy_params is not None:
        return cfg.privacy_params
    return privacy_lib.default_privacy_params()


class _Round(NamedTuple):
    """A round builder's parts, which one frame runs as the scanned
    ``engine`` or round by round (``host_step``). Each builder keeps its
    own scan body: JAX's PRNG lowering names the ops of a key split after
    the function that first makes it, so the flat engine's compiled program
    stays the same only while ``_make_sim_fns``' own closures split the
    run and round keys."""
    cfg: SimConfig
    init_carry: Callable   # init_params -> scan carry
    # (chan, cparams, aparams, extra, fparams, pparams, pol_w, site,
    #  k_rounds, eval_batch) -> step(carry, (t, batches))
    make_step: Callable
    scan: Callable         # the engine's body, with the args split
    site: Callable         # (key, chan) -> placement: distances / geometry
    params_of: Callable    # (carry, site) -> the global model
    n_extra: int = 0       # engine-specific traced args (HFL: bh_rate)
    mix: bool = False      # a traced policy-mixture weight closes the axes

    def _split(self, rest):
        # the engine-specific args come first, then the optional traced
        # axes in a fixed relative order — fparams, pparams, pol_w — each
        # present only where its static switch is on; the shared trailing
        # args close the list
        rest = list(rest)
        extra = tuple(rest.pop(0) for _ in range(self.n_extra))
        fparams = rest.pop(0) if self.cfg.faults is not None else None
        pparams = rest.pop(0) if self.cfg.privacy != "none" else None
        pol_w = rest.pop(0) if self.mix else None
        return extra, fparams, pparams, pol_w, rest

    @property
    def engine(self) -> Callable:
        """The full scanned run: ``(key, chan, cparams, aparams, *extra,
        [fparams], [pparams], [pol_w], init_params, batches, eval_batch)
        -> (final params, stacked RoundOut)``."""
        def engine(key, chan, cparams, aparams, *rest):
            ENGINE_STATS["traces"] += 1  # python side effect: trace only
            extra, fparams, pparams, pol_w, shared = self._split(rest)
            return self.scan(key, chan, cparams, aparams, extra, fparams,
                             pparams, pol_w, *shared)
        return engine

    @property
    def host_step(self) -> Callable:
        """One round with the run-specific values as arguments: ``(chan,
        cparams, aparams, *extra, [fparams], [pparams], site, k_rounds,
        eval_batch, carry, xs) -> (carry, RoundOut)``."""
        def host_step(chan, cparams, aparams, *rest):
            extra, fparams, pparams, _, shared = self._split(rest)
            site, k_rounds, eval_batch, carry, xs = shared
            return self.make_step(chan, cparams, aparams, extra, fparams,
                                  pparams, None, site, k_rounds,
                                  eval_batch)(carry, xs)
        return host_step


def _make_sim_fns(cfg: SimConfig, wcfg: wireless.WirelessConfig, loss_fn,
                  has_eval: bool,
                  policy_axis: Optional[Tuple[str, ...]] = None) -> _Round:
    """The flat round: ``fl_round``'s client pass inside the shared
    wireless round (``fl/link.py``).

    ``policy_axis`` switches the policy from a static name (``cfg.policy``)
    to a *traced* axis: the engine takes an extra one-hot weight vector
    ``pol_w`` of shape ``(len(policy_axis),)`` selecting which enabled
    policy runs (``scheduling.get_policy_mixture``), so a vmapped sweep can
    carry the policy choice per variant instead of retracing per policy.
    """
    n = cfg.n_devices
    if isinstance(cfg.n_scheduled, tuple):
        raise ValueError(
            "per-cluster n_scheduled tuples are a hierarchical-engine "
            "feature (run_hfl); the flat engine takes one global budget")
    pcfg = _policy_cfg(cfg, wcfg)
    if policy_axis is not None:
        mixture_fn = scheduling.get_policy_mixture(policy_axis)
        policy_fn = None
    else:
        mixture_fn = None
        policy_fn = scheduling.get_policy(cfg.policy)
    algo = algo_registry.get_algorithm(cfg.algorithm)
    comp_active = cfg.compression != "none"
    compress_fn = (compression.get_compressor(cfg.compression)
                   if comp_active else None)
    # chunk >= N degenerates to the unchunked pass (and would otherwise
    # change the canonical padding); chunked, per-client state is allocated
    # blocked, (m, chunk, ...) rows padded to whole blocks, so each block of
    # the client pass reads and writes one contiguous slab of it in place
    chunk = (cfg.chunk_size
             if cfg.chunk_size is not None and cfg.chunk_size < n else None)
    n_rows = (chunking.n_blocks(n, chunk), chunk) if chunk else n
    state_dt = (jnp.bfloat16 if cfg.state_dtype == "bfloat16"
                else jnp.float32)
    # static privacy switch: only the mechanism *name* specializes the
    # trace; clip/sigma/field_bits are traced PrivacyParams. The privacy
    # key is derived only when a mechanism is active, so privacy="none"
    # reproduces the legacy randomness streams bit for bit.
    priv_on = cfg.privacy != "none"
    priv = privacy_lib.get_privacy(cfg.privacy) if priv_on else None
    dp_on = priv_on and priv.uses_dp
    round_fn = functools.partial(
        fl_server.fl_round, loss_fn=loss_fn, algo=algo,
        compression_name=(cfg.compression if comp_active else None),
        chunk_size=chunk, n_clients=n, privacy=priv)

    # static fault switch: only the *presence* of faults (and the retry
    # bound) specializes the trace — every probability is traced FaultParams
    faults_on = cfg.faults is not None

    def init_carry(init_params):
        # message-space state rides in the scan carry (inside FLState): the
        # (*n_rows, D) EF matrix (dense/SparseEF, fp32/bf16) and, for
        # control-variate algorithms, the (*n_rows, D) ctrl matrix + (D,)
        # server control variate.
        state0 = fl_server.init_fl_state(
            init_params, n, algo=algo, use_ef=comp_active,
            double_ef=comp_active and cfg.double_ef, ef_mode=cfg.ef_mode,
            ef_slots=cfg.ef_slots, state_dtype=state_dt, n_rows=n_rows)
        state0 = dataclasses.replace(state0, round=jnp.int32(0))
        return state0, link.LinkState.init(n, faults_on, dp_on)

    def make_step(chan: wireless.ChannelParams, cparams: CompressionParams,
                  aparams: AlgoParams, extra, fparams, pparams, pol_w,
                  dist: jnp.ndarray, k_rounds: jax.Array, eval_batch):
        lk = link.Link(
            cfg, algo.uplink_factor, priv, chan, dist,
            lambda snr: wireless.shannon_rate_jax(
                snr, chan.bandwidth_hz / cfg.n_scheduled),
            n - 1, fparams, pparams)

        def z_eff(n_surv):
            # secagg_dp's local field noise aggregates to an effective
            # multiplier sigma * sqrt(survivors); central dp uses sigma
            if priv.dp_local:
                return pparams.sigma * jnp.sqrt(jnp.maximum(n_surv, 1.0))
            return pparams.sigma

        def step(carry, xs):
            state, ls = carry
            t, batches = xs
            kt = jax.random.fold_in(k_rounds, t)
            kf, kc, kp, kn, kz = jax.random.split(kt, 5)
            if priv_on:
                # fold-tagged so the five streams above are untouched —
                # privacy="none" draws what an engine without it draws
                k_priv = jax.random.fold_in(kt, privacy_lib.PRIVACY_FOLD)
            if cfg.datagen is not None:
                # per-round data key, derived only on the datagen path so
                # pre-stacked runs keep their exact randomness stream
                kd = jax.random.fold_in(kt, DATAGEN_FOLD)
                batches = functools.partial(cfg.datagen, kd)
            d_model = fl_server.flat_dim(state.params)
            ls, up = lk.uplink(ls, kt, kf, kc, t, d_model, cparams)

            with jax.named_scope("fl.schedule"):
                avail = ls.avail
                if faults_on:
                    # Gilbert-Elliott churn: offline devices are invisible
                    # to the policy (score-masked view) and unschedulable
                    avail = faults_lib.churn_step(fparams, kt, avail)

                rstate = scheduling.RoundState(
                    t=t, key=kp, snr_lin=up.snr_lin, avg_snr=ls.avg_snr,
                    rates=up.rates, comm_lat=up.comm_lat,
                    comp_lat=up.comp_lat, ages=ls.ages,
                    update_norms=ls.norms)
                rstate_pol = (scheduling.masked_round_state(rstate, avail)
                              if faults_on else rstate)
                if policy_fn is not None:
                    mask = policy_fn(pcfg, rstate_pol)
                else:
                    mask = mixture_fn(pcfg, rstate_pol, pol_w)
                if faults_on:
                    # index-based policies (random/round_robin) ignore
                    # scores, so offline devices must be intersected out
                    # explicitly
                    mask = mask & avail
                # staleness snapshot *before* this round's resets: a client
                # aggregated now contributes an update stale by the rounds
                # it sat out (fault mode tracks true per-client staleness;
                # the faults-off proxy is the pre-update scheduling age)
                stal_pre = ls.stal if faults_on else ls.ages
                ls = ls._replace(
                    avail=avail,
                    ages=scheduling.update_ages_jax(ls.ages, mask))

            fate, part = lk.retransmit(kt, up, mask)

            # staleness-aware algorithms (fedbuff) down-weight old updates;
            # everyone else gets None so the baseline trace is unchanged
            with jax.named_scope("fl.aggregate"):
                sw = (faults_lib.staleness_weights(aparams, stal_pre)
                      if algo.uses_staleness else None)
            comp_kw = (dict(compress_fn=compress_fn, cparams=cparams, key=kz)
                       if comp_active else {})
            fault_kw = (dict(gate_ef=True, guard_empty=True)
                        if faults_on else {})
            priv_kw = (dict(pparams=pparams, privacy_key=k_priv)
                       if priv_on else {})
            state, metrics = round_fn(
                state, batches, aparams=aparams, participation=part,
                staleness_weights=sw, **comp_kw, **fault_kw, **priv_kw)
            ubits = lk.bill(up, mask, fate,
                            metrics["uplink_bits"] if comp_active else None)

            with jax.named_scope("fl.channel"):
                # the server broadcast of the global model opens the round;
                # with double EF it is the compressed server message
                if comp_active and cfg.double_ef:
                    dl_bits = up.payload_scale * compression.uplink_bits_jax(
                        cfg.compression, cparams, d_model)
                else:
                    dl_bits = jnp.float32(cfg.model_bits)
            ls, out = lk.close(ls, kt, up, mask, part, fate, ubits, dl_bits,
                               z_eff)
            with jax.named_scope("fl.log"):
                loss = metrics["loss"]
                if has_eval:
                    loss = loss_fn(state.params, eval_batch)[0]
            ls, out = lk.finish(ls, kn, out, loss)
            return (state, ls), out
        return step

    def site(k_pos, chan):
        return wireless.sample_positions_jax(k_pos, chan, n)

    def _scan(key, chan, cparams, aparams, extra, fparams, pparams, pol_w,
              init_params, batches_all, eval_batch):
        k_pos, k_rounds = jax.random.split(key)
        with jax.named_scope("fl.channel"):
            dist = site(k_pos, chan)
        step = make_step(chan, cparams, aparams, extra, fparams, pparams,
                         pol_w, dist, k_rounds, eval_batch)
        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
        with jax.named_scope("fl.client_state"):
            carry0 = init_carry(init_params)
        # under the sharded sweep the carry varies per variant, like key
        (state, _), outs = lax.scan(
            step, compat.vary_like(carry0, key), (ts, batches_all))
        return state.params, outs

    return _Round(cfg, init_carry, make_step, _scan, site,
                  lambda carry, dist: carry[0].params,
                  mix=policy_axis is not None)


def _engine_key(cfg: SimConfig, wcfg: wireless.WirelessConfig, loss_fn,
                has_eval: bool, tag: str,
                policy_axis: Optional[Tuple[str, ...]] = None,
                hcfg: Optional[HFLConfig] = None) -> Tuple:
    # continuous channel / compression / algorithm params are traced
    # (ChannelParams / CompressionParams / AlgoParams); everything the trace
    # specializes on must appear here. Compression and the algorithm are
    # keyed by their static *names*, so two equal configs share one compiled
    # engine regardless of hyperparameter values. With a policy mixture the
    # *set of enabled names* replaces the single policy name in the key.
    # An HFLConfig enters as its static_key(): the traced backhaul_rate_bps
    # is zeroed out, so a backhaul-rate grid shares one compiled engine.
    return (tag,
            ("mix",) + tuple(policy_axis) if policy_axis is not None
            else cfg.policy,
            cfg.rounds, cfg.n_devices, cfg.n_scheduled,
            cfg.model_bits, cfg.comp_latency_s, cfg.deadline_s,
            cfg.age_alpha, cfg.algorithm, cfg.compression, cfg.double_ef,
            cfg.chunk_size, cfg.ef_mode, cfg.ef_slots, cfg.state_dtype,
            cfg.datagen, cfg.faults is not None, cfg.max_retries,
            cfg.privacy,
            wcfg.n_subchannels, wcfg.bandwidth_hz, loss_fn, has_eval,
            hcfg.static_key() if hcfg is not None else None)


_ENGINE_CACHE: Dict[Tuple, Callable] = {}
_ENGINE_CACHE_MAX = 64  # engines keyed partly on loss_fn identity; bound the
#                         retained compiled programs (FIFO eviction)


def _cached(cache: Dict[Tuple, Callable], key: Tuple,
            make: Callable[[], Callable]) -> Callable:
    """Bounded-FIFO memoization for compiled engines/steps."""
    fn = cache.get(key)
    if fn is None:
        while len(cache) >= _ENGINE_CACHE_MAX:
            cache.pop(next(iter(cache)))
        fn = cache[key] = make()
    return fn


def _mesh_key(mesh) -> Tuple:
    if mesh is None:
        return ()
    return (tuple(int(d.id) for d in np.asarray(mesh.devices).ravel()),
            tuple(mesh.axis_names))


def _shard_variants(vengine: Callable, mesh, n_var: int) -> Callable:
    """Shard a vmapped engine's flattened variant axis over the 1-D mesh.

    The ``n_var`` per-variant args split along the mesh axis; the three
    shared args (initial params, batches, eval batch) replicate. Callers pad
    the variant count to a multiple of the mesh size first
    (:func:`_pad_variants`) and slice the outputs back."""
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    return jax.shard_map(vengine, mesh=mesh,
                         in_specs=(P(axis),) * n_var + (P(), P(), P()),
                         out_specs=(P(axis), P(axis)))


def _make_round(cfg: SimConfig, wcfg: wireless.WirelessConfig, loss_fn,
                has_eval: bool, hcfg: Optional[HFLConfig] = None,
                policy_axis: Optional[Tuple[str, ...]] = None) -> _Round:
    if hcfg is None:
        return _make_sim_fns(cfg, wcfg, loss_fn, has_eval, policy_axis)
    return _make_hfl_fns(cfg, hcfg, wcfg, loss_fn, has_eval)


def _get_engine(cfg: SimConfig, wcfg: wireless.WirelessConfig, loss_fn,
                has_eval: bool, *, hcfg: Optional[HFLConfig] = None,
                vmapped: bool = False,
                policy_axis: Optional[Tuple[str, ...]] = None,
                mesh=None) -> Callable:
    """The cached compiled engine of the flat round, or of the hierarchical
    one where ``hcfg`` is given."""
    def make():
        rnd = _make_round(cfg, wcfg, loss_fn, has_eval, hcfg, policy_axis)
        # the per-variant (traced, vmappable) leading args
        n_var = (4 + rnd.n_extra + (cfg.faults is not None)
                 + (cfg.privacy != "none") + rnd.mix)
        if vmapped:
            vengine = jax.vmap(rnd.engine, in_axes=(0,) * n_var + (None,) * 3)
            if mesh is not None:
                vengine = _shard_variants(vengine, mesh, n_var)
            # broadcast init_params can't alias the per-variant outputs, so
            # there is nothing useful to donate on the sweep path.
            return jax.jit(vengine)
        if hcfg is not None:
            # the broadcast to (L, ...) cluster models copies the initial
            # params anyway, so there is no aliasable output buffer
            return jax.jit(rnd.engine)
        # init_params aliases the returned final params exactly; the
        # wrappers below pass a fresh copy, so donating it is safe and
        # lets XLA run the whole scan in-place on the parameter buffers.
        return jax.jit(rnd.engine, donate_argnums=(n_var,))

    return _cached(_ENGINE_CACHE,
                   _engine_key(cfg, wcfg, loss_fn, has_eval,
                               "sweep" if vmapped else "single",
                               policy_axis, hcfg) + _mesh_key(mesh), make)


def _get_host_step(cfg: SimConfig, wcfg: wireless.WirelessConfig, loss_fn,
                   has_eval: bool,
                   hcfg: Optional[HFLConfig] = None) -> Callable:
    """Jitted per-round step with the run-specific values (channel params,
    placement, round key, eval batch) as *arguments*, so the compiled step
    is shared across runs of the same static config (no per-call retrace)."""
    return _cached(
        _ENGINE_CACHE,
        _engine_key(cfg, wcfg, loss_fn, has_eval, "host-step", hcfg=hcfg),
        lambda: jax.jit(_make_round(cfg, wcfg, loss_fn, has_eval,
                                    hcfg).host_step))


def _run_args(cfg: SimConfig, hcfg: Optional[HFLConfig]) -> Tuple:
    """A single run's traced args after ``aparams``, in the engines'
    order: HFL's backhaul rate, then the enabled optional axes."""
    return (((jnp.float32(hcfg.backhaul_rate_bps),) if hcfg is not None
             else ())
            + ((cfg.faults,) if cfg.faults is not None else ())
            + ((_resolve_pparams(cfg),) if cfg.privacy != "none" else ()))


def _sim_logs(outs: link.RoundOut) -> SimLogs:
    return SimLogs(**jax.device_get(outs)._asdict())


def _run_scan(cfg: SimConfig, wcfg: wireless.WirelessConfig,
              hcfg: Optional[HFLConfig], chan, loss_fn, init_params: PyTree,
              batches, eval_batch) -> Tuple[PyTree, SimLogs]:
    with TraceAnnotation("fl.engine_lookup"):
        engine = _get_engine(cfg, wcfg, loss_fn, eval_batch is not None,
                             hcfg=hcfg)
    with TraceAnnotation("fl.prepare"):
        key = jax.random.PRNGKey(cfg.seed)
        if chan is None:
            chan = wireless.channel_params(wcfg)
        cparams = _resolve_cparams(cfg, init_params)
        aparams = _resolve_aparams(cfg)
        # donated to the flat engine
        init_copy = jax.tree.map(jnp.array, init_params)
        args = _run_args(cfg, hcfg)
    with TraceAnnotation("fl.dispatch"):
        params, outs = engine(key, chan, cparams, aparams, *args,
                              init_copy, batches, eval_batch)
    with TraceAnnotation("fl.fetch_logs"):
        return params, _sim_logs(outs)


def run_simulation_scan(cfg: SimConfig, loss_fn, init_params: PyTree,
                        batches: Optional[PyTree] = None, *,
                        eval_batch: Optional[Dict[str, jnp.ndarray]] = None,
                        wcfg: Optional[wireless.WirelessConfig] = None
                        ) -> Tuple[PyTree, SimLogs]:
    """Run ``cfg.rounds`` rounds as a single compiled ``lax.scan`` call.

    ``batches``: pytree with leading ``(rounds, n_devices, H, ...)`` leaves
    (see :func:`stack_batches`), or ``None`` when ``cfg.datagen`` generates
    batches on device (O(chunk) data residency instead of O(rounds * N)).
    Returns (final params, stacked logs).
    """
    if batches is None and cfg.datagen is None:
        raise ValueError("run_simulation_scan needs batches= (stack_batches) "
                         "or a SimConfig.datagen")
    wcfg = wcfg or wireless.WirelessConfig(n_devices=cfg.n_devices)
    return _run_scan(cfg, wcfg, None, None, loss_fn, init_params, batches,
                     eval_batch)


def run_simulation(cfg: SimConfig, loss_fn, init_params: PyTree,
                   sample_client_batches: Callable[[int, int], Dict[str, jnp.ndarray]],
                   eval_fn: Optional[Callable[[PyTree], float]] = None,
                   wcfg: Optional[wireless.WirelessConfig] = None,
                   engine: Optional[str] = None) -> List[RoundLog]:
    """Legacy entry point: returns per-round ``RoundLog``s.

    ``engine=None`` (default) auto-selects: the compiled scan engine when
    possible, else the host loop. ``engine="scan"`` / ``"host"`` force a
    path (forcing "scan" with an opaque ``eval_fn`` raises). Note the scan
    engine pre-materializes all rounds' batches on device (O(rounds)
    memory); use ``engine="host"`` for memory-constrained very long runs —
    it samples lazily round-by-round like the seed loop.

    Eval contract: attaching an ``eval_batch`` attribute to ``eval_fn``
    opts into in-program evaluation — the logged loss becomes
    ``loss_fn(params, eval_batch)`` and the callable itself is **not**
    invoked, so only attach it when ``eval_fn(p)`` computes exactly that
    (as ``benchmarks.common.make_lm_problem`` does). An opaque host-side
    ``eval_fn`` (no attribute) is honored as-is and runs on the host loop.
    """
    wcfg = wcfg or wireless.WirelessConfig(n_devices=cfg.n_devices)
    return _run(cfg, wcfg, None, wireless.channel_params(wcfg), loss_fn,
                init_params, sample_client_batches, eval_fn, engine)


def _run(cfg: SimConfig, wcfg: wireless.WirelessConfig,
         hcfg: Optional[HFLConfig], chan, loss_fn, init_params: PyTree,
         sample_client_batches, eval_fn, engine) -> List[RoundLog]:
    """The ``engine=`` / eval dispatch of :func:`run_simulation` and
    :func:`run_hfl`: the scanned engine, or the host loop over the same
    round step for ``engine="host"`` or an opaque ``eval_fn``."""
    if engine not in (None, "scan", "host"):
        raise ValueError(f"unknown engine {engine!r}; use 'scan' or 'host'")
    if cfg.rounds == 0:
        return []
    eval_batch = getattr(eval_fn, "eval_batch", None) if eval_fn else None
    opaque_eval = eval_fn is not None and eval_batch is None
    if engine == "scan" and opaque_eval:
        raise ValueError(
            "engine='scan' needs an in-program eval: attach eval_fn."
            "eval_batch (logged loss becomes loss_fn(params, eval_batch)) "
            "or drop engine= to let the host loop serve the opaque eval_fn")
    if engine == "host" or opaque_eval:
        return _run_host(cfg, wcfg, hcfg, chan, loss_fn, init_params,
                         sample_client_batches, eval_fn, eval_batch)
    batches = (None if cfg.datagen is not None else
               stack_batches(sample_client_batches, cfg.rounds,
                             cfg.n_devices))
    _, logs = _run_scan(cfg, wcfg, hcfg, chan, loss_fn, init_params, batches,
                        eval_batch)
    return logs.to_round_logs()


def _run_host(cfg: SimConfig, wcfg: wireless.WirelessConfig,
              hcfg: Optional[HFLConfig], chan, loss_fn, init_params: PyTree,
              sample_client_batches, eval_fn, eval_batch) -> List[RoundLog]:
    """Round-by-round dispatch loop over the *same* step function the scan
    engine uses (parity baseline + host-side eval_fn support)."""
    has_eval = eval_batch is not None
    rnd = _make_round(cfg, wcfg, loss_fn, has_eval, hcfg)
    step = _get_host_step(cfg, wcfg, loss_fn, has_eval, hcfg)
    k_site, k_rounds = jax.random.split(jax.random.PRNGKey(cfg.seed))
    site = rnd.site(k_site, chan)
    args = (chan, _resolve_cparams(cfg, init_params), _resolve_aparams(cfg),
            *_run_args(cfg, hcfg), site, k_rounds, eval_batch)
    carry = rnd.init_carry(init_params)
    outs = []
    for t in range(cfg.rounds):
        bt = (None if cfg.datagen is not None
              else sample_client_batches(t, cfg.n_devices))
        carry, out = step(*args, carry, (jnp.int32(t), bt))
        if eval_fn is not None and not has_eval:
            out = out._replace(loss=eval_fn(rnd.params_of(carry, site)))
        outs.append(jax.device_get(out))
    return _sim_logs(jax.tree.map(lambda *xs: np.stack(xs),
                                  *outs)).to_round_logs()


# ---------------------------------------------------------------------------
# Fleet-scale sweeps: one vmapped call over seed x channel x compression x
# algorithm x policy variants, optionally sharded over a device mesh
# ---------------------------------------------------------------------------
# Policies whose decision consumes the *static* per-subchannel bandwidth
# (PolicyConfig.sub_bw = bandwidth_hz / n_subchannels compiles in) or whose
# latency/deadline math otherwise specializes on the cell's static
# bandwidth: a bandwidth grid can't vary under them within one trace.
_BW_STATIC_POLICIES = ("age", "deadline", "bn2", "bn2_c")


def _validate_sweep_wcfgs(wcfgs: Sequence[wireless.WirelessConfig],
                          policies: Sequence[str]) -> None:
    """Validate the full wcfg grid once: static fields must match across
    every entry (not just against the first), and latency-sensitive
    policies additionally pin ``bandwidth_hz`` static."""
    ref = wcfgs[0]
    bw_pols = sorted(set(policies) & set(_BW_STATIC_POLICIES))
    for i, w in enumerate(wcfgs):
        if (w.n_devices, w.n_subchannels) != (ref.n_devices,
                                              ref.n_subchannels):
            raise ValueError(
                f"sweep wcfgs must share static fields (n_devices, "
                f"n_subchannels): wcfgs[{i}] has "
                f"({w.n_devices}, {w.n_subchannels}), wcfgs[0] has "
                f"({ref.n_devices}, {ref.n_subchannels})")
        if bw_pols and w.bandwidth_hz != ref.bandwidth_hz:
            raise ValueError(
                f"sweep wcfgs must share static bandwidth_hz for the "
                f"latency-sensitive policies {bw_pols} (their sub-band "
                f"bandwidth / deadline pricing compiles in statically): "
                f"wcfgs[{i}].bandwidth_hz={w.bandwidth_hz} != "
                f"wcfgs[0].bandwidth_hz={ref.bandwidth_hz}")


def _resolve_sweep_mesh(devices, mesh):
    """Resolve the ``devices=``/``mesh=`` knob to a 1-D mesh or ``None``
    (single-device vmap). ``devices`` accepts ``"auto"`` (all local
    devices), an int (first that many), or an explicit device sequence;
    anything resolving to <= 1 device degrades gracefully to ``None``."""
    if devices is not None and mesh is not None:
        raise ValueError("pass devices= or mesh=, not both")
    if mesh is not None:
        if len(mesh.axis_names) != 1:
            raise ValueError(f"run_sweep shards the flattened variant axis "
                             f"over a 1-D mesh; got axes {mesh.axis_names}")
        return mesh
    if devices is None:
        return None
    if devices == "auto":
        devs = jax.devices()
    elif isinstance(devices, int):
        avail = jax.devices()
        if devices > len(avail):
            raise ValueError(f"devices={devices} but only {len(avail)} "
                             "local devices are available")
        devs = avail[:devices]
    else:
        devs = list(devices)
    if len(devs) <= 1:
        return None
    return compat.make_mesh(devs, "variants")


def _tile_variants(tree: PyTree, reps: int) -> PyTree:
    """Repeat the leading variant axis ``reps`` times (policy-major order:
    the whole base grid for policy 0, then policy 1, ...)."""
    return jax.tree.map(
        lambda x: jnp.tile(x, (reps,) + (1,) * (x.ndim - 1)), tree)


def _pad_variants(tree: PyTree, n_pad: int) -> PyTree:
    """Pad the leading variant axis with ``n_pad`` copies of variant 0 (the
    ragged-grid filler for mesh sharding; outputs are sliced back)."""
    if n_pad == 0:
        return tree
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (n_pad,) + x.shape[1:])], axis=0),
        tree)


def _dispatch_variants(engine, var_args: Tuple, shared_args: Tuple,
                       mesh) -> Tuple:
    """One compiled sweep dispatch: pads the variant axis up to a multiple
    of the mesh size (ragged grids), calls the engine, slices the padding
    back off the outputs. Returns the stacked per-round ``RoundOut``."""
    v = jax.tree.leaves(var_args[0])[0].shape[0]
    if mesh is not None:
        n_pad = (-v) % int(np.asarray(mesh.devices).size)
        var_args = tuple(_pad_variants(a, n_pad) for a in var_args)
    _, outs = engine(*var_args, *shared_args)
    return jax.tree.map(lambda o: o[:v], outs)


def run_sweep(cfg: SimConfig, loss_fn, init_params: PyTree, batches: PyTree, *,
              seeds: Sequence[int],
              wcfgs: Optional[Sequence[wireless.WirelessConfig]] = None,
              policies: Optional[Sequence[str]] = None,
              compressions: Optional[Sequence[str]] = None,
              cparams_grid: Optional[Sequence[CompressionParams]] = None,
              algorithms: Optional[Sequence[str]] = None,
              aparams_grid: Optional[Sequence[AlgoParams]] = None,
              fparams_grid: Optional[Sequence[FaultParams]] = None,
              privacies: Optional[Sequence[str]] = None,
              pparams_grid: Optional[Sequence[PrivacyParams]] = None,
              eval_batch: Optional[Dict[str, jnp.ndarray]] = None,
              hcfg: Optional[HFLConfig] = None,
              hcfgs: Optional[Sequence[HFLConfig]] = None,
              policy_mode: str = "mixture",
              devices=None, mesh=None
              ) -> Dict[Any, SimLogs]:
    """Sweep policies x compressor names x algorithm names x seeds x
    channels x compression levels x algorithm hyperparameters.

    The scheduling policy is a *traced* one-hot mixture axis by default
    (``policy_mode="mixture"``): the whole seed x channel x compression x
    algorithm x **policy** grid flattens into a single variant axis and
    dispatches as **one** vmapped+compiled call per (compressor-name,
    algorithm-name) tuple — a full 10-policy study costs one trace.
    ``policy_mode="loop"`` restores the legacy one-call-per-policy
    baseline (also used automatically for single-policy sweeps and the
    hierarchical engine, whose per-cluster scheduling branches on the
    policy name). Either way the *results are bitwise identical*: the
    mixture selects each variant's mask by an exact one-hot einsum.

    ``devices=`` / ``mesh=`` shards the flattened variant axis over a 1-D
    device mesh with ``shard_map`` (``devices="auto"`` = all local devices,
    an int = first that many, or pass an explicit 1-axis ``mesh``). Ragged
    grids pad up to a multiple of the mesh size with copies of variant 0
    and the padding is sliced back off, so results are bitwise identical
    to the single-device vmap path; <= 1 device degrades to plain vmap.

    Compressor and algorithm *names* iterate in Python (static engine
    arguments). Returns ``{policy: SimLogs}``, with the key growing to
    ``(policy, compression)`` / ``(policy, algorithm)`` /
    ``(policy, compression, algorithm)`` when the ``compressions`` /
    ``algorithms`` axes are given. Arrays have shape
    ``(len(seeds)*len(wcfgs)*len(cparams_grid)*len(aparams_grid)
    [*len(fparams_grid)], rounds, ...)``, variants ordered
    ``itertools.product(seeds, wcfgs, cparams_grid, aparams_grid[,
    fparams_grid])``.

    ``fparams_grid`` makes the fault model a sweep axis: every entry is a
    traced :class:`~repro.core.faults.FaultParams`, so a dropout/churn/
    straggler grid rides the same compiled engine (zero extra traces on a
    warm cache). Omitting it while ``cfg.faults`` is set sweeps the single
    configured fault point; omitting both keeps the fault-free engine.

    ``privacies`` iterates privacy mechanism *names* in Python (another
    static axis, growing the result key like ``compressions``/
    ``algorithms``); ``pparams_grid`` makes the continuous privacy knobs a
    traced sweep axis — a clip x sigma grid of
    :class:`~repro.core.privacy.PrivacyParams` dispatches as **one**
    compiled call per static (policy, compression, algorithm, privacy)
    name tuple. When the name set mixes ``"none"`` with real mechanisms
    the pparams axis stays in the grid for every name (uniform variant
    shapes) but is only passed to privacy-enabled engines.

    All ``wcfgs`` must share the static fields (``n_devices``,
    ``n_subchannels``; additionally ``bandwidth_hz`` when sweeping a
    latency-sensitive policy — see ``_BW_STATIC_POLICIES``); the remaining
    continuous fields (power, radius, path loss, noise...) vary per
    variant through ``ChannelParams``, compression levels through
    ``CompressionParams``, and algorithm hyperparameters through
    ``AlgoParams``.

    ``hcfg`` switches the sweep onto the hierarchical engine: every variant
    runs the wireless-aware HFL scan (per-cluster scheduling, compressed
    intra-cluster + backhaul pricing; each variant's seed re-deploys the
    device/SBS geometry), still one compiled call per (policy, compression,
    algorithm) name tuple. ``hcfgs=`` makes the backhaul rate a sweep axis:
    every entry must share the static fields (``HFLConfig.static_key()``)
    and the grid grows a trailing ``len(hcfgs)`` product axis whose
    ``backhaul_rate_bps`` is traced — one engine for the whole rate grid.
    """
    wcfgs = list(wcfgs) if wcfgs else [
        wireless.WirelessConfig(n_devices=cfg.n_devices)]
    policies = list(policies) if policies else [cfg.policy]
    comp_names = list(compressions) if compressions is not None else None
    algo_names = list(algorithms) if algorithms is not None else None
    cparams_list = (list(cparams_grid) if cparams_grid
                    else [_resolve_cparams(cfg, init_params)])
    aparams_list = (list(aparams_grid) if aparams_grid
                    else [_resolve_aparams(cfg)])
    if policy_mode not in ("mixture", "loop"):
        raise ValueError(f"unknown policy_mode {policy_mode!r}; "
                         "use 'mixture' or 'loop'")
    _validate_sweep_wcfgs(wcfgs, policies)
    if hcfg is not None and hcfgs is not None:
        raise ValueError("pass hcfg= or hcfgs=, not both")
    hlist = (list(hcfgs) if hcfgs is not None
             else ([hcfg] if hcfg is not None else None))
    if hlist is not None:
        if not hlist:
            raise ValueError("hcfgs= needs at least one HFLConfig")
        ref = hlist[0].static_key()
        for i, h in enumerate(hlist):
            if h.static_key() != ref:
                raise ValueError(
                    f"sweep hcfgs must share static fields (everything but "
                    f"the traced backhaul_rate_bps): hcfgs[{i}] differs "
                    "from hcfgs[0]")
    mesh = _resolve_sweep_mesh(devices, mesh)
    fparams_list = (list(fparams_grid) if fparams_grid is not None
                    else ([cfg.faults] if cfg.faults is not None else None))
    faults_on = fparams_list is not None
    if faults_on and not fparams_list:
        raise ValueError("fparams_grid= needs at least one FaultParams")
    priv_iter = list(privacies) if privacies is not None else [cfg.privacy]
    if not priv_iter:
        raise ValueError("privacies= needs at least one mechanism name")
    any_priv = any(p != "none" for p in priv_iter)
    # the pparams axis stays in the grid even when "none" rides along
    # (uniform variant shapes across the name axis); the stacked params
    # are simply not passed to privacy-free engines
    pparams_list = (list(pparams_grid) if pparams_grid is not None
                    else ([_resolve_pparams(cfg)] if any_priv else None))
    if pparams_list is not None and not pparams_list:
        raise ValueError("pparams_grid= needs at least one PrivacyParams")

    grid = list(itertools.product(
        seeds, wcfgs, cparams_list, aparams_list,
        fparams_list if faults_on else [None],
        pparams_list if pparams_list is not None else [None],
        hlist if hlist is not None else [None]))
    if not grid:
        raise ValueError("run_sweep needs at least one "
                         "(seed, wcfg, cparams, aparams) variant")
    keys = jnp.stack([jax.random.PRNGKey(g[0]) for g in grid])
    chans = wireless.stack_channel_params([g[1] for g in grid])
    cps = compression.stack_compression_params([g[2] for g in grid])
    aps = stack_algo_params([g[3] for g in grid])
    fps = (stack_fault_params([g[4] for g in grid]) if faults_on else None)
    pps = (stack_privacy_params([g[5] for g in grid])
           if pparams_list is not None else None)
    bh = (jnp.asarray([g[6].backhaul_rate_bps for g in grid], jnp.float32)
          if hlist is not None else None)
    has_eval = eval_batch is not None
    shared = (init_params, batches, eval_batch)
    comp_iter = comp_names if comp_names is not None else [cfg.compression]
    algo_iter = algo_names if algo_names is not None else [cfg.algorithm]

    def result_key(pol, comp, alg, priv):
        parts = ((pol,)
                 + ((comp,) if comp_names is not None else ())
                 + ((alg,) if algo_names is not None else ())
                 + ((priv,) if privacies is not None else ()))
        return parts[0] if len(parts) == 1 else parts

    def cfg_variant(pol, comp, alg, priv) -> SimConfig:
        return dataclasses.replace(
            cfg, policy=pol, compression=comp, algorithm=alg,
            faults=fparams_list[0] if faults_on else cfg.faults,
            privacy=priv,
            privacy_params=(pparams_list[0] if priv != "none"
                            and pparams_list is not None
                            else cfg.privacy_params))

    results: Dict[Any, SimLogs] = {}
    # mixture: one dispatch for the whole policy set, the base grid tiled
    # policy-major and each block's policy selected by a traced one-hot;
    # loop: one dispatch per policy
    use_mixture = (hlist is None and policy_mode == "mixture"
                   and len(policies) > 1)
    n_base = len(grid)
    for group in ([tuple(policies)] if use_mixture
                  else [(p,) for p in policies]):
        n_pol = len(group)
        policy_axis = group if n_pol > 1 else None
        base_args = ((keys, chans, cps, aps)
                     + ((bh,) if hlist is not None else ())
                     + ((fps,) if faults_on else ()))
        pargs = (pps,)
        if policy_axis is not None:
            base_args = tuple(_tile_variants(a, n_pol) for a in base_args)
            pargs = (_tile_variants(pps, n_pol),) if pps is not None else ()
        mix_args = ((jnp.repeat(jnp.eye(n_pol, dtype=jnp.float32), n_base,
                                axis=0),) if policy_axis is not None else ())
        for comp, alg, priv in itertools.product(comp_iter, algo_iter,
                                                 priv_iter):
            cfg_v = cfg_variant(group[0], comp, alg, priv)
            with TraceAnnotation("fl.engine_lookup"):
                engine = _get_engine(
                    cfg_v, wcfgs[0], loss_fn, has_eval,
                    hcfg=hlist[0] if hlist is not None else None,
                    vmapped=True, policy_axis=policy_axis, mesh=mesh)
            var_args = (base_args + (pargs if priv != "none" else ())
                        + mix_args)
            with TraceAnnotation("fl.dispatch"):
                outs = _dispatch_variants(engine, var_args, shared, mesh)
            with TraceAnnotation("fl.fetch_logs"):
                arrs = jax.device_get(outs)
                for p_i, pol in enumerate(group):
                    results[result_key(pol, comp, alg, priv)] = _sim_logs(
                        jax.tree.map(lambda a: a[p_i * n_base:
                                                 (p_i + 1) * n_base], arrs))
    return results


# ---------------------------------------------------------------------------
# Hierarchical FL simulation (Alg. 9) — wireless-aware scanned engine
#
# The cluster -> cloud topology runs through the *same* channel/compression/
# policy machinery as flat FL: every device talks to its nearest SBS over the
# fading channel layer (per-cluster ChannelParams -> snr_jax /
# shannon_rate_jax / comm_latency_jax), each cluster runs the registry
# scheduling policy over its own members, compressed intra-cluster payloads
# (plus EF / SCAFFOLD ctrl state in the scan carry) price the device->SBS
# uplink, and the periodic SBS->MBS sync ships a separately-compressed and
# separately-priced backhaul payload over a fixed-rate fronthaul link.
# ---------------------------------------------------------------------------
_HFL_ALGOS = ("fedavg", "fedavg_m", "fedprox", "scaffold")


def _check_hfl_config(cfg: SimConfig) -> None:
    algo = algo_registry.get_algorithm(cfg.algorithm)
    if algo.name not in _HFL_ALGOS:
        raise ValueError(
            f"run_hfl supports client-side algorithms "
            f"({'/'.join(_HFL_ALGOS)}), not {algo.name!r}: Alg. 9 aggregates "
            "raw cluster models, so server-side optimizer state (slowmo/"
            "fedadam/fedyogi) has no SBS or MBS slot to live in. SCAFFOLD "
            "is supported with cluster-level server control variates.")
    if cfg.double_ef:
        raise ValueError(
            "run_hfl does not support double_ef: HFL has no single PS "
            "downlink to carry server-side EF state — each SBS broadcasts "
            "its raw cluster model. Drop double_ef (uplink EF still "
            "applies) or use the flat engine.")
    if (cfg.chunk_size is not None or cfg.datagen is not None
            or cfg.ef_mode != "dense" or cfg.state_dtype != "float32"):
        raise ValueError(
            "run_hfl does not support the fleet-scale knobs (chunk_size/"
            "datagen/ef_mode='sparse'/state_dtype='bfloat16'); they live on "
            "the flat engine, whose N is the fleet-scale axis")


def _make_hfl_fns(cfg: SimConfig, hcfg: HFLConfig,
                  wcfg: wireless.WirelessConfig, loss_fn,
                  has_eval: bool) -> _Round:
    """The wireless-aware HFL round, in the same frame as
    :func:`_make_sim_fns` (the host loop jits the same step the scanned
    engine scans, and ``run_sweep`` vmaps the engine like the flat one).

    One round (Alg. 9 + §III wireless):

    1. every device draws fading against its *own* SBS (distance from the
       jnp geometry, per-cluster ``ChannelParams``) and the compressed
       payload prices its device->SBS uplink via ``comm_latency_jax``;
    2. each cluster schedules its members with the registry policy, with
       ``cfg.n_scheduled`` as the *per-cluster* budget: score-based
       policies see an intra-cluster view of the round state (out-of-
       cluster devices carry -inf-grade scores, so top-k picks
       min(k, |C_l|) members); the index-based ``random``/``round_robin``
       use cluster-aware twins (random member k-subset / rotation over
       member ranks) because a global permutation doesn't factor through
       the masked score view;
    3. scheduled clients' EF-compressed deltas average into their cluster
       model (``aparams.server_lr`` scaled, exactly the flat server_update);
    4. every ``hcfg.inter_cluster_period`` rounds each SBS uplinks its
       compressed cluster-model delta over the ``backhaul_rate_bps``
       fronthaul; the MBS averages (population-weighted) and broadcasts.

    The synchronous round time is the slowest scheduled device's
    ``comm + comp`` (clusters operate in parallel), plus the backhaul time
    on sync rounds. Logged ``uplink_bits`` holds intra-cluster plus
    backhaul bits-on-the-wire.
    """
    n = cfg.n_devices
    n_clusters = hcfg.n_clusters
    period = hcfg.inter_cluster_period
    # cfg.n_scheduled is the per-cluster budget: one int shared by every
    # cluster, or a tuple giving each cluster its own (static) budget
    per_cluster_k = isinstance(cfg.n_scheduled, tuple)
    if per_cluster_k and len(cfg.n_scheduled) != n_clusters:
        raise ValueError(
            f"per-cluster n_scheduled needs one budget per cluster "
            f"({n_clusters}), got {len(cfg.n_scheduled)}")
    ks = (tuple(cfg.n_scheduled) if per_cluster_k
          else (cfg.n_scheduled,) * n_clusters)
    pcfg = _policy_cfg(
        dataclasses.replace(cfg, n_scheduled=ks[0]) if per_cluster_k
        else cfg, wcfg)
    policy_fn = scheduling.get_policy(cfg.policy)
    _check_hfl_config(cfg)
    algo = algo_registry.get_algorithm(cfg.algorithm)
    comp_active = cfg.compression != "none"
    compress_fn = (compression.get_compressor(cfg.compression)
                   if comp_active else None)
    faults_on = cfg.faults is not None
    # static privacy switch, mirroring _make_sim_fns: the mechanism *name*
    # specializes the trace; clip/sigma/field_bits ride traced PrivacyParams.
    # Masks cancel *within each cluster*: the SBS is the honest-but-curious
    # aggregator, so pairwise keys (and their wire overhead) are scoped to
    # cluster peers, and the per-cluster modular sum unmasks exactly.
    priv_on = cfg.privacy != "none"
    priv = privacy_lib.get_privacy(cfg.privacy) if priv_on else None
    dp_on = priv_on and priv.uses_dp
    masks_on = priv_on and priv.uses_masks
    field_on = priv_on and priv.uses_field

    def site(k_geo, chan):
        return hfl_geometry_jax(k_geo, hcfg, n)

    def params_of(carry, geo):
        return inter_cluster_average(carry[0], geo[3])

    def init_carry(init_params):
        d = fl_server.flat_dim(init_params)
        cm = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n_clusters,) + p.shape),
            init_params)
        gm = jax.tree.map(jnp.asarray, init_params)
        ef = jnp.zeros((n, d), jnp.float32) if comp_active else None
        ctrl = jnp.zeros((n, d), jnp.float32) if algo.uses_ctrl else None
        cc = (jnp.zeros((n_clusters, d), jnp.float32) if algo.uses_ctrl
              else None)
        return cm, gm, ef, ctrl, cc, link.LinkState.init(n, faults_on, dp_on)

    def make_step(chan: wireless.ChannelParams, cparams: CompressionParams,
                  aparams: AlgoParams, extra, fparams, pparams, pol_w, geo,
                  k_rounds: jax.Array, eval_batch):
        (bh_rate,) = extra
        cluster_ids, dist, member, cluster_sizes = geo
        chan_dev = wireless.gather_channel_params(chan, cluster_ids)
        member_f = member.astype(jnp.float32)                       # (L, N)
        w_cluster = cluster_sizes / jnp.maximum(jnp.sum(cluster_sizes), 1.0)

        # each device shares its own cell's uplink budget, and agrees masks
        # with its *cluster* peers only (the overhead varies with each
        # device's cell population)
        k_dev = (jnp.asarray(ks, jnp.float32)[cluster_ids] if per_cluster_k
                 else cfg.n_scheduled)
        lk = link.Link(
            cfg, algo.uplink_factor, priv, chan_dev, dist,
            lambda snr: wireless.shannon_rate_jax(
                snr, chan_dev.bandwidth_hz / k_dev),
            jnp.maximum(cluster_sizes[cluster_ids] - 1.0, 0.0), fparams,
            pparams)

        def step(carry, xs):
            cm, gm, ef, ctrl, cc, ls = carry
            t, batches = xs
            kt = jax.random.fold_in(k_rounds, t)
            kf, kc, kp, kn, kz = jax.random.split(kt, 5)
            if priv_on:
                # fold-tagged so the five streams above are untouched —
                # privacy="none" draws what an engine without it draws
                k_priv = jax.random.fold_in(kt, privacy_lib.PRIVACY_FOLD)
            d_model = fl_server.flat_dim(gm)
            ls, up = lk.uplink(ls, kt, kf, kc, t, d_model, cparams)

            # --- per-cluster scheduling (registry policy) ----------------
            if faults_on:
                # churned-off devices disappear from their cluster's view
                ls = ls._replace(avail=faults_lib.churn_step(
                    fparams, kt, ls.avail))
                member_eff = member & ls.avail[None, :]
            else:
                member_eff = member
            rstate = scheduling.RoundState(
                t=t, key=kp, snr_lin=up.snr_lin, avg_snr=ls.avg_snr,
                rates=up.rates, comm_lat=up.comm_lat, comp_lat=up.comp_lat,
                ages=ls.ages, update_norms=ls.norms)
            keys_l = jax.random.split(kp, n_clusters)
            k_sched = ks[0]

            if per_cluster_k:
                # heterogeneous budgets: each cluster's k_l is *static*
                # (policies compile the budget in — topk_mask_jax slices
                # [:k]), so the per-cluster masks unroll in a Python loop
                # over the (static) cluster count instead of one vmap
                if cfg.policy == "round_robin":
                    rank_pc = jnp.cumsum(member_f, axis=1) - 1.0    # (L, N)

                def sched_cluster(l, m, key_l):
                    k_l = ks[l]
                    if cfg.policy == "random":
                        score = jnp.where(m, jax.random.uniform(key_l, (n,)),
                                          -jnp.inf)
                        return scheduling.topk_mask_jax(score, k_l) & m
                    if cfg.policy == "round_robin":
                        g_l = jnp.maximum(
                            jnp.floor(cluster_sizes[l] / k_l), 1.0)
                        g = jnp.mod(jnp.float32(t), g_l)
                        r = rank_pc[l]
                        return m & (r >= g * k_l) & (r < (g + 1) * k_l)
                    stl = scheduling.masked_round_state(rstate, m, key_l)
                    pcfg_l = dataclasses.replace(pcfg, n_scheduled=k_l)
                    return policy_fn(pcfg_l, stl) & m

                masks_l = jnp.stack([
                    sched_cluster(l, member_eff[l], keys_l[l])
                    for l in range(n_clusters)])
            elif cfg.policy == "random":
                # cluster-aware twin of the registry policy: a random
                # k-subset of *each cluster's members* (the global
                # permutation's semantics don't factor through the masked
                # per-cluster score view below)
                def sched_one(m, k):
                    score = jnp.where(m, jax.random.uniform(k, (n,)),
                                      -jnp.inf)
                    return scheduling.topk_mask_jax(score, k_sched) & m
            elif cfg.policy == "round_robin":
                # per-cluster rotation over each cluster's member ranks —
                # exactly the flat G = |C_l|/K group cycling, per cluster
                rank = jnp.cumsum(member_f, axis=1) - 1.0          # (L, N)
                n_groups = jnp.maximum(
                    jnp.floor(cluster_sizes / k_sched), 1.0)       # (L,)

                def sched_one(m, k, r, g_l):
                    g = jnp.mod(jnp.float32(t), g_l)
                    return m & (r >= g * k_sched) & (r < (g + 1) * k_sched)
            else:
                def sched_one(m, k):
                    # intra-cluster view: non-members look unschedulable
                    # to every score-based policy (zero SNR/norm, infinite
                    # latency), so top-k picks min(k, |C_l|) members
                    stl = scheduling.masked_round_state(rstate, m, k)
                    return policy_fn(pcfg, stl) & m

            if not per_cluster_k:
                if cfg.policy == "round_robin":
                    masks_l = jax.vmap(sched_one)(member_eff, keys_l, rank,
                                                  n_groups)
                else:
                    masks_l = jax.vmap(sched_one)(member_eff, keys_l)
            mask = jnp.any(masks_l, axis=0)
            ls = ls._replace(ages=scheduling.update_ages_jax(ls.ages, mask))
            fate, part_f = lk.retransmit(kt, up, mask)

            # --- local updates from each device's cluster model ----------
            client_params = broadcast_to_clients(cm, cluster_ids)
            if algo.uses_ctrl:
                ci_tree = algo_registry.unflatten_rows(ctrl, gm)
                cdev_tree = algo_registry.unflatten_rows(cc[cluster_ids], gm)

                def one(p, b, ci, cd):
                    return algo.client_update(loss_fn, aparams, p, b,
                                              (ci, cd))

                deltas, ctrl_deltas, losses = jax.vmap(one)(
                    client_params, batches, ci_tree, cdev_tree)
                ctrl_flat, _ = fl_server.flatten_clients(ctrl_deltas)
            else:
                def one(p, b):
                    return algo.client_update(loss_fn, aparams, p, b, None)

                deltas, _, losses = jax.vmap(one)(client_params, batches)
                ctrl_flat = None

            # --- client-side compression + EF in message space -----------
            flat, _ = fl_server.flatten_clients(deltas)          # (N, D)
            ctrl_wire = ctrl_flat
            if comp_active:
                k_up, k_ctrl, k_bh = jax.random.split(kz, 3)
                flat = flat + ef
                keys_up = jax.random.split(k_up, n)
                wire, bits = jax.vmap(compress_fn, in_axes=(None, 0, 0))(
                    cparams, keys_up, flat)
                if faults_on:
                    # a dropped/undecoded client's residual carries forward
                    # untouched — its payload never reached the SBS
                    ef = jnp.where(fate.survived[:, None], flat - wire, ef)
                else:
                    ef = flat - wire
                flat = wire
                if ctrl_flat is not None:
                    keys_c = jax.random.split(k_ctrl, n)
                    ctrl_wire, cbits = jax.vmap(
                        compress_fn, in_axes=(None, 0, 0))(
                            cparams, keys_c, ctrl_flat)
                    bits = bits + cbits
                if field_on:
                    # the wire carries field elements, not compressor output
                    bits = jnp.broadcast_to(
                        pparams.field_bits * jnp.float32(d_model),
                        bits.shape)
                ubits_intra = lk.bill(up, mask, fate, jnp.sum(bits * part_f))
            else:
                k_bh = kz
                ubits_intra = lk.bill(up, mask, fate)

            # --- SBS aggregation: masked per-cluster delta mean ----------
            # (fault mode aggregates only the *survivors*; a cluster whose
            # every scheduled member failed keeps its model bitwise)
            wgt = member_f * part_f[None, :]                     # (L, N)
            cnt = jnp.sum(wgt, axis=1)                           # (L,)
            if field_on:
                # finite-field secure aggregation per cluster: encode every
                # client row, add pairwise masks scoped to *cluster* peers
                # (closed-form post-dropout algebra over each survivor
                # set), modular-sum per cluster, decode the centered
                # representative. uint32 wraparound is the field reduction.
                surv = part_f > 0.0
                ids_all = jnp.arange(n)
                q = priv.client_transform(pparams, k_priv, ids_all, flat)
                if masks_on:
                    g = privacy_lib.mask_rows(k_priv, ids_all, d_model)
                    gsum_l = jax.ops.segment_sum(
                        jnp.where(surv[:, None], g, jnp.uint32(0)),
                        cluster_ids, num_segments=n_clusters)
                    cnt_u_l = jax.ops.segment_sum(
                        surv.astype(jnp.uint32), cluster_ids,
                        num_segments=n_clusters)
                    q = q + (cnt_u_l[cluster_ids][:, None] * g
                             - gsum_l[cluster_ids])
                qsum_l = jax.ops.segment_sum(
                    jnp.where(surv[:, None], q, jnp.uint32(0)),
                    cluster_ids, num_segments=n_clusters)
                tot = priv.server_transform(pparams, k_priv, qsum_l)
                mean_delta = tot / jnp.maximum(cnt, 1.0)[:, None]
            elif priv_on:
                # central DP at each SBS: clip every client row, then add
                # *independent* Gaussian noise per cluster aggregate (one
                # shared draw would correlate the cells)
                flat_c = priv.client_transform(
                    pparams, k_priv, jnp.arange(n), flat)
                keys_l = chunking.client_keys(
                    jax.random.fold_in(k_priv, privacy_lib.NOISE_FOLD),
                    jnp.arange(n_clusters))
                noise = jax.vmap(
                    lambda k_: pparams.sigma * pparams.clip
                    * jax.random.normal(k_, (d_model,)))(keys_l)
                tot = (wgt @ flat_c
                       + jnp.where(cnt[:, None] > 0.0, noise, 0.0))
                mean_delta = tot / jnp.maximum(cnt, 1.0)[:, None]
            else:
                mean_delta = (wgt @ flat) / jnp.maximum(cnt, 1.0)[:, None]
            delta_tree = algo_registry.unflatten_rows(mean_delta, gm)
            cm_new = jax.tree.map(
                lambda m_, d_: (m_.astype(jnp.float32)
                                + aparams.server_lr * d_).astype(m_.dtype),
                cm, delta_tree)
            if faults_on:
                alive_l = cnt > 0.0
                cm = jax.tree.map(
                    lambda new, old: jnp.where(
                        alive_l.reshape((n_clusters,)
                                        + (1,) * (new.ndim - 1)), new, old),
                    cm_new, cm)
            else:
                cm = cm_new

            # --- SCAFFOLD: cluster-level server control variates ---------
            # c_l = mean over the cluster's c_i stays invariant: scheduled
            # clients advance c_i by the *transmitted* ctrl delta, and the
            # SBS integrates the same quantity scaled by 1/|C_l|.
            if algo.uses_ctrl:
                ctrl = ctrl + ctrl_wire * part_f[:, None]
                cc_upd = cc + ((wgt @ ctrl_wire)
                               / jnp.maximum(cluster_sizes, 1.0)[:, None])
                cc = (jnp.where(alive_l[:, None], cc_upd, cc)
                      if faults_on else cc_upd)

            # --- periodic inter-cluster sync over the SBS->MBS backhaul --
            # lax.cond skips the (L, D) flatten/compress work entirely on
            # the period-1 non-sync rounds of the single-run path (vmapped
            # sweeps lower cond to select, where both branches run anyway)
            sync = ((t + 1) % period) == 0

            def do_sync(ops):
                cm_, gm_, key = ops
                cm_flat, _ = fl_server.flatten_clients(cm_)      # (L, D)
                gm_flat = algo_registry.flatten_vec(gm_)
                bh_deltas = cm_flat - gm_flat[None, :]
                if comp_active:
                    keys_bh = jax.random.split(key, n_clusters)
                    bh_wire, bh_bits = jax.vmap(
                        compress_fn, in_axes=(None, 0, 0))(
                            cparams, keys_bh, bh_deltas)
                    bh_bits_sbs = up.payload_scale * bh_bits    # (L,)
                else:
                    bh_wire = bh_deltas
                    bh_bits_sbs = jnp.full((n_clusters,), cfg.model_bits,
                                           jnp.float32)
                gm_new = jax.tree.map(
                    lambda g, gn: gn.astype(g.dtype), gm_,
                    algo_registry.unflatten_vec(
                        gm_flat + w_cluster @ bh_wire, gm_))
                cm_new = jax.tree.map(
                    lambda c_, g_: jnp.broadcast_to(
                        g_[None], c_.shape).astype(c_.dtype), cm_, gm_new)
                # parallel per-SBS fronthaul links: one backhaul transfer
                # per SBS (bit cost is data-independent, so all L are equal).
                # bh_rate is *traced* (see HFLConfig.static_key), so a
                # backhaul-rate grid sweeps without retracing.
                return (cm_new, gm_new,
                        jnp.max(bh_bits_sbs) / bh_rate,
                        jnp.sum(bh_bits_sbs))

            def no_sync(ops):
                cm_, gm_, _ = ops
                return cm_, gm_, jnp.float32(0.0), jnp.float32(0.0)

            cm, gm, bh_time, ubits_bh = lax.cond(sync, do_sync, no_sync,
                                                 (cm, gm, k_bh))
            ubits = ubits_intra + ubits_bh

            # --- downlink pricing (always on): each SBS broadcasts its
            # cluster model to the members opening the round; on sync
            # rounds the MBS additionally pushes the fresh global model
            # back over every SBS's fronthaul link (parallel, equal cost).
            # The round time is the slowest scheduled device's plus the
            # backhaul's.
            mb = jnp.float32(cfg.model_bits)
            sync_f = sync.astype(jnp.float32)
            bh_time = bh_time + sync_f * (mb / bh_rate)

            def z_eff(_):
                # clusters compose in *parallel* (disjoint populations), so
                # the round's guarantee is the worst cell's: local field
                # noise aggregates to sigma * sqrt(m) in the smallest
                # non-empty cluster; central dp adds sigma per cluster
                if not priv.dp_local:
                    return pparams.sigma
                m_min = jnp.min(jnp.where(cnt > 0.0, cnt, jnp.inf))
                return pparams.sigma * jnp.sqrt(
                    jnp.where(jnp.isfinite(m_min), m_min, 1.0))

            ls, out = lk.close(ls, kt, up, mask, part_f, fate, ubits, mb,
                               z_eff, bh_time)
            out = out._replace(downlink_bits=out.downlink_bits * n_clusters
                               + sync_f * mb * n_clusters)
            loss = jnp.mean(losses)
            if has_eval:
                loss = loss_fn(inter_cluster_average(cm, cluster_sizes),
                               eval_batch)[0]
            ls, out = lk.finish(ls, kn, out, loss)
            return (cm, gm, ef, ctrl, cc, ls), out

        return step

    def _scan(key, chan, cparams, aparams, extra, fparams, pparams, pol_w,
              init_params, batches_all, eval_batch):
        k_geo, k_rounds = jax.random.split(key)
        geo = site(k_geo, chan)
        step = make_step(chan, cparams, aparams, extra, fparams, pparams,
                         pol_w, geo, k_rounds, eval_batch)
        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
        carry, outs = lax.scan(
            step, compat.vary_like(init_carry(init_params), key),
            (ts, batches_all))
        final = jax.tree.map(lambda p0, f: f.astype(p0.dtype), init_params,
                             params_of(carry, geo))
        return final, outs

    return _Round(cfg, init_carry, make_step, _scan, site, params_of,
                  n_extra=1)


def _resolve_hfl_channel(cfg: SimConfig, hcfg: HFLConfig, wcfg, cluster_wcfgs
                         ) -> Tuple[wireless.WirelessConfig,
                                    wireless.ChannelParams]:
    """Resolve the HFL channel inputs: a single cell config shared by every
    cluster (scalar ChannelParams fields), or one WirelessConfig per cluster
    (fields gain a leading (L,) axis, gathered per device in the engine).
    Returns ``(static wcfg, ChannelParams)``.

    Note: device placement — and therefore every device->SBS *distance* —
    comes from the hex geometry (``hcfg.deploy_radius_m`` /
    ``hcfg.sbs_pitch_m``), not from ``cell_radius_m``; the radiometric
    fields (tx power, path-loss exponent, noise, bandwidth, ...) are what
    vary per cluster here.
    """
    if wcfg is not None and cluster_wcfgs is not None:
        raise ValueError("pass wcfg= or cluster_wcfgs=, not both")
    if cluster_wcfgs is not None:
        ws = list(cluster_wcfgs)
        if len(ws) != hcfg.n_clusters:
            raise ValueError(
                f"cluster_wcfgs needs one WirelessConfig per cluster "
                f"({hcfg.n_clusters}), got {len(ws)}")
        statics = (ws[0].n_devices, ws[0].n_subchannels)
        for w in ws:
            if (w.n_devices, w.n_subchannels) != statics:
                raise ValueError("cluster_wcfgs must share static fields "
                                 "(n_devices, n_subchannels)")
            if cfg.policy == "age" and w.bandwidth_hz != ws[0].bandwidth_hz:
                raise ValueError(
                    "cluster_wcfgs must share static bandwidth_hz for the "
                    "'age' policy (its sub-band bandwidth compiles in "
                    "statically)")
        return ws[0], wireless.stack_channel_params(ws)
    w = wcfg or wireless.WirelessConfig(n_devices=cfg.n_devices)
    return w, wireless.channel_params(w)


def run_hfl(cfg: SimConfig, hcfg: HFLConfig, loss_fn, init_params: PyTree,
            sample_client_batches: Callable[[int, int], Dict[str, jnp.ndarray]],
            eval_fn: Optional[Callable[[PyTree], float]] = None, *,
            wcfg: Optional[wireless.WirelessConfig] = None,
            cluster_wcfgs: Optional[Sequence[wireless.WirelessConfig]] = None,
            engine: Optional[str] = None) -> List[RoundLog]:
    """Wireless-aware HFL (Alg. 9) as a single scanned program.

    Intra-cluster averaging runs every round over the fading device->SBS
    channel (per-cluster scheduling + compressed, priced uplinks);
    inter-cluster sync runs every ``hcfg.inter_cluster_period`` rounds over
    the ``hcfg.backhaul_rate_bps`` fronthaul. Same eval/engine contract as
    :func:`run_simulation`; ``cluster_wcfgs`` gives each SBS its own cell
    configuration (one entry per cluster — radiometric fields like tx
    power/path loss/bandwidth; device->SBS distances come from the
    ``hcfg`` hex geometry, so ``cell_radius_m`` is inert here).
    ``cfg.n_scheduled`` is the *per-cluster* scheduling budget — one int
    shared by every cluster, or a tuple with one budget per cluster
    (heterogeneous cells; each entry also sets that cell's uplink
    bandwidth split).
    """
    _check_hfl_config(cfg)
    wcfg_stat, chan = _resolve_hfl_channel(cfg, hcfg, wcfg, cluster_wcfgs)
    return _run(cfg, wcfg_stat, hcfg, chan, loss_fn, init_params,
                sample_client_batches, eval_fn, engine)
