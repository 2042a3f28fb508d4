"""Failure-aware engine (core/faults.py + fl/runtime.py fault layer):
scan/host parity with faults in the carry, graceful degradation (all-failed
rounds leave the model bitwise unchanged), fedbuff's synchronous limit,
the zero-retrace fault grid, and the always-on downlink pricing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import make_linear_problem
from repro.core import scheduling, wireless
from repro.core.faults import default_fault_params, fault_params
from repro.core.hierarchy import HFLConfig
from repro.fl import runtime as rt

AP01 = rt.algo_params(lr=0.1)
FAULTS = fault_params(drop_prob=0.3, churn_p_off=0.2, churn_p_on=0.6,
                      straggler_prob=0.3, straggler_alpha=1.5,
                      snr_min=2.0, fading_rho=0.7)


def _make_problem():
    params, loss_fn, make_batches, _ = make_linear_problem(d=16)
    return params, loss_fn, make_batches


def _cfg(**kw):
    kw.setdefault("n_devices", 8)
    kw.setdefault("n_scheduled", 3)
    kw.setdefault("rounds", 8)
    kw.setdefault("algo_params", AP01)
    kw.setdefault("policy", "random")
    kw.setdefault("seed", 7)
    return rt.SimConfig(**kw)


# ---------------------------------------------------------------------------
# parity + determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm,compression",
                         [("fedavg", "none"), ("scaffold", "topk"),
                          ("fedbuff", "none")])
def test_scan_host_parity_with_faults(algorithm, compression):
    """The scan and host engines agree exactly with churn, dropout,
    stragglers and retransmissions in the carry (same step function, same
    key streams)."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(algorithm=algorithm, compression=compression,
               faults=FAULTS, max_retries=2)
    scan_logs = rt.run_simulation(cfg, loss_fn, params0, make_batches,
                                  engine="scan")
    host_logs = rt.run_simulation(cfg, loss_fn, params0, make_batches,
                                  engine="host")
    assert len(scan_logs) == len(host_logs) == cfg.rounds
    for s, h in zip(scan_logs, host_logs):
        np.testing.assert_array_equal(s.participation, h.participation)
        assert s.n_survived == h.n_survived
        assert s.n_dropped == h.n_dropped
        assert s.retransmissions == h.retransmissions
        np.testing.assert_allclose(s.loss, h.loss, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(s.latency_s, h.latency_s,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(s.staleness_mean, h.staleness_mean,
                                   rtol=1e-5, atol=1e-6)


def test_faults_off_is_bitwise_legacy_stream():
    """Setting faults=None reproduces the pre-fault engine exactly: the
    fault layer must not shift the legacy kf/kc/kp key streams."""
    params0, loss_fn, make_batches = _make_problem()
    a = rt.run_simulation(_cfg(), loss_fn, params0, make_batches)
    b = rt.run_simulation(_cfg(faults=None, max_retries=0), loss_fn,
                          params0, make_batches)
    for s, h in zip(a, b):
        np.testing.assert_array_equal(s.participation, h.participation)
        assert s.loss == h.loss and s.latency_s == h.latency_s


def test_fault_logs_populated():
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(faults=FAULTS, max_retries=2, rounds=10)
    _, logs = rt.run_simulation_scan(
        cfg, loss_fn, jax.tree.map(jnp.array, params0),
        rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices))
    assert logs.n_survived.shape == (cfg.rounds,)
    assert (logs.n_survived + logs.n_dropped <= logs.n_scheduled).all()
    assert (logs.n_survived <= logs.n_scheduled).all()
    assert logs.retransmissions.min() >= 0
    assert logs.staleness_mean.min() >= 0


# ---------------------------------------------------------------------------
# graceful degradation: failed rounds leave state untouched
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm,compression",
                         [("fedavg", "none"), ("scaffold", "topk")])
def test_all_dropped_round_leaves_state_bitwise_unchanged(algorithm,
                                                          compression):
    """drop_prob=1 fails every scheduled client; one host step must return
    params / EF / ctrl bitwise identical (jnp.where keeps the old rows)."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(algorithm=algorithm, compression=compression,
               faults=fault_params(drop_prob=1.0), max_retries=0)
    wcfg = wireless.WirelessConfig(n_devices=cfg.n_devices)
    init_carry = rt._make_sim_fns(cfg, wcfg, loss_fn, False).init_carry
    step = rt._get_host_step(cfg, wcfg, loss_fn, False)
    key = jax.random.PRNGKey(cfg.seed)
    k_pos, k_rounds = jax.random.split(key)
    chan = wireless.channel_params(wcfg)
    dist = wireless.sample_positions_jax(k_pos, chan, cfg.n_devices)
    cparams = rt._resolve_cparams(cfg, params0)
    carry0 = init_carry(params0)
    batch = make_batches(0, cfg.n_devices)
    carry1, outs = step(chan, cparams, rt._resolve_aparams(cfg), cfg.faults,
                        dist, k_rounds, None, carry0, (jnp.int32(0), batch))
    assert int(outs.n_survived) == 0
    (s0, _), (s1, _) = carry0, carry1
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if s0.client_error is not None:
        np.testing.assert_array_equal(np.asarray(s0.client_error),
                                      np.asarray(s1.client_error))
    if s0.ctrl is not None:
        np.testing.assert_array_equal(np.asarray(s0.ctrl),
                                      np.asarray(s1.ctrl))


def test_permanent_outage_never_updates_model():
    """snr_min above any achievable SNR fails every decode even after
    retries: across a whole scanned run the model never moves and every
    failed attempt is billed as a retransmission."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(faults=fault_params(snr_min=1e30), max_retries=2, rounds=6)
    p0 = jax.tree.map(jnp.array, params0)
    params, logs = rt.run_simulation_scan(
        cfg, loss_fn, p0,
        rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices))
    assert (logs.n_survived == 0).all()
    np.testing.assert_array_equal(
        logs.retransmissions, cfg.max_retries * logs.n_scheduled)
    for a, b in zip(jax.tree.leaves(params0), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_churn_freeze_marks_everyone_offline():
    """p_off=1, p_on=0 drives the Gilbert-Elliott chain to all-offline
    after round 0: no client is scheduled and the model freezes."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(faults=fault_params(churn_p_off=1.0, churn_p_on=0.0),
               rounds=5)
    _, logs = rt.run_simulation_scan(
        cfg, loss_fn, jax.tree.map(jnp.array, params0),
        rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices))
    assert (logs.n_scheduled == 0).all()


# ---------------------------------------------------------------------------
# fault physics: stragglers + retransmissions change the priced round
# ---------------------------------------------------------------------------

def test_straggler_tail_slows_compute():
    """Pareto straggler multiplier (>= 1) inflates comp_s against the same
    config with the straggler channel disabled (identical schedules under
    the random policy, shared base exponential draws)."""
    params0, loss_fn, make_batches = _make_problem()
    base = fault_params()
    slow = fault_params(straggler_prob=1.0, straggler_alpha=1.1)
    logs = {}
    for name, f in [("base", base), ("slow", slow)]:
        cfg = _cfg(faults=f, rounds=6)
        _, logs[name] = rt.run_simulation_scan(
            cfg, loss_fn, jax.tree.map(jnp.array, params0),
            rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices))
    np.testing.assert_array_equal(logs["base"].participation,
                                  logs["slow"].participation)
    assert (logs["slow"].comp_s >= logs["base"].comp_s).all()
    assert logs["slow"].comp_s.sum() > logs["base"].comp_s.sum()


def test_retries_recover_survivors_and_bill_airtime():
    """A moderate snr_min fails some decodes; raising max_retries can only
    grow the survivor count, and every retry adds priced uplink bits."""
    params0, loss_fn, make_batches = _make_problem()
    f = fault_params(snr_min=3.0)
    out = {}
    for r in (0, 3):
        cfg = _cfg(faults=f, max_retries=r, rounds=8)
        _, out[r] = rt.run_simulation_scan(
            cfg, loss_fn, jax.tree.map(jnp.array, params0),
            rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices))
    assert (out[3].n_survived >= out[0].n_survived).all()
    assert out[3].retransmissions.sum() > 0
    assert out[3].uplink_bits.sum() > out[0].uplink_bits.sum()


# ---------------------------------------------------------------------------
# fedbuff: staleness-discounted buffered-async server
# ---------------------------------------------------------------------------

def test_fedbuff_synchronous_limit_is_bitwise_fedavg():
    """staleness_pow=0 + buffer_goal=1 reduces fedbuff to synchronous
    fedavg bitwise (x * 1.0 identity + unflatten(flatten(x)) identity)."""
    params0, loss_fn, make_batches = _make_problem()
    batches = rt.stack_batches(make_batches, 8, 8)
    pa, la = rt.run_simulation_scan(
        _cfg(algorithm="fedbuff",
             algo_params=rt.algo_params(lr=0.1, staleness_pow=0.0,
                                        buffer_goal=1.0)),
        loss_fn, jax.tree.map(jnp.array, params0), batches)
    pb, lb = rt.run_simulation_scan(
        _cfg(algorithm="fedavg"), loss_fn,
        jax.tree.map(jnp.array, params0), batches)
    np.testing.assert_array_equal(la.loss, lb.loss)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fedbuff_buffer_goal_defers_the_server_step():
    """buffer_goal=3 holds the aggregated deltas in the server buffer: the
    model is bitwise frozen through round 2 and moves at round 3."""
    params0, loss_fn, make_batches = _make_problem()
    ap = rt.algo_params(lr=0.1, staleness_pow=0.0, buffer_goal=3.0)

    def run(rounds):
        cfg = _cfg(algorithm="fedbuff", algo_params=ap, rounds=rounds)
        p, _ = rt.run_simulation_scan(
            cfg, loss_fn, jax.tree.map(jnp.array, params0),
            rt.stack_batches(make_batches, rounds, cfg.n_devices))
        return jax.tree.leaves(p)

    for a, b in zip(jax.tree.leaves(params0), run(2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = any((np.asarray(a) != np.asarray(b)).any()
                for a, b in zip(jax.tree.leaves(params0), run(3)))
    assert moved


def test_fedbuff_staleness_discount_changes_the_trajectory():
    """With faults on, staleness_pow > 0 discounts stale survivors, so the
    trajectory departs from the undiscounted run."""
    params0, loss_fn, make_batches = _make_problem()
    batches = rt.stack_batches(make_batches, 10, 8)
    runs = {}
    for pw in (0.0, 2.0):
        cfg = _cfg(algorithm="fedbuff",
                   algo_params=rt.algo_params(lr=0.1, staleness_pow=pw,
                                              buffer_goal=1.0),
                   faults=FAULTS, max_retries=1, rounds=10)
        _, runs[pw] = rt.run_simulation_scan(
            cfg, loss_fn, jax.tree.map(jnp.array, params0), batches)
    assert (runs[0.0].loss != runs[2.0].loss).any()


# ---------------------------------------------------------------------------
# sweeps: the fault axis is traced
# ---------------------------------------------------------------------------

def test_fault_grid_sweep_zero_retraces_warm():
    """A 4-dropout x 2-policy fault grid rides one engine: exactly one
    trace cold, zero on the warm cache, and survivors fall with dropout."""
    params0, loss_fn, make_batches = _make_problem()
    rounds, n = 5, 8
    fgrid = [fault_params(drop_prob=p) for p in (0.0, 0.2, 0.5, 0.9)]
    cfg = _cfg(faults=fgrid[0], rounds=rounds)
    batches = rt.stack_batches(make_batches, rounds, n)
    kw = dict(seeds=[0, 1], policies=["random", "best_channel"],
              fparams_grid=fgrid)

    out = rt.run_sweep(cfg, loss_fn, params0, batches, **kw)
    before = rt.ENGINE_STATS["traces"]
    out2 = rt.run_sweep(cfg, loss_fn, params0, batches, **kw)
    assert rt.ENGINE_STATS["traces"] == before  # zero retraces warm

    for pol in ("random", "best_channel"):
        logs = out[pol]
        assert logs.loss.shape == (2 * len(fgrid), rounds)
        assert logs.n_survived.shape == (2 * len(fgrid), rounds)
        np.testing.assert_array_equal(logs.loss, out2[pol].loss)
        # variants are ordered seed-major: (seed, drop) -> mean survivors
        # fall monotonically-ish; compare the grid endpoints per seed
        surv = logs.n_survived.reshape(2, len(fgrid), rounds).mean(axis=2)
        assert (surv[:, 0] > surv[:, -1]).all()


# ---------------------------------------------------------------------------
# downlink pricing (always on) + outage latency semantics
# ---------------------------------------------------------------------------

def test_downlink_is_priced_flat():
    """Every round broadcasts model_bits downlink; the logged round time
    decomposes as downlink + uplink + compute with a positive downlink
    residual."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(rounds=5)
    _, logs = rt.run_simulation_scan(
        cfg, loss_fn, jax.tree.map(jnp.array, params0),
        rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices))
    np.testing.assert_array_equal(logs.downlink_bits,
                                  np.full(cfg.rounds, cfg.model_bits))
    dt = np.diff(np.concatenate([[0.0], logs.latency_s]))
    assert (dt - (logs.comm_s + logs.comp_s) > 0).all()


def test_downlink_is_priced_hfl():
    """HFL prices the MBS->SBS broadcast every round plus the sync-round
    backhaul copy: downlink bits jump on inter-cluster sync rounds."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = rt.SimConfig(n_devices=12, rounds=6, algo_params=AP01, seed=3)
    hcfg = HFLConfig(n_clusters=3, inter_cluster_period=3)
    logs = rt.run_hfl(cfg, hcfg, loss_fn, params0, make_batches)
    dl = np.asarray([l.downlink_bits for l in logs])
    assert (dl > 0).all()
    # rounds 2, 5 are sync rounds (period 3): extra backhaul model copy
    assert dl[2] > dl[1]


def test_hfl_runs_with_faults_and_logs_survivors():
    params0, loss_fn, make_batches = _make_problem()
    cfg = rt.SimConfig(n_devices=12, rounds=6, algo_params=AP01, seed=3,
                       faults=FAULTS, max_retries=1)
    hcfg = HFLConfig(n_clusters=3, inter_cluster_period=3)
    logs = rt.run_hfl(cfg, hcfg, loss_fn, params0, make_batches)
    assert len(logs) == cfg.rounds
    for l in logs:
        assert l.n_survived <= l.n_scheduled
        assert np.isfinite(l.loss)


def test_comm_latency_outage_is_inf_not_clamped():
    """Zero/negative rate means an outage: latency is inf (satellite 1),
    in both the numpy and the traced jax pricing."""
    rates = np.array([1e6, 0.0, -1.0])
    lat = wireless.comm_latency(1e6, rates)
    assert lat[0] == 1.0
    assert np.isinf(lat[1]) and np.isinf(lat[2])
    jlat = wireless.comm_latency_jax(jnp.float32(1e6),
                                     jnp.asarray(rates, jnp.float32))
    np.testing.assert_array_equal(np.asarray(jlat), lat)


def test_deadline_policy_excludes_outage_device():
    """An inf comm latency can never fit a deadline: the greedy deadline
    policy must not schedule the outage device."""
    n = 6
    pcfg = scheduling.PolicyConfig(n_devices=n, n_scheduled=4,
                                   deadline_s=10.0)
    comm = jnp.asarray([0.1, jnp.inf, 0.2, 0.1, 0.3, 0.2], jnp.float32)
    st = scheduling.RoundState(
        t=jnp.int32(0), key=jax.random.PRNGKey(0),
        snr_lin=jnp.ones(n), avg_snr=jnp.ones(n), rates=jnp.ones(n),
        comm_lat=comm, comp_lat=jnp.zeros(n),
        ages=jnp.zeros(n), update_norms=jnp.zeros(n))
    mask = np.asarray(scheduling.get_policy("deadline")(pcfg, st))
    assert mask[1] == 0
    assert mask.sum() == n - 1  # every finite-latency device fits


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_simconfig_validates_fault_fields():
    with pytest.raises(ValueError, match="max_retries"):
        _cfg(max_retries=-1)
    with pytest.raises(ValueError, match="FaultParams"):
        _cfg(faults={"drop_prob": 0.5})
    # defaults construct cleanly and are all-off
    f = default_fault_params()
    assert float(f.drop_prob) == 0.0
