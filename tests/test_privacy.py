"""Privacy axis (core/privacy + fl/server + fl/runtime threading):

* pairwise PRG masks cancel to the uint32 zero word over *any* survivor
  set (closed-form Bonawitz post-dropout algebra), under jit;
* the full secagg engine is bitwise the hidden field-quantized-but-unmasked
  oracle — masks are invisible in the aggregate, including under churn,
  dropout and decode failure;
* privacy="none" reproduces the legacy key streams bit for bit;
* scan/host parity with the accountant ledger in the carry;
* an all-dropped round is a no-op (masks of an empty survivor set);
* a clip x sigma x seed grid is one compiled call (zero retraces warm);
* per-round (epsilon, delta) is monotone non-decreasing, +inf/1.0 without
  a DP mechanism, and prices into the tuner's eps_budget;
* wire pricing: field modes bill field_bits/coord, masks bill 2*KEY_BITS
  per cluster/cohort peer;
* illegal compositions raise at config time.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import make_linear_problem
from repro.core import wireless
from repro.core.faults import fault_params
from repro.core.hierarchy import HFLConfig
from repro.core.privacy import (ALPHAS, DELTA, KEY_BITS, epsilon_of,
                                get_privacy, mask_bits_jax, mask_rows,
                                pairwise_masks, privacy_names, privacy_params,
                                rdp_increment, uplink_bits_jax,
                                validate_privacy_config)
from repro.fl import runtime as rt

AP01 = rt.algo_params(lr=0.1)
PP = privacy_params(clip=0.5, sigma=0.0, field_bits=20.0)
FAULTS = fault_params(drop_prob=0.3, churn_p_off=0.2, churn_p_on=0.6,
                      snr_min=2.0, fading_rho=0.5)


def _make_problem():
    params, loss_fn, make_batches, _ = make_linear_problem(d=16)
    return params, loss_fn, make_batches


def _cfg(**kw):
    kw.setdefault("n_devices", 8)
    kw.setdefault("n_scheduled", 3)
    kw.setdefault("rounds", 6)
    kw.setdefault("algo_params", AP01)
    kw.setdefault("policy", "random")
    kw.setdefault("seed", 7)
    return rt.SimConfig(**kw)


# ---------------------------------------------------------------------------
# mask algebra: exact modular cancellation over arbitrary survivor sets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("surv_ids", [(0, 1, 2, 3, 4, 5, 6, 7),
                                      (0, 3, 7), (2,), (5, 6)])
def test_pairwise_masks_cancel_exactly(surv_ids):
    """The masked survivor sum equals the unmasked one word-for-word in
    uint32: the pairwise masks sum to the zero element of Z_{2^32}."""
    n, d = 8, 33
    key = jax.random.PRNGKey(0)
    ids = jnp.asarray(surv_ids, jnp.int32)

    @jax.jit
    def masked_minus_plain(k):
        g_all = mask_rows(k, jnp.arange(n), d)
        gsum = jnp.sum(jnp.where(jnp.isin(jnp.arange(n), ids)[:, None],
                                 g_all, jnp.uint32(0)), axis=0,
                       dtype=jnp.uint32)
        cnt = jnp.int32(len(surv_ids))
        rows = jax.random.bits(k, (n, d), jnp.uint32)  # arbitrary payload
        masks = pairwise_masks(k, ids, d, gsum, cnt)
        masked = jnp.sum(rows[ids] + masks, axis=0, dtype=jnp.uint32)
        plain = jnp.sum(rows[ids], axis=0, dtype=jnp.uint32)
        return masked - plain

    np.testing.assert_array_equal(np.asarray(masked_minus_plain(key)),
                                  np.zeros(d, np.uint32))


def test_empty_survivor_set_masks_are_zero_sum():
    """No survivors -> gsum = 0, cnt = 0 -> every mask row is 0 - 0 = 0:
    the all-dropped round adds nothing to the (empty) aggregate."""
    d = 16
    key = jax.random.PRNGKey(3)
    masks = pairwise_masks(key, jnp.arange(0, dtype=jnp.int32), d,
                           jnp.zeros(d, jnp.uint32), jnp.int32(0))
    assert masks.shape == (0, d)


# ---------------------------------------------------------------------------
# engine-level: secagg aggregate == field-quantized unmasked sum, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compression", ["none", "sign"])
def test_secagg_bitwise_equals_unmasked_field_sum(compression):
    params0, loss_fn, make_batches = _make_problem()
    a = rt.run_simulation(_cfg(privacy="secagg", privacy_params=PP,
                               compression=compression),
                          loss_fn, params0, make_batches)
    b = rt.run_simulation(_cfg(privacy="_secagg_unmasked", privacy_params=PP,
                               compression=compression),
                          loss_fn, params0, make_batches)
    for s, h in zip(a, b):
        # aggregates (and thus the whole trajectory) bitwise equal; only
        # the *wire pricing* differs (the oracle pays no key agreement)
        assert s.loss == h.loss
        assert s.uplink_bits == h.uplink_bits + s.mask_bits


def test_secagg_bitwise_under_churn_and_dropout():
    """Dropout-robust cancellation: whatever survivor set the fault layer
    produces each round, the masked aggregate matches the unmasked one."""
    params0, loss_fn, make_batches = _make_problem()
    a = rt.run_simulation(_cfg(privacy="secagg", privacy_params=PP,
                               faults=FAULTS, max_retries=2),
                          loss_fn, params0, make_batches)
    b = rt.run_simulation(_cfg(privacy="_secagg_unmasked", privacy_params=PP,
                               faults=FAULTS, max_retries=2),
                          loss_fn, params0, make_batches)
    surv = [s.n_survived for s in a]
    assert len(set(surv)) > 1  # the fault draw actually varies the cohort
    for s, h in zip(a, b):
        assert s.loss == h.loss


def test_hfl_secagg_bitwise_equals_unmasked():
    params0, loss_fn, make_batches = _make_problem()
    h = HFLConfig(n_clusters=2, inter_cluster_period=2)
    a = rt.run_hfl(_cfg(privacy="secagg", privacy_params=PP), h, loss_fn,
                   params0, make_batches)
    b = rt.run_hfl(_cfg(privacy="_secagg_unmasked", privacy_params=PP), h,
                   loss_fn, params0, make_batches)
    for s, t in zip(a, b):
        assert s.loss == t.loss


# ---------------------------------------------------------------------------
# legacy preservation + parity
# ---------------------------------------------------------------------------

def test_privacy_none_is_bitwise_legacy_stream():
    """privacy="none" must not shift any legacy key stream: the privacy
    fold is derived only when a mechanism is active."""
    params0, loss_fn, make_batches = _make_problem()
    a = rt.run_simulation(_cfg(), loss_fn, params0, make_batches)
    b = rt.run_simulation(_cfg(privacy="none"), loss_fn, params0,
                          make_batches)
    for s, h in zip(a, b):
        np.testing.assert_array_equal(s.participation, h.participation)
        assert s.loss == h.loss and s.latency_s == h.latency_s
        assert s.uplink_bits == h.uplink_bits
        assert s.epsilon == float("inf") and s.delta == 1.0
        assert s.mask_bits == 0.0


@pytest.mark.parametrize("privacy", ["dp", "secagg_dp"])
def test_scan_host_parity_with_privacy(privacy):
    """Scan and host engines agree with the Renyi ledger in the carry."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(privacy=privacy,
               privacy_params=privacy_params(clip=1.0, sigma=0.8))
    scan_logs = rt.run_simulation(cfg, loss_fn, params0, make_batches,
                                  engine="scan")
    host_logs = rt.run_simulation(cfg, loss_fn, params0, make_batches,
                                  engine="host")
    for s, h in zip(scan_logs, host_logs):
        np.testing.assert_array_equal(s.participation, h.participation)
        np.testing.assert_allclose(s.loss, h.loss, rtol=1e-4, atol=1e-5)
        assert s.epsilon == h.epsilon and s.delta == h.delta
        assert s.mask_bits == h.mask_bits


def test_all_dropped_round_is_noop_with_secagg():
    """drop_prob=1: the masked field aggregate of the empty survivor set
    decodes to zero and the guard keeps the model bitwise."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(privacy="secagg", privacy_params=PP,
               faults=fault_params(drop_prob=1.0), max_retries=0)
    wcfg = wireless.WirelessConfig(n_devices=cfg.n_devices)
    init_carry = rt._make_sim_fns(cfg, wcfg, loss_fn, False).init_carry
    step = rt._get_host_step(cfg, wcfg, loss_fn, False)
    key = jax.random.PRNGKey(cfg.seed)
    k_pos, k_rounds = jax.random.split(key)
    chan = wireless.channel_params(wcfg)
    dist = wireless.sample_positions_jax(k_pos, chan, cfg.n_devices)
    carry0 = init_carry(params0)
    batch = make_batches(0, cfg.n_devices)
    carry1, outs = step(chan, rt._resolve_cparams(cfg, params0),
                        rt._resolve_aparams(cfg), cfg.faults, PP, dist,
                        k_rounds, None, carry0, (jnp.int32(0), batch))
    assert int(outs.n_survived) == 0
    (s0, _), (s1, _) = carry0, carry1
    for p0, p1 in zip(jax.tree.leaves(s0.params),
                      jax.tree.leaves(s1.params)):
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


# ---------------------------------------------------------------------------
# sweep economics: one trace per static name, traced clip x sigma grid
# ---------------------------------------------------------------------------

def test_clip_sigma_seed_grid_is_zero_retrace_warm():
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg()
    batches = rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices)
    grid = [privacy_params(clip=c, sigma=s)
            for c in (0.5, 1.0) for s in (0.4, 0.8)]
    rt.run_sweep(cfg, loss_fn, params0, batches, seeds=[0, 1],
                 privacies=["dp", "secagg_dp"], pparams_grid=grid)
    before = rt.ENGINE_STATS["traces"]
    out = rt.run_sweep(cfg, loss_fn, params0, batches, seeds=[2, 3],
                       privacies=["dp", "secagg_dp"],
                       pparams_grid=[privacy_params(clip=c, sigma=s)
                                     for c in (0.7, 1.3)
                                     for s in (0.6, 1.1)])
    assert rt.ENGINE_STATS["traces"] == before  # warm grid: zero retraces
    logs = out[("random", "dp")]
    assert logs.loss.shape == (2 * 4, cfg.rounds)
    assert logs.epsilon is not None


def test_sweep_mixes_none_with_mechanisms():
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(rounds=4)
    batches = rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices)
    out = rt.run_sweep(cfg, loss_fn, params0, batches, seeds=[0],
                       privacies=["none", "dp"],
                       pparams_grid=[privacy_params(clip=1.0, sigma=1.0)])
    assert set(out) == {("random", "none"), ("random", "dp")}
    assert np.isinf(np.asarray(out[("random", "none")].epsilon)).all()
    assert np.isfinite(np.asarray(out[("random", "dp")].epsilon)).all()


# ---------------------------------------------------------------------------
# accountant: monotone, correctly guarded, budget-scored
# ---------------------------------------------------------------------------

def test_epsilon_monotone_and_delta_fixed():
    params0, loss_fn, make_batches = _make_problem()
    logs = rt.run_simulation(
        _cfg(privacy="dp", privacy_params=privacy_params(clip=1.0,
                                                         sigma=1.2)),
        loss_fn, params0, make_batches)
    eps = [l.epsilon for l in logs]
    assert all(np.isfinite(eps))
    assert all(b >= a for a, b in zip(eps, eps[1:]))
    assert all(l.delta == np.float32(DELTA) for l in logs)


def test_rdp_increment_guards():
    assert np.isinf(np.asarray(rdp_increment(0.5, 0.0))).all()  # no noise
    np.testing.assert_array_equal(np.asarray(rdp_increment(0.0, 1.0)),
                                  np.zeros(len(ALPHAS)))        # no sampling
    full = np.asarray(rdp_increment(1.0, 2.0))
    sub = np.asarray(rdp_increment(0.1, 2.0))
    assert (sub <= full).all()


def test_epsilon_of_minimizes_over_orders():
    rdp = jnp.full(len(ALPHAS), 0.01)
    per_order = [0.01 + np.log(1.0 / DELTA) / (a - 1.0) for a in ALPHAS]
    np.testing.assert_allclose(float(epsilon_of(rdp)), min(per_order),
                               rtol=1e-6)


def test_tune_eps_budget_gates_scoring():
    from repro.fl.tune import loss_at_budget
    loss = np.asarray([[3.0, 2.0, 1.0]])
    eps = np.asarray([[0.5, 1.0, 2.0]])
    logs = rt.SimLogs(loss=loss, latency_s=np.ones_like(loss).cumsum(-1),
                      n_scheduled=None, participation=None, uplink_bits=None,
                      comm_s=None, comp_s=None, downlink_bits=None,
                      epsilon=eps, delta=np.full_like(loss, DELTA))
    np.testing.assert_array_equal(loss_at_budget(logs, None, 1.0), [2.0])
    np.testing.assert_array_equal(loss_at_budget(logs, None, 0.1), [np.inf])
    np.testing.assert_array_equal(loss_at_budget(logs, 2.5, 2.0), [2.0])
    # no DP mechanism (epsilon=None) can never meet an epsilon budget
    logs_np = dataclasses.replace(logs, epsilon=None, delta=None)
    np.testing.assert_array_equal(loss_at_budget(logs_np, None, 10.0),
                                  [np.inf])


# ---------------------------------------------------------------------------
# wire pricing
# ---------------------------------------------------------------------------

def test_uplink_and_mask_bit_pricing():
    pp = privacy_params(clip=1.0, sigma=0.0, field_bits=20.0)
    assert float(uplink_bits_jax("secagg", pp, 33, 0.0)) == 20.0 * 33
    assert float(uplink_bits_jax("dp", pp, 33, 7.0)) == 7.0
    assert float(mask_bits_jax("secagg", 7)) == 2.0 * KEY_BITS * 7
    assert float(mask_bits_jax("dp", 7)) == 0.0


def test_secagg_uplink_priced_as_field_plus_keys():
    """Engine-level pricing: with compression off, every scheduled client
    bills field_bits/32 * model_bits payload + 2*KEY_BITS*(n-1) keys."""
    params0, loss_fn, make_batches = _make_problem()
    cfg = _cfg(privacy="secagg", privacy_params=PP)
    logs = rt.run_simulation(cfg, loss_fn, params0, make_batches)
    d = 16 + 1  # linear problem flat dim (w + b)
    payload_scale = cfg.model_bits / (32.0 * d)
    for l in logs:
        k = l.n_scheduled
        keys_bits = 2.0 * KEY_BITS * (cfg.n_devices - 1) * k
        payload = payload_scale * 20.0 * d * k
        assert l.mask_bits == keys_bits
        np.testing.assert_allclose(l.uplink_bits, payload + keys_bits,
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# registry + composition validation
# ---------------------------------------------------------------------------

def test_privacy_names_hides_oracle():
    names = privacy_names()
    assert set(names) >= {"none", "secagg", "dp", "secagg_dp"}
    assert all(not n.startswith("_") for n in names)
    get_privacy("_secagg_unmasked")  # still resolvable
    with pytest.raises(ValueError, match="unknown privacy"):
        get_privacy("paillier")


def test_illegal_pairs_raise():
    with pytest.raises(ValueError, match="sparse"):
        validate_privacy_config("secagg", compression="topk",
                                algorithm="fedavg")
    with pytest.raises(ValueError, match="control"):
        validate_privacy_config("dp", compression="none",
                                algorithm="scaffold")
    with pytest.raises(ValueError, match="stale"):
        validate_privacy_config("secagg", compression="none",
                                algorithm="fedbuff")
    # legal: central dp composes with sparse compression and fedbuff
    validate_privacy_config("dp", compression="topk", algorithm="fedbuff")


def test_simconfig_validates_privacy():
    with pytest.raises(ValueError, match="sparse"):
        _cfg(privacy="secagg", compression="topk")
    with pytest.raises(ValueError, match="unknown privacy"):
        _cfg(privacy="nope")
