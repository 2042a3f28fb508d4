"""Fleet-scale engine (chunked client pass + on-device data):

* canonical pairwise-tree reductions are chunk-invariant bitwise;
* the ``lax.scan`` chunked client pass of ``fl_round`` matches the
  unchunked pass bitwise — every compressor, dense/sparse EF, bf16 state,
  SCAFFOLD ctrl, participation masks — when both run under ``jax.jit``
  (the engine's only mode; eager constant-folds transcendentals with a
  different evaluator, see the ``fl_round`` docstring);
* on-device datagen reproduces the pre-stacked ``stack_batches`` path bit
  for bit and matches the host sampler's statistics;
* chunking actually bounds the compiled program's temp memory;
* hierarchical per-cluster ``n_scheduled`` budgets;
* the row-batched kernel dispatch API (jit mirror == interpret Pallas).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import make_linear_problem
from repro.core import chunking, compression
from repro.core.compression import SparseEF, compression_params
from repro.core.hierarchy import HFLConfig
from repro.core.privacy import privacy_params
from repro.data import make_linear_datagen
from repro.fl import runtime as rt
from repro.fl import server

AP01 = rt.algo_params(lr=0.1)
N = 10       # deliberately not a multiple of the chunk: exercises padding
CHUNK = 4
D = 24


def _problem():
    params, loss_fn, make_batches, w_star = make_linear_problem(d=D, h=2, b=4)
    return params, loss_fn, make_batches, w_star


def _elementwise_loss(params, batch):
    """The linear problem's loss with the prediction as a product and a
    sum, no dot: XLA's CPU backend lowers a batch-1 vmapped dot (a pass
    of blocks of one client) in another summation order than the batched
    dot, so per-client updates would differ in the last ulp before any
    sum of the round (ROADMAP C1)."""
    pred = jnp.sum(batch["x"] * params["w"], axis=-1)
    return jnp.mean((pred - batch["y"]) ** 2), {}


# ---------------------------------------------------------------------------
# canonical reduction tree
# ---------------------------------------------------------------------------
def test_canonical_sum_chunk_invariance():
    """Aligned pow2 blocks are complete subtrees of the adjacent-pair fold:
    block partials + a canonical fold over the partials reproduce the full
    canonical sum bitwise, for every chunk size."""
    x = jax.random.normal(jax.random.PRNGKey(3), (23, 5))
    full = np.asarray(chunking.canonical_sum(x))
    for chunk in (1, 2, 4, 8, 16):
        m = chunking.n_blocks(23, chunk)
        pad = jnp.zeros((m * chunk - 23, 5), x.dtype)
        blocks = jnp.concatenate([x, pad]).reshape(m, chunk, 5)
        partials = jax.vmap(chunking.canonical_sum)(blocks)
        got = np.asarray(chunking.canonical_sum(partials))
        np.testing.assert_array_equal(got, full)
        # stacked as the chunked pass stacks them, (m, 1, ...)
        got = np.asarray(chunking.canonical_sum(partials[:, None])[0])
        np.testing.assert_array_equal(got, full)


def test_canonical_sum_weighted_matches_masked():
    x = jax.random.normal(jax.random.PRNGKey(4), (7, 3))
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0, 1.0])
    got = chunking.canonical_sum(x, w)
    want = chunking.canonical_sum(x * w[:, None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# chunked fl_round == unchunked fl_round, bitwise (under jit)
# ---------------------------------------------------------------------------
def _round_outputs(name, chunk, *, ef_mode="dense", state_dtype=jnp.float32,
                   algo="fedavg", double_ef=False, with_part=False,
                   privacy=None, blocked=False, gate_ef=False,
                   elementwise=False):
    """``blocked`` allocates a chunked pass's state as the engine does,
    (m, chunk, ...) rows; ``gate_ef`` adds the fault mode's EF gate and
    empty-round guard (needs ``with_part``); ``elementwise`` takes
    `_elementwise_loss`."""
    params, loss_fn, make_batches, _ = _problem()
    if elementwise:
        loss_fn = _elementwise_loss
    batches = jax.tree.map(jnp.asarray, make_batches(0, N))
    # chunk >= N degenerates to the unchunked pass (N state rows)
    eff = chunk if chunk is not None and chunk < N else None
    m = chunking.n_blocks(N, eff) if eff else None
    rows = ((m, eff) if blocked else m * eff) if eff else N
    comp = name != "none"
    state = server.init_fl_state(
        params, N, algo=algo, use_ef=comp, double_ef=comp and double_ef,
        ef_mode=ef_mode, state_dtype=state_dtype, n_rows=rows)
    kwargs = dict(loss_fn=loss_fn, algo=algo, aparams=AP01,
                  chunk_size=chunk, n_clients=N)
    if comp:
        kwargs.update(compression_name=name,
                      compress_fn=compression.get_compressor(name),
                      cparams=compression_params(), key=jax.random.PRNGKey(7))
    if with_part:
        part = (jnp.arange(N) % 2).astype(jnp.float32)
        kwargs.update(participation=part)
    if gate_ef:
        kwargs.update(gate_ef=True, guard_empty=True)
    if privacy is not None:
        kwargs.update(privacy=privacy,
                      pparams=privacy_params(clip=0.5, sigma=0.3),
                      privacy_key=jax.random.PRNGKey(11))
    fn = jax.jit(functools.partial(server.fl_round, **kwargs))
    new_state, metrics = fn(state, batches)
    # the round hands per-client state back in the layout it was given
    for old, new in ((state.client_error, new_state.client_error),
                     (state.ctrl, new_state.ctrl)):
        assert jax.tree.map(jnp.shape, old) == jax.tree.map(jnp.shape, new)
    return new_state, metrics


def _state_rows(x, dtype=None):
    """The first N client rows of per-client state, flat or blocked."""
    return np.asarray(x, dtype).reshape(-1, x.shape[-1])[:N]


def _assert_rounds_equal(a, b):
    sa, ma = a
    sb, mb = b
    for k in ma:
        np.testing.assert_array_equal(np.asarray(ma[k]), np.asarray(mb[k]),
                                      err_msg=f"metric {k}")
    for la, lb in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    if sa.client_error is not None:
        if isinstance(sa.client_error, SparseEF):
            np.testing.assert_array_equal(
                _state_rows(sa.client_error.values, jnp.float32),
                _state_rows(sb.client_error.values, jnp.float32))
            np.testing.assert_array_equal(
                _state_rows(sa.client_error.indices),
                _state_rows(sb.client_error.indices))
        else:
            np.testing.assert_array_equal(
                _state_rows(sa.client_error, jnp.float32),
                _state_rows(sb.client_error, jnp.float32))
    if sa.ctrl is not None:
        np.testing.assert_array_equal(_state_rows(sa.ctrl, jnp.float32),
                                      _state_rows(sb.ctrl, jnp.float32))
    if sa.server_error is not None:
        np.testing.assert_array_equal(np.asarray(sa.server_error),
                                      np.asarray(sb.server_error))


# every compressor on a flat chunk-padded state, then the engine's blocked
# (m, chunk, ...) state under each kind of per-client state it carries
_PARITY_CASES = (
    [pytest.param(name, {}, id=name)
     for name in compression.compressor_names()]
    + [pytest.param("topk", dict(blocked=True), id="blocked-topk"),
       pytest.param("topk", dict(blocked=True, state_dtype=jnp.bfloat16),
                    id="blocked-topk-bf16"),
       pytest.param("topk", dict(blocked=True, ef_mode="sparse"),
                    id="blocked-topk-sparse_ef"),
       pytest.param("topk", dict(blocked=True, algo="scaffold"),
                    id="blocked-topk-scaffold"),
       pytest.param("topk", dict(blocked=True, with_part=True, gate_ef=True),
                    id="blocked-topk-gate_ef")]
    # blocks of 2 (m = 5): the stacked block partials outnumber the rows
    # of a block, so the fold after the scan carries most of the tree
    + [pytest.param(name, dict(blocked=True, chunk=2, **o),
                    id=f"blocked-{name}{s}-chunk2")
       for name, o, s in (("none", {}, ""), ("topk", {}, ""),
                          ("topk", dict(algo="scaffold"), "-scaffold"))]
    # blocks of 1 (m = 10), the Mellum cell's shape: no in-block fold, the
    # stacked partials carry the whole tree
    + [pytest.param(name, dict(blocked=True, chunk=1, elementwise=True, **o),
                    id=f"blocked-{name}{s}-chunk1")
       for name, o, s in (("none", {}, ""), ("topk", {}, ""),
                          ("topk", dict(algo="scaffold"), "-scaffold"))])


@pytest.mark.parametrize("name,opts", _PARITY_CASES)
def test_chunked_round_bitwise_parity(name, opts):
    opts = dict(opts)
    chunk = opts.pop("chunk", CHUNK)
    _assert_rounds_equal(_round_outputs(name, chunk, **opts),
                         _round_outputs(name, None, **opts))


@pytest.mark.parametrize("name", ["topk", "randk", "rtopk"])
def test_chunked_parity_sparse_ef(name):
    _assert_rounds_equal(_round_outputs(name, CHUNK, ef_mode="sparse"),
                         _round_outputs(name, None, ef_mode="sparse"))


def test_chunked_parity_bf16_state():
    _assert_rounds_equal(
        _round_outputs("topk", CHUNK, state_dtype=jnp.bfloat16),
        _round_outputs("topk", None, state_dtype=jnp.bfloat16))


def test_chunked_parity_scaffold_ctrl():
    _assert_rounds_equal(_round_outputs("topk", CHUNK, algo="scaffold"),
                         _round_outputs("topk", None, algo="scaffold"))


def test_chunked_parity_double_ef_and_participation():
    _assert_rounds_equal(
        _round_outputs("topk", CHUNK, double_ef=True, with_part=True),
        _round_outputs("topk", None, double_ef=True, with_part=True))


def test_chunk_ge_n_degenerates_to_unchunked():
    _assert_rounds_equal(_round_outputs("topk", 16),
                         _round_outputs("topk", None))


@pytest.mark.parametrize("privacy", ["secagg", "dp", "secagg_dp"])
def test_chunked_parity_with_privacy(privacy):
    """The chunked client pass stays bitwise chunk-invariant with privacy
    transforms active: per-client masks/noise key off absolute client ids
    (domain-separated fold_in), not chunk-local positions, and the uint32
    field sum is exactly associative."""
    _assert_rounds_equal(_round_outputs("none", CHUNK, privacy=privacy),
                         _round_outputs("none", None, privacy=privacy))


def test_chunked_parity_privacy_composes_with_compression():
    """secagg over a field-compatible compressor (sign) is chunk-invariant
    too — EF and the mask prepass both ride the chunked scan."""
    _assert_rounds_equal(_round_outputs("sign", CHUNK, privacy="secagg"),
                         _round_outputs("sign", None, privacy="secagg"))


def test_wrong_state_rows_raises():
    params, loss_fn, make_batches, _ = _problem()
    batches = jax.tree.map(jnp.asarray, make_batches(0, N))
    state = server.init_fl_state(params, N, use_ef=True)  # n_rows = N
    with pytest.raises(ValueError, match="n_rows"):
        server.fl_round(state, batches, loss_fn, aparams=AP01,
                        compression_name="topk",
                        compress_fn=compression.get_compressor("topk"),
                        cparams=compression_params(),
                        key=jax.random.PRNGKey(0), chunk_size=CHUNK,
                        n_clients=N)


# ---------------------------------------------------------------------------
# on-device data generation
# ---------------------------------------------------------------------------
def test_datagen_rows_are_chunk_invariant():
    """Row i depends only on (key, ids[i]) — the contract that makes the
    chunked and unchunked passes see identical per-client batches."""
    _, _, _, w_star = _problem()
    dg = make_linear_datagen(w_star, local_steps=2, batch=4)
    key = jax.random.PRNGKey(11)
    full = dg(key, jnp.arange(8))
    part = dg(key, jnp.arange(3, 8))
    np.testing.assert_array_equal(np.asarray(full["x"][3:]),
                                  np.asarray(part["x"]))
    np.testing.assert_array_equal(np.asarray(full["y"][3:]),
                                  np.asarray(part["y"]))


def test_datagen_matches_host_sampler_statistics():
    """Same moments as make_linear_problem's host sampler: x ~ N(0, 1),
    y - x @ w* ~ N(0, noise^2)."""
    _, _, _, w_star = _problem()
    dg = make_linear_datagen(w_star, local_steps=2, batch=64, noise=0.01)
    got = dg(jax.random.PRNGKey(0), jnp.arange(256))
    x = np.asarray(got["x"])
    resid = np.asarray(got["y"]) - x @ np.asarray(w_star)
    assert abs(x.mean()) < 0.01 and abs(x.std() - 1.0) < 0.01
    assert abs(resid.std() - 0.01) < 0.002


def test_engine_datagen_matches_prestacked_bitwise():
    """A datagen+chunked run == an unchunked run fed the pre-materialized
    pytree of exactly what the datagen produces each round."""
    params, loss_fn, _, w_star = _problem()
    dg = make_linear_datagen(w_star, local_steps=2, batch=4)
    rounds, seed = 3, 0
    cfg_dg = rt.SimConfig(n_devices=N, n_scheduled=4, rounds=rounds,
                          seed=seed, algo_params=AP01, compression="topk",
                          chunk_size=CHUNK, datagen=dg)
    p_dg, logs_dg = rt.run_simulation_scan(
        cfg_dg, loss_fn, jax.tree.map(jnp.array, params))

    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[dg(rt.datagen_round_key(seed, t), jnp.arange(N))
          for t in range(rounds)])
    cfg_pre = rt.SimConfig(n_devices=N, n_scheduled=4, rounds=rounds,
                           seed=seed, algo_params=AP01, compression="topk")
    p_pre, logs_pre = rt.run_simulation_scan(
        cfg_pre, loss_fn, jax.tree.map(jnp.array, params), stacked)

    np.testing.assert_array_equal(logs_dg.loss, logs_pre.loss)
    np.testing.assert_array_equal(logs_dg.uplink_bits, logs_pre.uplink_bits)
    np.testing.assert_array_equal(logs_dg.latency_s, logs_pre.latency_s)
    for a, b in zip(jax.tree.leaves(p_dg), jax.tree.leaves(p_pre)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_sparse_bf16_runs_finite():
    params, loss_fn, _, w_star = _problem()
    dg = make_linear_datagen(w_star, local_steps=2, batch=4)
    cfg = rt.SimConfig(n_devices=N, n_scheduled=4, rounds=3,
                       algo_params=AP01, compression="topk",
                       chunk_size=CHUNK, datagen=dg, ef_mode="sparse",
                       state_dtype="bfloat16")
    _, logs = rt.run_simulation_scan(cfg, loss_fn,
                                     jax.tree.map(jnp.array, params))
    assert np.all(np.isfinite(logs.loss))


@pytest.mark.parametrize("algo,comp", [("fedavg", "topk"),
                                       ("scaffold", "topk"),
                                       ("fedbuff", "none")])
def test_engine_chunk_parity_with_faults(algo, comp):
    """Fault draws are keyed per-client (fold constants off the round key),
    so the chunked client pass reproduces the unchunked engine bitwise
    with churn + dropout + stragglers + retransmissions enabled."""
    from repro.core.faults import fault_params
    params, loss_fn, make_batches, _ = _problem()
    rounds = 4
    batches = rt.stack_batches(make_batches, rounds, N)
    faults = fault_params(drop_prob=0.3, churn_p_off=0.2, churn_p_on=0.6,
                          straggler_prob=0.3, snr_min=2.0, fading_rho=0.7)
    out = {}
    for chunk in (None, CHUNK):
        cfg = rt.SimConfig(n_devices=N, n_scheduled=4, rounds=rounds,
                           seed=9, algo_params=AP01, algorithm=algo,
                           compression=comp, chunk_size=chunk,
                           faults=faults, max_retries=2)
        out[chunk] = rt.run_simulation_scan(
            cfg, loss_fn, jax.tree.map(jnp.array, params), batches)
    p_u, l_u = out[None]
    p_c, l_c = out[CHUNK]
    np.testing.assert_array_equal(l_u.loss, l_c.loss)
    np.testing.assert_array_equal(l_u.latency_s, l_c.latency_s)
    np.testing.assert_array_equal(l_u.n_survived, l_c.n_survived)
    np.testing.assert_array_equal(l_u.retransmissions, l_c.retransmissions)
    for a, b in zip(jax.tree.leaves(p_u), jax.tree.leaves(p_c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scan_engine_requires_batches_or_datagen():
    params, loss_fn, _, _ = _problem()
    cfg = rt.SimConfig(n_devices=N, n_scheduled=4, rounds=2,
                       algo_params=AP01)
    with pytest.raises(ValueError, match="datagen"):
        rt.run_simulation_scan(cfg, loss_fn, params)


# ---------------------------------------------------------------------------
# memory boundedness (the point of chunking)
# ---------------------------------------------------------------------------
def test_chunking_bounds_compiled_temp_memory():
    """XLA's temp-buffer estimate for the chunked engine is a fraction of
    the unchunked one at the same fleet size (O(chunk*D) vs O(N*D))."""
    params, loss_fn, _, w_star = _problem()
    dg = make_linear_datagen(w_star, local_steps=2, batch=4)

    def temp_bytes(chunk):
        cfg = rt.SimConfig(n_devices=2048, n_scheduled=64, rounds=2,
                           algo_params=AP01, compression="topk",
                           chunk_size=chunk, datagen=dg)
        wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
        engine = rt._make_sim_fns(cfg, wcfg, loss_fn, False).engine
        lowered = jax.jit(engine).lower(
            jax.random.PRNGKey(0), rt.wireless.channel_params(wcfg),
            rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg),
            jax.tree.map(jnp.array, params), None, None)
        return lowered.compile().memory_analysis().temp_size_in_bytes

    assert temp_bytes(128) < temp_bytes(None) / 2


# ---------------------------------------------------------------------------
# hierarchical per-cluster budgets
# ---------------------------------------------------------------------------
HCFG = HFLConfig(n_clusters=3, inter_cluster_period=3)


def _hfl_logs(n_scheduled, policy="random"):
    params, loss_fn, make_batches, _ = _problem()
    cfg = rt.SimConfig(n_devices=12, n_scheduled=n_scheduled, rounds=6,
                       algo_params=AP01, policy=policy, seed=3)
    return rt.run_hfl(cfg, HCFG, loss_fn, params, make_batches)


@pytest.mark.parametrize("policy", ["random", "round_robin", "best_channel"])
def test_uniform_tuple_budget_matches_scalar(policy):
    scalar = _hfl_logs(2, policy)
    tup = _hfl_logs((2, 2, 2), policy)
    for s, h in zip(scalar, tup):
        np.testing.assert_array_equal(s.participation, h.participation)
        assert s.loss == h.loss and s.uplink_bits == h.uplink_bits


def test_heterogeneous_budgets_respected_per_cluster():
    from repro.core.hierarchy import hfl_geometry_jax
    logs = _hfl_logs((1, 2, 3))
    # reconstruct the engine's deployment: geometry comes from the first
    # split of PRNGKey(seed) (seed=3 in _hfl_logs)
    k_geo, _ = jax.random.split(jax.random.PRNGKey(3))
    cluster_ids = np.asarray(hfl_geometry_jax(k_geo, HCFG, 12)[0])
    sizes = np.bincount(cluster_ids, minlength=3)
    caps = np.minimum([1, 2, 3], sizes)
    for log in logs:
        mask = np.asarray(log.participation)
        for cl in range(3):
            assert mask[cluster_ids == cl].sum() == caps[cl]


def test_flat_engine_rejects_tuple_budget():
    params, loss_fn, make_batches, _ = _problem()
    cfg = rt.SimConfig(n_devices=12, n_scheduled=(2, 2, 2), rounds=2,
                       algo_params=AP01)
    with pytest.raises(ValueError, match="hierarchical"):
        rt.run_simulation(cfg, loss_fn, params, make_batches, engine="scan")


def test_hfl_rejects_wrong_length_tuple():
    params, loss_fn, make_batches, _ = _problem()
    cfg = rt.SimConfig(n_devices=12, n_scheduled=(2, 2), rounds=2,
                       algo_params=AP01)
    with pytest.raises(ValueError, match="one budget per cluster"):
        rt.run_hfl(cfg, HCFG, loss_fn, params, make_batches)


# ---------------------------------------------------------------------------
# row-batched kernel dispatch API
# ---------------------------------------------------------------------------
def test_rows_kernels_jit_matches_interpret():
    from repro.kernels import qsgd_rows, sign_ef_rows, topk_rows
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 256))
    u = jax.random.uniform(jax.random.PRNGKey(6), x.shape)
    e = 0.1 * jax.random.normal(jax.random.PRNGKey(8), x.shape)

    np.testing.assert_allclose(
        np.asarray(topk_rows(x, 8, mode="jit")),
        np.asarray(topk_rows(x, 8, mode="interpret")), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(qsgd_rows(x, u, 16, mode="jit")),
        np.asarray(qsgd_rows(x, u, 16, mode="interpret")),
        rtol=1e-5, atol=1e-6)
    cj, ej = sign_ef_rows(x, e, mode="jit")
    ci, ei = sign_ef_rows(x, e, mode="interpret")
    np.testing.assert_allclose(np.asarray(cj), np.asarray(ci),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ej), np.asarray(ei),
                               rtol=1e-5, atol=1e-6)


def test_rows_kernels_tile_wide_ragged_rows():
    """Rows wider than one column block, with ragged row and column edges:
    the column-tiled kernels agree with their jnp mirrors."""
    from repro.kernels import ops, qsgd_rows, sign_ef_rows, topk_rows
    shape = (3, 2 * ops._BLOCK_COLS + 1000)
    x = jax.random.normal(jax.random.PRNGKey(5), shape)
    u = jax.random.uniform(jax.random.PRNGKey(6), shape)
    e = 0.1 * jax.random.normal(jax.random.PRNGKey(8), shape)
    k = shape[1] // 100

    np.testing.assert_array_equal(
        np.asarray(topk_rows(x, k, mode="jit")),
        np.asarray(topk_rows(x, k, mode="interpret")))
    np.testing.assert_allclose(
        np.asarray(qsgd_rows(x, u, 16, mode="jit")),
        np.asarray(qsgd_rows(x, u, 16, mode="interpret")),
        rtol=1e-5, atol=1e-6)
    for got, want in zip(sign_ef_rows(x, e, mode="interpret"),
                         sign_ef_rows(x, e, mode="jit")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_rows_topk_accepts_traced_k():
    from repro.kernels import topk_rows
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 128))
    out = jax.jit(topk_rows)(x, jnp.float32(4.0))
    nnz = np.count_nonzero(np.asarray(out), axis=1)
    assert (nnz == 4).all()  # exact top-k: no ties in normal draws
