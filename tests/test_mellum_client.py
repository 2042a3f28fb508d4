"""The Mellum2-12B-A2.5B client (``bench/configs/mellum2-client.*``) at a
tiny size on the CPU, on seeded random weights: the program's patterned MoE
decoder against the plain reference, the chip's share of a layer against
the uncut layer, routing that a capacity limit would have cut, YaRN against
its formula, and the whole engine against ``bench/reference.py``."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness
from bench.tests import tiny, tiny_mellum
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import transformer as tf
from repro.models.layers import apply_rope, yarn_frequencies

CONF = tiny_mellum.conf()


@pytest.fixture(scope="module")
def mod():
    _, module = harness.load_config("mellum2-client")
    return module


def _batch(seed=0, b=2, s=64, vocab=CONF["vocab_size"]):
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (b, s)),
                       jnp.int32)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}


def _moe_cfg(held, e=16, k=4, d=64, f=32):
    return ModelConfig(name="moe-tiny", family="moe", source="test",
                       n_layers=1, d_model=d, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=f, vocab_size=128, n_experts=e,
                       n_experts_held=held, moe_top_k=k, d_ff_expert=f,
                       dtype="float32")


def _dense_moe(p, x, cfg):
    """Every expert of ``p`` on every token, times its renormalized top-k
    gate (zero where the token did not pick it); ``p`` holds all experts."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], top_e].set(top_p)
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", xf, p["w_gate"]))
         * jnp.einsum("td,edf->etf", xf, p["w_up"]))
    out = jnp.einsum("etf,efd->etd", h, p["w_down"])
    return jnp.einsum("te,etd->td", gates, out).reshape(x.shape)


def test_program_loss_and_gradients_match_the_reference(mod):
    params = mod.init_params(CONF, jax.random.PRNGKey(0))
    batch = _batch()
    loss_p, g_p = jax.value_and_grad(
        lambda p: mod.program_loss(CONF)(p, batch)[0])(params)
    with jax.default_matmul_precision("highest"):
        loss_r, g_r = jax.value_and_grad(mod.reference_loss(CONF))(params,
                                                                   batch)
    # float32 on both sides, summed in other orders (grouped against dense
    # expert products, chunked against whole-sequence softmax): about 1e-5
    # of each leaf's largest gradient on this size; bfloat16 anywhere on
    # the path would move them by 1e-3 or more
    assert abs(float(loss_p) - float(loss_r)) <= 1e-5 * abs(float(loss_r))
    for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_r)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale


def _take_experts(p, lo, n):
    return dict(p, **{k: p[k][lo:lo + n] for k in ("w_gate", "w_up",
                                                   "w_down")})


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Two chips of 8 experts each, both routing over all 16: their partial
    outputs add up to the layer that holds all 16, and to the experts
    applied densely with their gates."""
    whole = _moe_cfg(held=16)
    share = dataclasses.replace(whole, n_experts_held=8)
    p = moe_mod.init_moe_block(jax.random.PRNGKey(1), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    parts = [moe_mod.routed_experts(_take_experts(p, lo, 8), x.reshape(-1, 64),
                                    share, first_expert=lo)[0].reshape(x.shape)
             for lo in (0, 8)]
    uncut, _ = moe_mod.moe_forward(p, x, whole)
    # float32 sums of the same products in another order
    np.testing.assert_allclose(parts[0] + parts[1], uncut, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(uncut, _dense_moe(p, x, whole), rtol=1e-5,
                               atol=1e-5)
    assert all(float(jnp.max(jnp.abs(q))) > 0 for q in parts)


def test_the_head_shares_add_up_to_the_uncut_attention():
    """Four chips of 2 query heads each, KV head h // 4 beside them (each
    of the 2 KV heads on 2 chips): their attention outputs, through their
    rows of the output projection, add up to the uncut layer."""
    n_heads, n_kv, hd, d = 8, 2, 16, 64
    p = attn.init_attention(jax.random.PRNGKey(3), d, n_heads, n_kv, hd,
                            jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 48, d))
    kw = dict(head_dim=hd, use_rope=True, rope_theta=5e5,
              yarn=(16.0, 8192.0, 32.0, 1.0, 1.2772588722239782), window=16)
    uncut = attn.self_attention(p, x, n_heads=n_heads, n_kv_heads=n_kv, **kw)
    total = 0.0
    for chip in range(4):
        q = slice(2 * chip * hd, (2 * chip + 2) * hd)
        kv = slice((chip // 2) * hd, (chip // 2 + 1) * hd)
        part = {"wq": p["wq"][:, q], "wk": p["wk"][:, kv],
                "wv": p["wv"][:, kv], "wo": p["wo"][q, :]}
        total = total + attn.self_attention(part, x, n_heads=2, n_kv_heads=1,
                                            **kw)
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)


def test_skewed_routing_drops_no_token():
    """Every token picks expert 3 first: 4x the tokens an expert would get
    under even routing, past any capacity of 1.25x that; each still gets
    expert 3's output, as the dense computation gives it."""
    cfg = _moe_cfg(held=8)
    p = moe_mod.init_moe_block(jax.random.PRNGKey(5), cfg, jnp.float32)
    # positive inputs, and a router column that sums them
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 32, 64)))
    p = dict(p, router=p["router"].at[:, 3].set(1.0))
    xf = x.reshape(-1, 64)
    _, top_e = jax.lax.top_k(xf @ p["router"], cfg.moe_top_k)
    assert bool(jnp.all(top_e[:, 0] == 3))
    t = xf.shape[0]
    assert t > 1.25 * cfg.moe_top_k * t / cfg.n_experts
    out, _ = moe_mod.moe_forward(p, x, cfg)
    whole = dict(p, **{k: jnp.concatenate([p[k], jnp.zeros_like(p[k])])
                       for k in ("w_gate", "w_up", "w_down")})
    np.testing.assert_allclose(out, _dense_moe(whole, x, cfg), rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.min(jnp.linalg.norm(out.reshape(t, 64), axis=-1))) > 0


def test_prefill_and_decode_match_the_forward_pass():
    """A patterned MoE trunk (two periods of sliding x3 / full, window 4)
    serves as it trains: the prefill's last logits, and those of each
    decode step past the window over a cache longer than it, equal the
    forward pass's at the same positions."""
    from repro.launch.serve import _load_prefill
    cfg = dataclasses.replace(
        _moe_cfg(held=16), n_layers=8, sliding_window=4, rope_theta=5e5,
        block_pattern=("sliding", "sliding", "sliding", "full"),
        yarn=(16.0, 8192.0, 32.0, 1.0, 1.2772588722239782))
    params = tf.init_params(cfg, jax.random.PRNGKey(8))
    toks = _batch(seed=9, s=16, vocab=cfg.vocab_size)["tokens"]
    h, _, _ = tf.forward_trunk(params, cfg, toks, remat=False)
    full = tf.unembed(params, cfg, h)
    logits, pf_cache = tf.prefill(params, cfg, toks[:, :10])
    # float32 sums of the same products in another order
    np.testing.assert_allclose(logits[:, 0], full[:, 9], rtol=1e-4,
                               atol=1e-5)
    cache = _load_prefill(cfg, tf.init_decode_cache(cfg, 2, 32), pf_cache, 10)
    for pos in range(10, 16):
        logits, cache = tf.decode_step(params, cfg, cache,
                                       toks[:, pos:pos + 1], jnp.int32(pos))
        np.testing.assert_allclose(logits[:, 0], full[:, pos], rtol=1e-4,
                                   atol=1e-5)


def test_yarn_frequencies_and_scale_match_the_formula():
    """Mellum2's full layers: theta 5e5, factor 16, original 8192 positions,
    beta_fast 32, beta_slow 1, head_dim 128. The ramp runs from dimension
    pair floor(18.08) = 18 to ceil(34.98) = 35: below it the frequencies are
    RoPE's, above it RoPE's / 16, between a linear blend; the scale is
    0.1 ln 16 + 1."""
    yarn = (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782)
    inv, scale = yarn_frequencies(128, 5e5, yarn)
    plain = 5e5 ** (-np.arange(0, 128, 2) / 128)
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    expected = plain * (1 - ramp) + plain / 16 * ramp
    np.testing.assert_allclose(inv, expected, rtol=1e-6)
    assert inv[0] == 1.0 and inv[18] == pytest.approx(plain[18], rel=1e-6)
    assert inv[35] == pytest.approx(plain[35] / 16, rel=1e-6)
    assert inv[26] == pytest.approx(plain[26] * (9 / 17 + 8 / 17 / 16),
                                    rel=1e-6)
    assert scale == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    # cos and sin carry the scale: a rotated vector's norm grows by it
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 5, 1, 128))
    rot = apply_rope(x, jnp.arange(5)[None], 5e5, yarn)
    np.testing.assert_allclose(jnp.linalg.norm(rot, axis=-1),
                               scale * jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_param_count_counts_the_experts_held(mod):
    params = jax.eval_shape(lambda k: mod.init_params(
        tiny_mellum.load("configs", "mellum2-client"), k),
        jax.random.PRNGKey(0))
    d = sum(math.prod(x.shape) for x in jax.tree.leaves(params))
    assert d == 267_211_008
    cfg = ModelConfig(name="m", family="moe", source="s", n_layers=4,
                      d_model=2304, n_heads=4, n_kv_heads=1, head_dim=128,
                      d_ff=7168, vocab_size=12288, n_experts=64,
                      n_experts_held=8, moe_top_k=8, d_ff_expert=896)
    # the analytic count leaves out the final norm's 2304 scales
    assert cfg.param_count() == d - 2304
    # top-8 of 64 experts, 8 held here: one held expert a token on average
    assert cfg.active_param_count() == cfg.param_count() - 4 * 7 * 3 * 2304 * 896
    for arch, n in (("qwen2-moe-a2.7b", 60), ("kimi-k2-1t-a32b", 384)):
        full = get_config(arch)
        assert full.experts_held == full.n_experts == n
        small = full.reduced()
        real = tf.init_params(small, jax.random.PRNGKey(0))
        # the stacks of all the experts are padded to shard evenly over the
        # production mesh's model axis; the padding is never routed to
        padding = ((moe_mod.expert_stack_size(small) - small.n_experts)
                   * 3 * small.d_model * small.d_ff_expert * small.n_layers)
        assert sum(x.size for x in jax.tree.leaves(real)) == pytest.approx(
            small.param_count() + padding, rel=0.01)


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("mellumbench")
    manifest = tiny_mellum.make_bench_dir(str(root))
    saved = harness.BENCH
    harness.BENCH = str(root / "bench")
    yield manifest
    harness.BENCH = saved


def test_run_simulation_scan_matches_the_reference_for_two_rounds(
        tiny_bench):
    cell = harness.build_cell(
        harness.find_workload(tiny_bench, tiny_mellum.CELL), 41)
    assert cell.sim["rounds"] == 2
    [(_, _, logs, final)] = cell.answers(1, cell.call(1))
    norms = np.asarray(cell.leaf_norms(final, cell.params0))
    ref = check.reference_call(cell, cell.call_seed(1))
    assert np.all(ref["norms"] > 0)
    readings = check.readings(logs, norms, ref, ref["norms"])
    assert {k: v for k, v in readings.items() if v > tiny.LIMITS[k]} == {}
