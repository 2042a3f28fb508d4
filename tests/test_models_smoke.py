"""Per-architecture smoke tests (required deliverable f): reduced variant of
each assigned family runs one forward/train step on CPU — shapes + no NaNs —
plus prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import transformer as tf

B, S = 2, 32


def _batch(cfg):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (B, S + 1))
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_vision_tokens, cfg.vision_dim)),
            jnp.float32)
    if cfg.family == "audio":
        batch["audio_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_audio_frames, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)

    loss, metrics = tf.lm_loss(params, cfg, batch, remat=False)
    assert loss.shape == ()
    assert not jnp.isnan(loss), arch
    assert float(loss) > 0

    # one SGD step reduces nothing catastrophically (grads finite)
    g = jax.grad(lambda p: tf.lm_loss(p, cfg, batch, remat=False)[0])(params)
    gn = [jnp.isnan(x).any() for x in jax.tree.leaves(g)]
    assert not any(bool(b) for b in gn), arch
    new = jax.tree.map(lambda p, gg: p - 0.01 * gg.astype(p.dtype), params, g)
    loss2, _ = tf.lm_loss(new, cfg, batch, remat=False)
    assert not jnp.isnan(loss2)


@pytest.mark.parametrize("arch", ARCHS)
def test_output_shapes(arch):
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    h, aux, _ = tf.forward_trunk(params, cfg, batch["tokens"], extras,
                                 remat=False)
    assert h.shape == (B, S, cfg.d_model), arch
    logits = tf.unembed(params, cfg, h)
    assert logits.shape == (B, S, cfg.vocab_size), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_shapes_and_finiteness(arch):
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    cache = tf.init_decode_cache(cfg, B, 64)
    tok = jnp.ones((B, 1), jnp.int32)
    logits, cache2 = tf.decode_step(params, cfg, cache, tok, jnp.int32(3))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert not jnp.isnan(logits).any(), arch
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma-2b", "falcon-mamba-7b",
                                  "recurrentgemma-2b", "stablelm-12b",
                                  "qwen2-moe-a2.7b"])
def test_prefill_decode_consistency(arch):
    """Teacher-forced forward logits at position t == decode-step logits after
    prefilling t tokens (the serving path computes the same function)."""
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 16)), jnp.int32)

    h, _, _ = tf.forward_trunk(params, cfg, toks, {}, remat=False)
    full_logits = tf.unembed(params, cfg, h)  # (B,16,V)

    # prefill first 15, decode token 15
    logits_p, pf_cache = tf.prefill(params, cfg, toks[:, :15], {})
    np.testing.assert_allclose(np.asarray(logits_p[:, 0]),
                               np.asarray(full_logits[:, 14]),
                               rtol=2e-2, atol=2e-3)

    from repro.launch.serve import _load_prefill
    cache = tf.init_decode_cache(cfg, B, 64)
    cache = _load_prefill(cfg, cache, pf_cache, 15)
    logits_d, _ = tf.decode_step(params, cfg, cache, toks[:, 15:16],
                                 jnp.int32(15))
    np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                               np.asarray(full_logits[:, 15]),
                               rtol=2e-2, atol=2e-3)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "recurrentgemma-2b",
                                  "gemma-2b"])
def test_long_context_circular_decode(arch):
    """Sliding/constant-state decode keeps working past the window size."""
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    window = 16
    cache = tf.init_decode_cache(cfg, B, window, sliding=True)
    tok = jnp.ones((B, 1), jnp.int32)
    for pos in [0, 5, window - 1, window, 3 * window + 2]:
        logits, cache = tf.decode_step(params, cfg, cache, tok,
                                       jnp.int32(pos), circular=True)
        assert not jnp.isnan(logits).any(), (arch, pos)


def test_param_count_analytic_close_to_actual():
    """Analytic param_count (used for MODEL_FLOPS) within 5% of real count."""
    for arch in ("gemma-2b", "stablelm-12b", "falcon-mamba-7b"):
        cfg = get_config(arch).reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.05, (arch, actual, analytic)
