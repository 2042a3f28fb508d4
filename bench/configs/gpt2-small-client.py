"""GPT-2 small as every client's model (``gpt2-small-client.json``).

The program runs it through its own dense decoder (``repro.models``); the
plain reference below writes the same network out in ``jax.numpy``,
departures included: pre-norm blocks, learned positions, causal softmax
attention with no projection biases, a tanh-GELU MLP with biases, a final
layer norm, and the token embedding tied to the output layer and scaled by
sqrt(n_embd) on input. The weights are drawn here, from the run's seed, in
the program's parameter layout (layers stacked on a leading axis).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _sizes(conf):
    return (conf["n_layer"], conf["n_embd"], conf["n_head"],
            conf["head_dim"], conf["n_inner"], conf["vocab_size"],
            conf["n_positions"])


def init_params(conf, key):
    """Random weights from ``key``: normal(0, initializer_range) matrices and
    tables, unit layer-norm scales, zero biases; float32."""
    n_layer, d, n_head, hd, d_ff, vocab, n_pos = _sizes(conf)
    std = conf["initializer_range"]
    ks = iter(jax.random.split(key, 8))

    def normal(shape):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    def norm(*lead):
        return {"scale": jnp.ones(lead + (d,), jnp.float32),
                "bias": jnp.zeros(lead + (d,), jnp.float32)}

    return {
        "embed": normal((vocab, d)),
        "pos_embed": normal((n_pos, d)),
        "final_norm": norm(),
        "blocks": {
            "norm1": norm(n_layer),
            "norm2": norm(n_layer),
            "attn": {"wq": normal((n_layer, d, n_head * hd)),
                     "wk": normal((n_layer, d, n_head * hd)),
                     "wv": normal((n_layer, d, n_head * hd)),
                     "wo": normal((n_layer, n_head * hd, d))},
            "mlp": {"w_up": normal((n_layer, d, d_ff)),
                    "b_up": jnp.zeros((n_layer, d_ff), jnp.float32),
                    "w_down": normal((n_layer, d_ff, d)),
                    "b_down": jnp.zeros((n_layer, d), jnp.float32)},
        },
    }


def program_loss(conf):
    """The system under test: the program's dense decoder and loss."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as tf
    n_layer, d, n_head, hd, d_ff, vocab, n_pos = _sizes(conf)
    mcfg = ModelConfig(
        name=conf["name"], family="dense", source=conf["source"],
        n_layers=n_layer, d_model=d, n_heads=n_head, n_kv_heads=n_head,
        head_dim=hd, d_ff=d_ff, vocab_size=vocab, mlp_type="gelu",
        norm_type="layernorm", tie_embeddings=True, use_rope=False,
        pos_embed="learned", max_position=n_pos, dtype=conf["dtype"])
    remat = bool(conf.get("remat", True))

    def loss_fn(params, batch):
        return tf.lm_loss(params, mcfg, batch, remat=remat)
    return loss_fn


def reference_loss(conf):
    """Plain forward and mean token cross-entropy: ``loss(params, batch)``,
    computed in the dtype of ``params``."""
    n_layer, d, n_head, hd, d_ff, vocab, n_pos = _sizes(conf)
    # the program's epsilon: a departure from GPT-2's (see the json)
    eps = conf["departures"]["layer_norm_epsilon"]

    def layer_norm(x, p):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]

    def block(x, p):
        b, s, _ = x.shape
        h = layer_norm(x, p["norm1"])
        q = (h @ p["attn"]["wq"]).reshape(b, s, n_head, hd)
        k = (h @ p["attn"]["wk"]).reshape(b, s, n_head, hd)
        v = (h @ p["attn"]["wv"]).reshape(b, s, n_head, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, n_head * hd)
        x = x + o @ p["attn"]["wo"]
        h = layer_norm(x, p["norm2"])
        up = jax.nn.gelu(h @ p["mlp"]["w_up"] + p["mlp"]["b_up"],
                         approximate=True)
        return x + up @ p["mlp"]["w_down"] + p["mlp"]["b_down"], None

    def loss(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        s = tokens.shape[-1]
        emb = params["embed"]
        x = emb[tokens] * jnp.asarray(math.sqrt(d), emb.dtype)
        x = x + params["pos_embed"][:s]
        x, _ = jax.lax.scan(block, x, params["blocks"])
        logits = layer_norm(x, params["final_norm"]) @ emb.T
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
    return loss


def model_flops_per_token(conf, seq: int) -> float:
    """Matrix-product FLOPs of forward and backward (3x forward) per
    trained token: the q, k, v, o and MLP projections, the attention scores
    and values over the whole sequence, and the tied output layer. The
    embedding gather and recomputation are not counted."""
    n_layer, d, n_head, hd, d_ff, vocab, n_pos = _sizes(conf)
    qkvo = 2 * d * 3 * n_head * hd + 2 * n_head * hd * d
    mlp = 2 * 2 * d * d_ff
    attn = 2 * 2 * seq * n_head * hd
    return 3.0 * (n_layer * (qkvo + mlp + attn) + 2 * d * vocab)
