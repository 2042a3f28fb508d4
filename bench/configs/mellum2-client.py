"""Mellum2-12B-A2.5B as every client's model (``mellum2-client.json``), one
chip's share of an 8-chip deployment: layers 0-3 (sliding, sliding,
sliding, full), 4 of the 32 query heads and 1 of the 4 KV heads, experts
0-7 of the 64 that the router scores, 12,288 of the 98,304 ids.

The program runs it through its own MoE decoder (``repro.models``,
``family="moe"`` with a ``block_pattern``); the plain reference below
writes the same network out in ``jax.numpy``: pre-norm blocks with RMSNorm,
grouped-query attention with no biases, plain RoPE and a causal window in
the sliding layers and YaRN RoPE over the whole context in the full ones,
a softmax router over all 64 experts whose top 8 gates are renormalized
over the 8 chosen, the held experts' SwiGLU outputs weighted by their gates
(computed densely: every held expert on every token, times its gate, zero
where the token did not choose it), a final RMSNorm and an untied output
head. What the experts, heads and vocabulary held on the other chips would
add is left out on both sides. The weights are drawn here, from the run's
seed, in the program's parameter layout (one stack of all the layers).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def _sizes(conf):
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["moe_intermediate_size"],
            conf["num_experts"], conf["vocab_size"])


def _period(conf):
    """The layer kinds of one period: up to and with the first full layer."""
    kinds = [KINDS[t] for t in conf["layer_types"]]
    return tuple(kinds[:kinds.index("full") + 1])


def init_params(conf, key):
    """Random weights from ``key``: normal(0, initializer_range) matrices,
    unit RMSNorm scales; float32."""
    n_layer, d, n_head, n_kv, hd, d_exp, held, vocab = _sizes(conf)
    std = conf["initializer_range"]
    ks = iter(jax.random.split(key, 10))

    def normal(shape):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    def layers():
        lead = (n_layer,)
        return {
            "norm1": {"scale": jnp.ones(lead + (d,), jnp.float32)},
            "attn": {"wq": normal(lead + (d, n_head * hd)),
                     "wk": normal(lead + (d, n_kv * hd)),
                     "wv": normal(lead + (d, n_kv * hd)),
                     "wo": normal(lead + (n_head * hd, d))},
            "norm2": {"scale": jnp.ones(lead + (d,), jnp.float32)},
            "mlp": {"router": normal(lead + (d, conf["num_experts_routed"])),
                    "w_gate": normal(lead + (held, d, d_exp)),
                    "w_up": normal(lead + (held, d, d_exp)),
                    "w_down": normal(lead + (held, d_exp, d))},
        }

    return {
        "embed": normal((vocab, d)),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "lm_head": normal((d, vocab)),
        "blocks": layers(),
    }


def _yarn(conf):
    y = conf["rope_parameters"]["full_attention"]
    return (float(y["factor"]), float(y["original_max_position_embeddings"]),
            float(y["beta_fast"]), float(y["beta_slow"]),
            float(y["attention_factor"]))


def program_loss(conf):
    """The system under test: the program's MoE decoder and loss."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as tf
    n_layer, d, n_head, n_kv, hd, d_exp, held, vocab = _sizes(conf)
    rope = conf["rope_parameters"]
    if not conf["norm_topk_prob"] or conf["hidden_act"] != "silu" or len(
            {r["rope_theta"] for r in rope.values()}) != 1:
        raise ValueError("the program's MoE decoder renormalizes the top-k "
                         "gates, uses SwiGLU and one RoPE theta")
    mcfg = ModelConfig(
        name=conf["name"], family="moe", source=conf["source"],
        n_layers=n_layer, d_model=d, n_heads=n_head, n_kv_heads=n_kv,
        head_dim=hd, d_ff=conf["intermediate_size"], vocab_size=vocab,
        mlp_type="swiglu", norm_type="rmsnorm",
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn=_yarn(conf), block_pattern=_period(conf),
        sliding_window=conf["sliding_window"],
        n_experts=conf["num_experts_routed"], n_experts_held=held,
        moe_top_k=conf["num_experts_per_tok"], d_ff_expert=d_exp,
        router_aux_weight=0.0, dtype=conf["dtype"])
    remat = bool(conf.get("remat", True))

    def loss_fn(params, batch):
        return tf.lm_loss(params, mcfg, batch, remat=remat)
    return loss_fn


def yarn_inv_freq(hd, theta, factor, original, beta_fast, beta_slow):
    """YaRN's blended inverse frequencies (Peng et al. 2023, section 3.2):
    extrapolated where a dimension turns more than ``beta_fast`` times over
    the original context, interpolated by ``factor`` where it turns fewer
    than ``beta_slow`` times, a linear ramp between, the bounds floored and
    ceiled to whole dimensions."""
    def dim(turns):
        return hd * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), hd - 1)
    extra = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * extra + ramp * extra / factor


def reference_loss(conf):
    """Plain forward and mean token cross-entropy: ``loss(params, batch)``,
    computed in the dtype of ``params``."""
    n_layer, d, n_head, n_kv, hd, d_exp, held, vocab = _sizes(conf)
    eps = conf["rms_norm_eps"]
    top_k = conf["num_experts_per_tok"]
    window = conf["sliding_window"]
    kinds = [KINDS[t] for t in conf["layer_types"]]
    rope = conf["rope_parameters"]
    y = rope["full_attention"]
    freqs = {
        "sliding": (1.0 / rope["sliding_attention"]["rope_theta"] ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd), 1.0),
        "full": (yarn_inv_freq(hd, y["rope_theta"], y["factor"],
                               y["original_max_position_embeddings"],
                               y["beta_fast"], y["beta_slow"]),
                 y["attention_factor"])}

    def rms_norm(x, p):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + eps) * p["scale"]

    def rotate(x, kind):
        inv, scale = freqs[kind]
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
        cos = (jnp.cos(ang) * scale)[None, :, None, :].astype(x.dtype)
        sin = (jnp.sin(ang) * scale)[None, :, None, :].astype(x.dtype)
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def attention(h, p, kind):
        b, s, _ = h.shape
        q = rotate((h @ p["wq"]).reshape(b, s, n_head, hd), kind)
        k = rotate((h @ p["wk"]).reshape(b, s, n_kv, hd), kind)
        v = (h @ p["wv"]).reshape(b, s, n_kv, hd)
        k = jnp.repeat(k, n_head // n_kv, axis=2)
        v = jnp.repeat(v, n_head // n_kv, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        lag = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        seen = (lag >= 0) & ((lag < window) if kind == "sliding" else True)
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, n_head * hd)
        return o @ p["wo"]

    def experts(h, p):
        b, s, _ = h.shape
        x = h.reshape(b * s, d)
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        top_p, top_e = jax.lax.top_k(probs, top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(b * s)[:, None], top_e].set(top_p)[:, :held]
        g = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"]))
        u = jnp.einsum("td,edf->etf", x, p["w_up"])
        out = jnp.einsum("etf,efd->etd", g * u, p["w_down"])
        return jnp.einsum("te,etd->td", gates, out).reshape(b, s, d)

    def layer(x, p, kind):
        x = x + attention(rms_norm(x, p["norm1"]), p["attn"], kind)
        return x + experts(rms_norm(x, p["norm2"]), p["mlp"])

    def loss(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        x = params["embed"][tokens]
        for j, kind in enumerate(kinds):
            x = layer(x, jax.tree.map(lambda a: a[j], params["blocks"]), kind)
        logits = rms_norm(x, params["final_norm"]) @ params["lm_head"]
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
    return loss


def model_flops_per_token(conf, seq: int) -> float:
    """Matrix-product FLOPs of forward and backward (3x forward) per
    trained token: q, k, v and o projections, attention scores and values
    over the keys a query sees (the whole sequence in a full layer, the
    window in a sliding one), the router, the held experts' expected work
    (top-8 of 64 experts, 8 held: 8 x 8/64 expert products a token) and the
    output head. The embedding gather and recomputation are not counted."""
    n_layer, d, n_head, n_kv, hd, d_exp, held, vocab = _sizes(conf)
    n_exp, top_k = conf["num_experts_routed"], conf["num_experts_per_tok"]
    qkvo = 2 * d * (2 * n_head + 2 * n_kv) * hd
    router = 2 * d * n_exp
    expert = 3 * 2 * d * d_exp * top_k * held / n_exp
    total = 0.0
    for t in conf["layer_types"]:
        keys = seq if KINDS[t] == "full" else min(seq, conf["sliding_window"])
        total += qkvo + router + expert + 2 * 2 * keys * n_head * hd
    return 3.0 * (total + 2 * d * vocab)
