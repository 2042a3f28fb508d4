"""The one traffic generator: builds a cell's on-device client data from its
traffic file (``bench/traffic/<traffic>.json``, key ``data``) and the run's
seed.

The engine calls ``datagen(key, ids)`` once per round and block of clients,
inside its compiled program, and expects leaves of shape
``(len(ids), local_steps, ...)``. Row ``i`` depends only on
``(key, ids[i])``: each row has its own key ``fold_in(key, id)``, so the
data of a client does not depend on how clients are blocked.

Kinds:

``linear``  noisy linear regression toward a fixed target ``w_star ~
            N(0, 1)`` drawn from the traffic's ``target_seed`` (a constant
            of the compiled program, the same for every run seed):
            ``x ~ N(0, 1)`` of shape (local_steps, batch, d),
            ``y = x @ w_star + noise * N(0, 1)``.
``tokens``  language-model batches: tokens uniform over the vocabulary,
            half of them moved into the band of the client's class
            (``id mod n_classes``), labels the tokens shifted by one
            (``roll(tokens, -1)``).
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp


def _row_keys(key, ids):
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)


def make_datagen(spec: Dict, conf: Dict) -> Callable:
    """``spec``: the traffic file's ``data`` object; ``conf``: the
    configuration (sizes). The data of a round comes from the engine's
    round key, which the run's seed sets."""
    kind = spec["kind"]
    h, b = int(spec["local_steps"]), int(spec["batch"])
    if kind == "linear":
        d = int(conf["d"])
        noise = float(spec["noise"])
        w_star = jax.random.normal(
            jax.random.PRNGKey(int(spec["target_seed"])), (d,), jnp.float32)

        def one(k):
            kx, kn = jax.random.split(k)
            x = jax.random.normal(kx, (h, b, d), jnp.float32)
            y = x @ w_star + noise * jax.random.normal(kn, (h, b),
                                                       jnp.float32)
            return {"x": x, "y": y}

        def datagen(key, ids):
            return jax.vmap(one)(_row_keys(key, ids))
        return datagen
    if kind == "tokens":
        vocab = int(conf["vocab_size"])
        seq, n_cls = int(spec["seq"]), int(spec["n_classes"])
        band = vocab // n_cls

        def one(k, cls):
            kt, kl = jax.random.split(k)
            in_band = jax.random.bernoulli(kl, 0.5, (h, b, seq))
            toks = jax.random.randint(kt, (h, b, seq), 0, vocab)
            toks = jnp.where(in_band, (cls * vocab) // n_cls
                             + jnp.mod(toks, band), toks)
            return {"tokens": toks.astype(jnp.int32),
                    "labels": jnp.roll(toks, -1, axis=-1).astype(jnp.int32)}

        def datagen(key, ids):
            return jax.vmap(one)(_row_keys(key, ids), jnp.mod(ids, n_cls))
        return datagen
    raise ValueError(f"unknown traffic data kind {kind!r}")

