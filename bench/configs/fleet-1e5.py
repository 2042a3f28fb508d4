"""The cross-device fleet (``fleet-1e5.json``): a d-dimensional linear probe
trained by squared error, the same function in the program and in the
reference. The weights start at zero."""
from __future__ import annotations

import jax.numpy as jnp


def init_params(conf, key):
    del key
    return {"w": jnp.zeros((conf["d"],), jnp.float32)}


def reference_loss(conf):
    def loss(params, batch):
        return jnp.mean(jnp.square(batch["x"] @ params["w"] - batch["y"]))
    return loss


def program_loss(conf):
    ref = reference_loss(conf)

    def loss_fn(params, batch):
        return ref(params, batch), {}
    return loss_fn


def model_flops_per_token(conf, seq: int):
    return None
