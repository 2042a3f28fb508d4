"""Policy ``random`` of the plain reference: the first ``n_scheduled``
entries of a permutation of the clients, drawn from the round's policy
key.

A policy module gives ``schedule(key, n, k, channel)``: the round's policy
key, the number of clients and of clients to schedule, and the round's
channel as the reference prices it (``snr``, ``comm_s``, ``comp_s``: numpy
arrays over the clients). It returns a boolean numpy mask over the
clients."""
from __future__ import annotations

import functools

import jax
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2))
def _first_of_permutation(key, n: int, k: int):
    return jax.random.permutation(key, n)[:k]


def schedule(key, n: int, k: int, channel) -> np.ndarray:
    mask = np.zeros(n, bool)
    mask[np.asarray(_first_of_permutation(key, n, k))] = True
    return mask
