"""Plain reference of one engine call: R rounds of federated learning over a
wireless cell, written from the simulator's documented semantics and
importing nothing of the program.

One round, as the simulator defines it (``SimConfig`` with faults, privacy
and downlink EF off):

1. draws: the round key is ``fold_in(k_rounds, t)``, split five ways into
   (fading, compute, policy, norms, compression); the data key is
   ``fold_in(round key, 0x0DA7A)`` and the downlink fading of client ``i``
   comes from ``fold_in(fold_in(round key, 0xD0DE), i)``. Positions are
   drawn once per call from the first half of ``split(PRNGKey(seed))``;
2. channel and pricing: log-distance path loss, Rayleigh power fading,
   uplink SNR against the noise of the whole band, Shannon rate over
   ``bandwidth / n_scheduled``, the payload the compressor's ``bits``,
   compute time ``comp_latency_s * Exp(1)``;
3. scheduling: the policy module (``bench/policies/<policy>.py``) picks
   the round's clients from the policy key and the round's channel;
4. local update: every client, scheduled or not, takes ``local_steps`` SGD
   steps from the global model on its own batches;
5. compression: the compressor module (``bench/compressors/<name>.py``)
   makes the message; with error feedback the client adds its error row
   to its update first, and what it did not send becomes its new row;
6. aggregation and server update: the scheduled clients' messages are
   summed, divided by their count, and added to the model (``server_lr``);
7. logs: the mean of every client's mean local loss, the scheduled uplink
   bits, and the wall clock: downlink broadcast of the model to the slowest
   scheduled client, plus the upload and compute time of the scheduled
   client that finishes last.

The learning arithmetic runs in ``dtype`` with matrix products at
``precision``: float32 at ``highest`` for the reference, bfloat16 for the
control that a check must fail. Channel, pricing and clock arithmetic run
on the host, in float64 for the reference and rounded to bfloat16 after
every operation for the control.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

DATA_FOLD = 0x0DA7A
DOWNLINK_FOLD = 0xD0DE

# the cell of the simulator's default channel (WirelessConfig's defaults)
WIRELESS = {"cell_radius_m": 500.0, "bandwidth_hz": 2e7,
            "noise_dbw_per_hz": -204.0, "tx_power_dbm": 10.0,
            "bs_power_dbm": 15.0, "path_loss_exponent": 3.0,
            "ref_loss_db": 30.0}


def _flat(tree) -> jnp.ndarray:
    return jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(tree)])


def _unflat(vec: jnp.ndarray, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for leaf in leaves:
        out.append(vec[off:off + leaf.size].reshape(leaf.shape))
        off += leaf.size
    return jax.tree.unflatten(treedef, out)


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def _make_client_fn(loss_fn: Callable, sim: Dict, compressor, dtype):
    """(params, ef rows, batches of a block of clients) -> (messages, new ef
    rows, mean local losses), vmapped over the block."""
    lr = jnp.asarray(sim["lr"], dtype)
    cp = sim["compression_params"]

    def one(params, ef, batch):
        batch = _cast(batch, dtype)
        p, losses = params, []
        for h in range(jax.tree.leaves(batch)[0].shape[0]):
            b = jax.tree.map(lambda x: x[h], batch)
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            p = jax.tree.map(lambda a, gg: a - lr * gg, p, g)
            losses.append(loss.astype(jnp.float32))
        delta = _flat(p) - _flat(params)
        if compressor.ERROR_FEEDBACK:
            corrected = delta + ef
            msg = compressor.compress(corrected, cp, dtype)
            ef = corrected - msg
        else:
            msg = compressor.compress(delta, cp, dtype)
            ef = jnp.zeros_like(delta)
        return msg, ef, sum(losses) / len(losses)

    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0)))


def _draw_fn(n: int):
    @jax.jit
    def draws(k_rounds, t):
        kt = jax.random.fold_in(k_rounds, t)
        kf, kc, kp, _, _ = jax.random.split(kt, 5)
        fading = jax.random.exponential(kf, (n,))
        comp = jax.random.exponential(kc, (n,))
        kdl = jax.random.fold_in(kt, DOWNLINK_FOLD)
        dl = jax.vmap(lambda i: jax.random.exponential(
            jax.random.fold_in(kdl, i), ()))(jnp.arange(n, dtype=jnp.int32))
        return fading, comp, kp, dl, jax.random.fold_in(kt, DATA_FOLD)
    return draws


def simulate(sim: Dict, seed: int, params0, datagen: Callable,
             loss_fn: Callable, *, policy, compressor, dtype=jnp.float32,
             precision: str = "highest", block: int = 1) -> Dict:
    """Run one call's ``sim["rounds"]`` rounds. ``policy`` and
    ``compressor`` are the modules of ``bench/policies`` and
    ``bench/compressors`` that ``sim`` names. Returns the per-round
    ``participation`` (R, N), ``uplink_bits``, ``latency_s`` (cumulative
    clock) and ``loss``, and the final ``params`` (float32)."""
    with jax.default_matmul_precision(precision):
        return _simulate(sim, seed, params0, datagen, loss_fn, policy,
                         compressor, dtype, block)


def _simulate(sim, seed, params0, datagen, loss_fn, policy, compressor,
              dtype, block):
    n, k_sched, rounds = sim["n_devices"], sim["n_scheduled"], sim["rounds"]
    w = WIRELESS
    params = _cast(params0, dtype)
    d = sum(x.size for x in jax.tree.leaves(params))
    # error-feedback rows, one array per block of clients
    starts = list(range(0, n, block))
    ef = [jnp.zeros((min(block, n - lo), d), dtype) for lo in starts]
    client_fn = _make_client_fn(loss_fn, sim, compressor, dtype)
    datagen = jax.jit(datagen)
    draws = _draw_fn(n)

    # host arithmetic: float64 for the reference; the control rounds every
    # intermediate to its own dtype
    if dtype == jnp.float32:
        def q(a):
            return np.asarray(a, np.float64)
    else:
        def q(a):
            return np.asarray(np.asarray(a, np.float64).astype(dtype),
                              np.float64)

    k_pos, k_rounds = jax.random.split(jax.random.PRNGKey(seed))
    u = q(jax.random.uniform(k_pos, (n,)))
    dist = q(np.maximum(w["cell_radius_m"] * q(np.sqrt(u)), 1.0))
    gain = q(10.0 ** q(-q(w["ref_loss_db"] + 10.0 * w["path_loss_exponent"]
                          * q(np.log10(dist))) / 10.0))
    bw = w["bandwidth_hz"]
    n0 = 10.0 ** (w["noise_dbw_per_hz"] / 10.0) * bw
    p_up = 10.0 ** ((w["tx_power_dbm"] - 30.0) / 10.0)
    p_dl = 10.0 ** ((w["bs_power_dbm"] - 30.0) / 10.0)
    model_bits = float(sim["model_bits"])
    bits_dev = float(q(compressor.bits(d, sim["compression_params"],
                                       model_bits)))

    part = np.zeros((rounds, n), bool)
    ubits, clock_log, loss_log = (np.zeros(rounds) for _ in range(3))
    clock = 0.0
    server_lr = jnp.asarray(sim["server_lr"], dtype)
    for t in range(rounds):
        fading, comp, kp, dl_fad, kd = draws(k_rounds, t)
        fading, comp, dl_fad = jax.device_get((fading, comp, dl_fad))
        snr = q(q(p_up * gain) * q(fading) / n0)
        rate = q(bw / k_sched * q(np.log2(q(1.0 + snr))))
        comm = q(np.where(rate > 0, bits_dev / np.maximum(rate, 1e-300),
                          np.inf))
        comp_s = q(sim["comp_latency_s"] * q(comp))
        mask = np.asarray(policy.schedule(
            kp, n, k_sched, {"snr": snr, "comm_s": comm, "comp_s": comp_s}),
            bool)
        part[t] = mask
        dl_rate = q(bw * q(np.log2(q(1.0 + q(q(p_dl * gain) * q(dl_fad)
                                                  / n0)))))
        dl_s = float(np.max(np.where(mask, q(model_bits / dl_rate), 0.0)))
        total = q(np.where(mask, comm + comp_s, -np.inf))
        slow = int(np.argmax(total))
        clock = float(q(q(q(clock + dl_s) + comm[slow]) + comp_s[slow]))
        clock_log[t] = clock
        ubits[t] = q(bits_dev * mask.sum())

        msg_sum = jnp.zeros((d,), dtype)
        loss_sum = 0.0
        mask_dev = jnp.asarray(mask)
        for b, lo in enumerate(starts):
            ids = jnp.arange(lo, lo + ef[b].shape[0], dtype=jnp.int32)
            msgs, ef[b], losses = client_fn(params, ef[b], datagen(kd, ids))
            keep = mask_dev[lo:lo + len(ids)]
            msg_sum = msg_sum + jnp.sum(
                jnp.where(keep[:, None], msgs, jnp.zeros((), dtype)), axis=0)
            loss_sum += float(jnp.sum(losses.astype(jnp.float32)))
            del msgs
        mean = msg_sum / jnp.asarray(max(int(mask.sum()), 1), dtype)
        params = jax.tree.map(lambda p, m: p + server_lr * m, params,
                              _unflat(mean, params))
        loss_log[t] = loss_sum / n
    return {"participation": part, "uplink_bits": ubits,
            "latency_s": clock_log, "loss": loss_log,
            "params": _cast(params, jnp.float32)}
