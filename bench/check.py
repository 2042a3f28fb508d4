"""The comparison that decides ``correct``: a sample of the window's calls,
each against the plain reference (``bench/reference.py``) run over the same
seed, weights and data after the window has closed.

Numbers compared, each against the cell's own limit
(``bench/limits/<cell>.json``):

``sched_mismatches``  participation entries (round x client) that differ:
                      scheduling; exact, limit 0.
``bits_rel_gap``      worst round's relative gap of the scheduled uplink
                      bits: pricing.
``clock_rel_gap``     median over rounds of the relative gap of the
                      round's simulated time (the clock's increment):
                      channel draws, rates and latencies. The median, as a
                      round whose slowest device sits in a deep fade (SNR
                      near 1e-6) prices its float32 rate ``log2(1 + SNR)``
                      with a relative error near 2^-24 / SNR: a swing of
                      the number compared, not of the program.
``loss_rel_gap``      worst round's relative gap of the logged loss (mean
                      local loss over all clients): the local update
                      through the client model.
``change_rel_gap``    worst leaf's gap between the norms of the parameters'
                      change over the call, program against reference,
                      over the larger of that leaf's reference norm and the
                      median over the leaves the reference moves (top-k
                      leaves some leaves unmoved on both sides): local
                      update, compression with error feedback, aggregation
                      and server update together.
"""
from __future__ import annotations

import types
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.harness import derive_seed, load_compressor, load_policy


def as_logs(out: Dict):
    """A reference's output in the shape of the program's logs."""
    return types.SimpleNamespace(**{k: out[k] for k in (
        "participation", "uplink_bits", "latency_s", "loss")})


def leaf_change_norms(final, start) -> np.ndarray:
    return np.array([float(jnp.linalg.norm((a - b).reshape(-1)))
                     for a, b in zip(jax.tree.leaves(final),
                                     jax.tree.leaves(start))])


def readings(logs, prog_norms, ref: Dict, ref_norms) -> Dict[str, float]:
    """The compared numbers of one call: program ``logs`` (per-round
    ``participation``, ``uplink_bits``, ``latency_s``, ``loss``) and
    per-leaf change norms against the reference's."""
    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))

    prog_norms = np.asarray(prog_norms, np.float64)
    ref_norms = np.asarray(ref_norms, np.float64)
    moved = ref_norms[ref_norms > 0]
    floor = np.maximum(ref_norms, np.median(moved) if moved.size else 0.0)
    change = np.abs(prog_norms - ref_norms) / np.maximum(floor, 1e-30)
    def steps(clock):
        return np.diff(np.asarray(clock, np.float64), prepend=0.0)

    dp, dr = steps(logs.latency_s), steps(ref["latency_s"])
    clock_gap = np.abs(dp - dr) / np.maximum(np.abs(dr), 1e-30)
    return {
        "sched_mismatches": float(np.sum(
            np.asarray(logs.participation) != ref["participation"])),
        "bits_rel_gap": rel(logs.uplink_bits, ref["uplink_bits"]),
        "clock_rel_gap": float(np.median(np.nan_to_num(clock_gap,
                                                        nan=np.inf))),
        "loss_rel_gap": rel(logs.loss, ref["loss"]),
        "change_rel_gap": float(np.max(change)),
    }


def reference_call(cell, sim_seed: int, sim: Dict = None, *,
                   dtype=jnp.float32, precision: str = "highest") -> Dict:
    """The reference over one simulation of a call (``sim``: the cell's,
    where not given), with the weights drawn anew from the run's seed by
    the configuration's own initializer, and the policy and compressor
    found by name."""
    sim = sim or cell.sim
    params0 = jax.jit(lambda k: cell.mod.init_params(cell.conf, k))(
        jax.random.PRNGKey(derive_seed(cell.seed, "weights")))
    out = reference.simulate(
        sim, sim_seed, params0, cell.datagen,
        cell.mod.reference_loss(cell.conf),
        policy=load_policy(sim["policy"]),
        compressor=load_compressor(sim["compression"]), dtype=dtype,
        precision=precision, block=int(cell.conf["reference_block"]))
    out["norms"] = leaf_change_norms(out.pop("params"), params0)
    return out


def compare_samples(cell, samples: List, limits: Dict[str, float]
                    ) -> Dict[str, Dict[str, float]]:
    """Worst reading over the sampled simulations, beside each limit."""
    worst: Dict[str, float] = {}
    for s in samples:
        ref = reference_call(cell, s.sim_seed, s.sim)
        for k, v in readings(s.logs, s.leaf_norms, ref,
                             ref["norms"]).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return {k: {"value": worst[k], "limit": float(limits[k])}
            for k in limits}
