"""Entry ``run_simulation_scan``: one simulation a call, its rounds as one
compiled ``lax.scan`` (``repro.fl.runtime.run_simulation_scan``), on the
cell's first chip.

An entry module gives ``build(cell, devices)``: the cell (``bench.harness
.Cell``) and the chips the cell holds. It places the cell's weights and
returns an object with

* ``variants``: the simulations one call advances by ``rounds_per_call``;
* ``call(seed)``: one timed call, its work drawn from ``seed``; what it
  returns is waited on with ``jax.block_until_ready``;
* ``answers(seed, out)``: for each simulation of that call, a tuple
  ``(sim, sim_seed, logs, final)``: the simulation as the reference reads
  it (``Cell.sim``, with what the variant changes), the seed it ran
  from, its per-round logs (``participation``, ``uplink_bits``,
  ``latency_s``, ``loss``) and its final weights.
"""
from __future__ import annotations

import dataclasses


class Entry:
    variants = 1

    def __init__(self, cell, devices):
        import jax
        from repro.core.algorithms.registry import algo_params
        from repro.core.compression.registry import compression_params
        from repro.fl import runtime as rt
        self.cell = cell
        cell.params0 = jax.device_put(cell.params0, devices[0])
        sim, conf = cell.sim, cell.conf
        self.cfg = rt.SimConfig(
            n_devices=sim["n_devices"], n_scheduled=sim["n_scheduled"],
            rounds=sim["rounds"], policy=sim["policy"],
            algorithm=conf["algorithm"], chunk_size=conf["chunk_size"],
            compression=sim["compression"], model_bits=sim["model_bits"],
            comp_latency_s=sim["comp_latency_s"],
            compression_params=compression_params(
                **sim["compression_params"]),
            algo_params=algo_params(lr=sim["lr"],
                                    server_lr=sim["server_lr"]),
            datagen=cell.datagen)

    def call(self, seed: int):
        from repro.fl import runtime as rt
        cfg = dataclasses.replace(self.cfg, seed=seed)
        return rt.run_simulation_scan(cfg, self.cell.loss_fn,
                                      self.cell.params0)

    def answers(self, seed: int, out):
        final, logs = out
        return [(self.cell.sim, seed, logs, final)]


def build(cell, devices) -> Entry:
    return Entry(cell, devices)
