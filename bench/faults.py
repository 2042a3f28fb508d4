"""Faults planted under the timed path, and the control put in the
program's place: a run with any of them must come out not correct.

``state_unchanged``  the entry returns the starting weights;
``half_batch``       every local step's loss sees the first half of its
                     batch, the mean taken over that half;
``answer_altered``   two participation entries of the first round are
                     flipped where the program produces them;
``control``          the plain reference in bfloat16 (default matrix
                     precision, every host intermediate rounded) is called
                     in the program's place.

The first three wrap the program's ``run_simulation_scan``; the control
replaces the cell's entry with one that answers like it. A cell on one
chip has no exchange between chips to leave out. ``install(name, set_)``
plants one, ``set_`` being ``setattr`` or a test's
``monkeypatch.setattr``. ``bench/plant.py`` plants them on the chip,
``bench/tests/test_bench_faults.py`` on the CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def state_unchanged(run):
    def broken(cfg, loss_fn, init_params, *a, **kw):
        _, logs = run(cfg, loss_fn, init_params, *a, **kw)
        return jax.tree.map(jnp.array, init_params), logs
    return broken


def half_batch(run):
    def broken(cfg, loss_fn, init_params, *a, **kw):
        def half(params, batch):
            return loss_fn(params, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))
        return run(cfg, half, init_params, *a, **kw)
    return broken


def answer_altered(run):
    def broken(cfg, loss_fn, init_params, *a, **kw):
        final, logs = run(cfg, loss_fn, init_params, *a, **kw)
        part = np.array(logs.participation)
        part[0, :2] = ~part[0, :2]
        logs.participation = part
        return final, logs
    return broken


class ControlEntry:
    """The bfloat16 reference in the place of a one-simulation entry: a
    call returns ``(final weights, logs)`` as the program's would."""

    def __init__(self, cell, entry):
        self.cell, self.entry = cell, entry
        self.variants = entry.variants

    def call(self, seed: int):
        from bench import check, harness, reference
        cell = self.cell
        out = reference.simulate(
            cell.sim, seed, cell.params0, cell.datagen,
            cell.mod.reference_loss(cell.conf),
            policy=harness.load_policy(cell.sim["policy"]),
            compressor=harness.load_compressor(cell.sim["compression"]),
            dtype=jnp.bfloat16, precision="default",
            block=int(cell.conf["reference_block"]))
        return out["params"], check.as_logs(out)

    def answers(self, seed: int, out):
        return self.entry.answers(seed, out)


def _with_control(build_cell):
    def build(workload, seed, devices=None):
        cell = build_cell(workload, seed, devices)
        cell.entry = ControlEntry(cell, cell.entry)
        return cell
    return build


PROGRAM_FAULTS = {"state_unchanged": state_unchanged,
                  "half_batch": half_batch,
                  "answer_altered": answer_altered}
NAMES = tuple(PROGRAM_FAULTS) + ("control",)


def install(name: str, set_=setattr) -> None:
    from bench import harness
    from repro.fl import runtime as rt
    if name == "control":
        set_(harness, "build_cell", _with_control(harness.build_cell))
    else:
        set_(rt, "run_simulation_scan",
             PROGRAM_FAULTS[name](rt.run_simulation_scan))
