"""At a tiny size on the CPU, the plain reference agrees with the engine,
and the bfloat16 control that a check must fail does not."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness
from bench.tests import tiny

CELL_NAMES = [c[0] for c in tiny.CELLS]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinybench")
    manifest = tiny.make_bench_dir(str(root))
    saved = harness.BENCH
    harness.BENCH = str(root / "bench")
    yield manifest
    harness.BENCH = saved


def _cell(manifest, name, rounds=None):
    cell = harness.build_cell(harness.find_workload(manifest, name), 31)
    if rounds:
        cell.sim["rounds"] = rounds
        cell.entry.cfg = dataclasses.replace(cell.entry.cfg, rounds=rounds)
    return cell


def _program(cell, j=1):
    [(_, _, logs, final)] = cell.answers(j, cell.call(j))
    return logs, np.asarray(cell.leaf_norms(final, cell.params0))


def _within(readings):
    return {k: v for k, v in readings.items() if v > tiny.LIMITS[k]}


@pytest.mark.parametrize("name", CELL_NAMES)
def test_one_round_matches_the_reference(bench, name):
    cell = _cell(bench, name, rounds=1)
    logs, norms = _program(cell)
    ref = check.reference_call(cell, cell.call_seed(1))
    assert ref["participation"].sum() == cell.sim["n_scheduled"]
    assert np.any(ref["norms"] > 0)
    assert _within(check.readings(logs, norms, ref, ref["norms"])) == {}


@pytest.mark.parametrize("name", ["fleet-tiny.topk-ef", "fleet-tiny.dense"])
def test_the_bfloat16_control_fails(bench, name):
    cell = _cell(bench, name)
    ref = check.reference_call(cell, cell.call_seed(1))
    ctl = check.reference_call(cell, cell.call_seed(1), dtype=jnp.bfloat16,
                               precision="default")
    failed = _within(check.readings(check.as_logs(ctl), ctl["norms"], ref,
                                    ref["norms"]))
    assert {"loss_rel_gap", "change_rel_gap"} & set(failed)
