"""A run with the timed path broken underneath, or with the bfloat16 control
in the program's place, comes out not correct: the harness's look for a
chip is skipped, the rest of a run is driven on a tiny cell. The faults are
``bench/faults.py``'s. The cells run on one chip, so there is no exchange
between chips to leave out.

The second test holds each committed cell's own limits
(``bench/limits/<cell>.json``) against its tiny stand-in: the tiny cell of
the same configuration module and compressor."""
import json
import os

import pytest

from bench import faults, harness
from bench.tests import tiny

CELL = "fleet-tiny.topk-ef"
ROOT = os.path.dirname(tiny.BENCH)


def _run(tmp_path, monkeypatch, fault, cell, limits=None):
    manifest = tiny.make_bench_dir(str(tmp_path))
    bench = tmp_path / "bench"
    if limits is not None:
        with open(bench / "limits" / (cell + ".json"), "w") as f:
            json.dump({"limits": limits}, f)
    monkeypatch.setattr(harness, "BENCH", str(bench))
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "")
    faults.install(fault, monkeypatch.setattr)
    return harness.run(cell, 11, 0.1, False, 0.0, allow_platform="cpu",
                       manifest=manifest)


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "change_rel_gap"),
    ("half_batch", "loss_rel_gap"),
    ("answer_altered", "sched_mismatches"),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                            caught_by):
    res = _run(tmp_path, monkeypatch, fault, CELL)
    assert res["correct"] is False
    v = res["checks"][caught_by]
    assert v["value"] > v["limit"]


def _committed_cells():
    """(committed cell, its tiny stand-in) for each cell of BENCHMARK.json
    that has one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = []
    for wl in manifest["workloads"]:
        comp = harness.load_traffic(wl["traffic"])["compression"]["name"]
        for cell, _, traffic, real in tiny.CELLS:
            if (real == wl["config"] and
                    tiny.TRAFFIC[traffic]["compression"]["name"] == comp):
                out.append((wl["name"], cell))
                break
    return out


@pytest.mark.parametrize("fault", faults.NAMES)
@pytest.mark.parametrize("committed,stand_in", _committed_cells(),
                         ids=[c for c, _ in _committed_cells()])
def test_the_committed_limits_catch_each_fault(tmp_path, monkeypatch, fault,
                                               committed, stand_in):
    limits = harness.load_limits(committed)
    res = _run(tmp_path, monkeypatch, fault, stand_in, limits)
    assert res["correct"] is False, res["checks"]
    assert {k: v["limit"] for k, v in res["checks"].items()} == limits
