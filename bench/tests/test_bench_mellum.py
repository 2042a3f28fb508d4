"""The Mellum2 client cell at a tiny size on the CPU: its configuration and
traffic, loaded by name, run through the harness and come out correct; a
broken timed path or the bfloat16 control comes out not correct under the
committed cell's own limits; and the client model's scopes reach the
engine's compiled HLO inside its ``fl.local_update`` stage."""
import json

import pytest

from bench import faults, harness, model_scopes, stages
from bench.tests import tiny_mellum

SCOPES = {"model.attn.sliding", "model.attn.full", "model.moe.route",
          "model.moe.experts", "model.moe.combine"}


def _run(tmp_path, monkeypatch, limits=None, fault=None):
    manifest = tiny_mellum.make_bench_dir(str(tmp_path))
    bench = tmp_path / "bench"
    if limits is not None:
        with open(bench / "limits" / (tiny_mellum.CELL + ".json"), "w") as f:
            json.dump({"limits": limits}, f)
    monkeypatch.setattr(harness, "BENCH", str(bench))
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "")
    if fault:
        faults.install(fault, monkeypatch.setattr)
    return harness.run(tiny_mellum.CELL, 23, 0.1, False, 0.0,
                       allow_platform="cpu", manifest=manifest)


def test_the_tiny_cell_runs_correct_through_the_harness(tmp_path,
                                                        monkeypatch):
    res = _run(tmp_path, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", faults.NAMES)
def test_the_committed_limits_catch_each_fault(tmp_path, monkeypatch, fault):
    limits = harness.load_limits(tiny_mellum.COMMITTED)
    res = _run(tmp_path, monkeypatch, limits, fault)
    assert res["correct"] is False, res["checks"]


def test_model_scopes_reach_the_engine_inside_the_local_update(
        tmp_path, monkeypatch):
    manifest = tiny_mellum.make_bench_dir(str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path / "bench"))
    cell = harness.build_cell(
        harness.find_workload(manifest, tiny_mellum.CELL), 3)
    text = stages.engine_hlo(cell)
    _, scopes = model_scopes.op_scopes(text)
    assert set(scopes.values()) == SCOPES
    # the engine's own instructions (reducer bodies carry a bare path):
    # each model.* scope lies inside the local update
    paths = [p for _, p in model_scopes._INSTR.findall(text)
             if p.startswith("jit(") and "model." in p]
    assert paths
    assert {stages.innermost(p) for p in paths} == {"fl.local_update"}


def test_model_scopes_compile_and_read_the_trace_once(monkeypatch):
    """Every model.* metric of a run reads one compile of the engine and
    one read of the trace, kept on the metric context."""
    import types

    import jax
    from bench import trace
    compiled = []
    text = ('HloModule jit_engine\n'
            '  %fusion.1 = f32[2] fusion(), metadata={op_name='
            '"jit(engine)/fl.local_update/model.moe.route/add"}\n')

    def engine_hlo(cell):
        compiled.append(cell)
        return text
    monkeypatch.setattr(stages, "engine_hlo", engine_hlo)
    monkeypatch.setattr(trace, "find_xplane", lambda d: "window.xplane.pb")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: types.SimpleNamespace(planes=["p"]))
    monkeypatch.setattr(stages, "reduce_planes", lambda planes, mod, sc:
                        types.SimpleNamespace(stage_seconds={
                            sc["fusion.1"]: 0.5 if planes == ["p"] and
                            mod == "jit_engine" else 0.0}))
    ctx = types.SimpleNamespace(cell=types.SimpleNamespace(
        workload={"name": "c"}), rounds=2)
    assert model_scopes.ms_per_round(ctx, "model.moe") == 250.0
    assert model_scopes.ms_per_round(ctx, "model.moe.route") == 250.0
    assert model_scopes.ms_per_round(ctx, "model.attn") is None
    assert model_scopes.engine_trace(ctx) == (text, ["p"])
    assert len(compiled) == 1
