"""The per-stage reading of a trace (``bench/stages.py``): on planes built
by hand, on the op-name scopes of a compiled program, on the recorded
``trace_v5e.json.gz`` beside ``bench.trace``, and, through the metric
readers, on ``trace_v5e_stages.json.gz`` (three calls of a small fleet
cell with top-k and dense EF recorded on a TPU v5e with the engine's
scopes, by ``record_stages_trace.py``)."""
import gzip
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from bench import harness, stages, trace
from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
STAGE_METRICS = ("channel", "schedule", "data", "local_update", "compress",
                 "client_state", "aggregate", "server_update")


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


def _recorded(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        rec = json.load(f)
    planes = [_plane(p["name"], {ln["name"]: ln["events"]
                                 for ln in p["lines"]})
              for p in rec["planes"]]
    return rec, planes


def _hand_built():
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 1000),
        ("bench.call", 0, 500), ("fl.dispatch", 10, 40),
        ("fl.fetch_logs", 400, 100),
        ("bench.call", 500, 500), ("fl.engine_lookup", 500, 10),
        ("fl.prepare", 510, 20), ("fl.dispatch", 530, 20),
        ("fl.fetch_logs", 900, 100)]})
    dev = _plane("/device:TPU:0", {
        trace.OPS_LINE: [
            ("copy.1", 20, 10),                    # the call's argument copy
            ("fusion.1", 50, 100), ("while.2", 150, 200),
            ("topk_rows.3", 160, 150),             # nested in the loop
            ("fusion.4", 350, 50),
            ("copy.1", 560, 10),
            ("fusion.1", 600, 100), ("while.2", 700, 150),
            ("topk_rows.3", 710, 100)],
        stages.MODULES_LINE: [("jit_engine(7)", 50, 350),
                              ("jit_copy(3)", 20, 10),
                              ("jit_engine(7)", 600, 250),
                              ("jit_copy(3)", 560, 10)]})
    scopes = {"copy.1": "fl.data", "fusion.1": "fl.channel",
              "topk_rows.3": "fl.compress", "fusion.4": "fl.log"}
    return [host, dev], scopes


def test_each_op_goes_to_its_innermost_scope_in_the_engine_module():
    planes, scopes = _hand_built()
    st = stages.reduce_planes(planes, "jit_engine", scopes)
    ns = {k: v * 1e9 for k, v in st.stage_seconds.items()}
    assert ns == pytest.approx({"fl.channel": 200, "fl.compress": 250,
                                "fl.log": 50})
    # the loop's own time (no scope) and the copies outside the engine's
    # module (whose name an engine op shares) are unattributed
    assert st.unattributed_s * 1e9 == pytest.approx(50 + 50 + 10 + 10)
    assert dict(st.unattributed_ops) == pytest.approx(
        {"while.2": 100e-9, "copy.1": 20e-9})
    assert st.calls == 2 and st.window_s == pytest.approx(1000e-9)
    assert (sum(st.stage_seconds.values()) + st.unattributed_s
            == pytest.approx(st.busy_s))


def test_idle_gaps_go_to_the_innermost_program_span():
    planes, scopes = _hand_built()
    st = stages.reduce_planes(planes, "jit_engine", scopes)
    gaps = {k: v * 1e9 for k, v in st.span_gaps.items()}
    # 0..20 and 30..50 under the first dispatch, 400..560 whole to the
    # span over its midpoint (480, the first log fetch), 570..600 under no
    # fl.* span, 850..1000 under the second log fetch
    assert gaps == pytest.approx({"fl.dispatch": 20 + 20,
                                  "fl.fetch_logs": 160 + 150,
                                  stages.OTHER: 30})
    assert (sum(st.span_gaps.values()) + st.busy_s
            == pytest.approx(st.window_s))
    # bench.trace labels the same gaps by the shortest host event
    red = trace.reduce_planes(planes)
    assert sum(s for _, s in red.gaps) == pytest.approx(
        sum(st.span_gaps.values()))


def test_a_program_without_scopes_or_spans_reads_nothing():
    planes, _ = _hand_built()
    host = _plane("/host:CPU", {"python": [
        (n, s, d) for n, s, d in [("bench.window", 0, 1000),
                                  ("bench.call", 0, 500),
                                  ("bench.call", 500, 500)]]})
    st = stages.reduce_planes([host, planes[1]], "jit_engine", {})
    assert st.stage_seconds is None and st.span_gaps is None
    ctx = types.SimpleNamespace(stages=st, rounds=6)
    for name in ([f"stage.{s}.ms_per_round" for s in STAGE_METRICS]
                 + ["stage.unattributed_pct",
                    "entry.fetch_logs.idle_ms_per_call"]):
        assert harness.load_metric(name).read(ctx) is None, name


def test_op_scopes_of_a_compiled_program():
    def f(x):
        with jax.named_scope("fl.channel"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("fl.data"):
            with jax.named_scope("fl.log"):
                z = jnp.cumsum(y)
        return z + 1.0
    text = jax.jit(f).lower(jnp.ones(64)).compile().as_text()
    module, scopes = stages.op_scopes(text)
    assert module == "jit_f"
    assert set(scopes.values()) <= {"fl.channel", "fl.log"}
    assert "fl.log" in scopes.values()
    for name in scopes:
        assert f"%{name} = " in text


def test_the_engine_text_of_a_cell_names_its_stages(tmp_path, monkeypatch):
    """The reader's own compile of a (tiny) cell's engine, past the
    compile caches, carries the stage scopes and restores the cache
    setting it turned off."""
    manifest = tiny.make_bench_dir(str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path / "bench"))
    cell = harness.build_cell(
        harness.find_workload(manifest, "fleet-tiny.topk-ef"), 7)
    enabled = jax.config.jax_enable_compilation_cache
    module, scopes = stages.op_scopes(stages.engine_hlo(cell))
    assert jax.config.jax_enable_compilation_cache == enabled
    assert module == "jit_engine"
    assert {"fl.channel", "fl.schedule", "fl.data", "fl.local_update",
            "fl.compress", "fl.client_state", "fl.aggregate",
            "fl.server_update"} <= set(scopes.values())


@pytest.mark.parametrize("path,scope", [
    ("jit(engine)/while/body/fl.channel/mul", "fl.channel"),
    ("jit(engine)/while/body/closed_call/while/body/closed_call/"
     "fl.local_update/vmap()/while/body/closed_call/transpose(jvp())/"
     "dot_general", "fl.local_update"),
    ("jit(engine)/fl.compress/jit(topk_rows)/topk_rows/pallas_call",
     "fl.compress"),
    ("jit(f)/vmap(fl.data)/fl.log/add", "fl.log"),
    ("jit(engine)/while/body/dynamic_update_slice", None),
    ("jit(engine)/flow/fl/add", None),
])
def test_innermost_scope_of_an_op_name(path, scope):
    assert stages.innermost(path) == scope


def test_the_first_recorded_trace_reads_as_before():
    """``trace_v5e.json.gz`` (no scopes recorded): ``bench.trace``'s
    numbers stay what they were, and the per-stage reading covers the
    same busy time and idle gaps."""
    _, planes = _recorded("trace_v5e.json.gz")
    red = trace.reduce_planes(planes)
    assert red.calls == 1 and red.n_devices == 1
    assert red.window_s == pytest.approx(0.02317307, rel=1e-9)
    assert red.busy_s == pytest.approx(0.010902176, rel=1e-9)
    assert red.ops_matching(r"^topk_rows") == (
        24, pytest.approx(0.002484002, rel=1e-9))
    st = stages.reduce_planes(planes, "jit_engine", {})
    assert (st.window_s, st.busy_s, st.calls) == (
        red.window_s, red.busy_s, red.calls)
    assert st.unattributed_s == pytest.approx(sum(red.op_seconds.values()))
    assert st.stage_seconds is None and st.span_gaps is None


@pytest.fixture(scope="module")
def recorded_ctx():
    rec, planes = _recorded("trace_v5e_stages.json.gz")
    st = stages.reduce_planes(planes, rec["module"], rec["scopes"])
    return types.SimpleNamespace(stages=st, rounds=3 * st.calls)


def test_the_stage_fixture_adds_up_to_busy_time(recorded_ctx):
    st = recorded_ctx.stages
    assert st.calls == 3
    total_ms = sum(
        harness.load_metric(f"stage.{s}.ms_per_round").read(recorded_ctx)
        for s in STAGE_METRICS)
    other = {k: v for k, v in st.stage_seconds.items()
             if k not in {f"fl.{s}" for s in STAGE_METRICS}}
    assert set(other) <= {"fl.log", "fl.privacy"}
    busy_ms = st.busy_s / recorded_ctx.rounds * 1e3
    unattr_ms = st.unattributed_s / recorded_ctx.rounds * 1e3
    assert total_ms + unattr_ms + sum(other.values()) / \
        recorded_ctx.rounds * 1e3 == pytest.approx(busy_ms, rel=1e-9)
    pct = harness.load_metric("stage.unattributed_pct").read(recorded_ctx)
    assert pct == pytest.approx(100 * unattr_ms / busy_ms)
    assert 0 <= pct <= 10


def test_the_stage_fixture_puts_the_kernel_under_compress(recorded_ctx):
    rec, planes = _recorded("trace_v5e_stages.json.gz")
    kernels = {k: v for k, v in rec["scopes"].items()
               if k.startswith("topk_rows")}
    assert kernels and set(kernels.values()) == {"fl.compress"}
    red = trace.reduce_planes(planes)
    _, kernel_s = red.ops_matching(r"^topk_rows")
    assert recorded_ctx.stages.stage_seconds["fl.compress"] >= kernel_s
    for s in STAGE_METRICS:
        v = harness.load_metric(f"stage.{s}.ms_per_round").read(
            recorded_ctx)
        assert v > 0, s


def test_the_stage_fixture_reads_the_log_fetch(recorded_ctx):
    st = recorded_ctx.stages
    v = harness.load_metric("entry.fetch_logs.idle_ms_per_call").read(
        recorded_ctx)
    assert v == pytest.approx(st.span_gaps["fl.fetch_logs"] / st.calls
                              * 1e3)
    assert 0 < v <= (st.window_s - st.busy_s) / st.calls * 1e3
