"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on planes built by hand, and on a small trace recorded on a TPU v5e
(``trace_v5e.json.gz``: the device's operations and the benchmark's host
thread from one traced call of a fleet cell with the top-k row kernel)."""
import gzip
import json
import os
import types

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "trace_v5e.json.gz")


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


def test_busy_is_the_union_and_gaps_carry_the_host_span():
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 1000), ("bench.call", 0, 600),
        ("bench.call", 600, 400), ("fetch logs", 450, 150)]})
    dev = _plane("/device:TPU:0", {trace.OPS_LINE: [
        ("fusion.1", 100, 200), ("topk_rows", 150, 250),   # overlap
        ("fusion.2", 700, 100)], "XLA Modules": [("jit", 0, 1000)]})
    red = trace.reduce_planes([host, dev])
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((400 - 100 + 100) * 1e-9)
    assert red.calls == 2
    assert red.ops_matching("topk") == (1, pytest.approx(250e-9))
    gaps = dict(red.gaps)
    # a gap goes whole to the shortest span over its midpoint
    assert gaps["fetch logs"] == pytest.approx(300e-9)      # 400 .. 700
    assert gaps["bench.call"] == pytest.approx((100 + 200) * 1e-9)
    assert sum(gaps.values()) + red.busy_s == pytest.approx(red.window_s)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["topk_rows", pytest.approx(250e-9)]


def test_a_trace_without_the_window_is_refused():
    dev = _plane("/device:TPU:0", {trace.OPS_LINE: [("fusion.1", 0, 10)]})
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes([_plane("/host:CPU", {"python": []}), dev])


def test_a_recorded_v5e_trace():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    planes = [_plane(p["name"], {ln["name"]: ln["events"]
                                 for ln in p["lines"]})
              for p in rec["planes"]]
    red = trace.reduce_planes(planes)
    assert red.n_devices == 1 and red.calls == 1
    assert 0 < red.busy_s <= red.window_s
    # 8 blocks of clients x 3 rounds, one top-k kernel call each
    n, secs = red.ops_matching(r"^topk_rows")
    assert n == 24 and 0 < secs <= red.busy_s
    bd = red.breakdown()
    assert bd["device_ops"][0][0].startswith("topk_rows")
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    own = sum(red.op_seconds.values())
    assert own == pytest.approx(red.busy_s, rel=1e-6)
    assert sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
