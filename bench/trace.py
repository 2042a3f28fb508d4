"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the result's ``device`` and ``breakdown`` fields
need.

A device plane (``/device:TPU:<n>``) holds one line of operations (``XLA
Ops``), named by their HLO instruction (``topk_rows.9``). Busy time is the
union of that line's event intervals inside the window, so nested events
(a loop and its body) count once; idle is the rest of the window. An
operation's time is its own time, without the operations nested in it.
The window is the host span that the benchmark opens around the traced
calls (``bench.window``); every plane shares the profiler's clock. An idle gap is labelled with the shortest host event on the
benchmark's own thread that covers the gap's midpoint: what the host was
doing while the device waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # mean over the device planes
    n_devices: int
    calls: int                       # bench.call spans inside the window
    op_seconds: Dict[str, float]     # own time per op name, device mean
    op_counts: Dict[str, int]        # per op name, summed over devices
    gaps: List[Tuple[str, float]]    # (host label, seconds), longest first

    def ops_matching(self, pattern: str) -> Tuple[int, float]:
        """(events, seconds) of the ops whose name matches ``pattern``,
        events summed and seconds averaged over the devices."""
        rx = re.compile(pattern)
        n = sum(c for k, c in self.op_counts.items() if rx.search(k))
        s = sum(v for k, v in self.op_seconds.items() if rx.search(k))
        return n, s

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps_of(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi) given a merged, clipped busy list."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(name: str) -> str:
    """An operation's name without its HLO signature: ``%topk_rows.9 =
    f32[...] custom-call(...)`` -> ``topk_rows.9``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line, short: bool = False):
    return [(op_name(ev.name) if short else ev.name, float(ev.start_ns),
             float(ev.start_ns) + float(ev.duration_ns))
            for ev in line.events]


def self_times(events) -> List[Tuple[str, float]]:
    """(name, own time) per event of one line: its duration less the part
    covered by the events nested inside it (a loop less its body)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []            # [name, start, end, child time]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][2]:
            n0, s0, e0, c0 = stack.pop()
            out.append((n0, (e0 - s0) - c0))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    out.extend((n0, (e0 - s0) - c0) for n0, s0, e0, c0 in stack)
    return out


def reduce_planes(planes) -> Reduced:
    """``planes``: an iterable of objects with ``name`` and ``lines`` (each
    line with ``name`` and ``events`` of ``name``, ``start_ns`` and
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    host_lines, dev_ops = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops.append(_events(line, short=True))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append(_events(line))
    if not dev_ops:
        raise ValueError("the trace holds no device operations")
    window: Optional[Interval] = None
    bench_line: List = []
    for evs in host_lines:
        for name, s, e in evs:
            if name == WINDOW_SPAN:
                window, bench_line = (s, e), evs
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    calls = sum(1 for name, s, e in bench_line
                if name == CALL_SPAN and s >= lo and e <= hi)

    busy_total, op_s, op_n = 0.0, {}, {}
    gap_ns: Dict[str, float] = {}
    for evs in dev_ops:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        merged = union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in merged)
        for n, own in self_times(inside):
            op_s[n] = op_s.get(n, 0.0) + own
            op_n[n] = op_n.get(n, 0) + 1
        for s, e in gaps_of(merged, lo, hi):
            label = _host_label(bench_line, 0.5 * (s + e))
            gap_ns[label] = gap_ns.get(label, 0.0) + (e - s)
    nd = len(dev_ops)
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / nd,
        n_devices=nd, calls=calls,
        op_seconds={k: v * 1e-9 / nd for k, v in op_s.items()},
        op_counts=op_n,
        gaps=sorted(((k, v * 1e-9 / nd) for k, v in gap_ns.items()),
                    key=lambda kv: -kv[1]))


def _host_label(line, t: float) -> str:
    """The shortest event of ``line`` that covers time ``t``."""
    best, best_len = "outside any host span", float("inf")
    for name, s, e in line:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def reduce_file(path: str) -> Reduced:
    import jax
    return reduce_planes(list(jax.profiler.ProfileData.from_file(path).planes))
