"""Per-stage reading of a profiler trace, beside ``bench.trace``.

The program names the stages of a simulated round with ``jax.named_scope``
(``fl.channel``, ``fl.schedule``, ``fl.data``, ``fl.local_update``,
``fl.compress``, ``fl.client_state``, ``fl.privacy``, ``fl.aggregate``,
``fl.server_update``, ``fl.log``) and the host side of a call with
``jax.profiler.TraceAnnotation`` spans (``fl.engine_lookup``,
``fl.prepare``, ``fl.dispatch``, ``fl.fetch_logs``).

Over the same window as ``bench.trace`` (the ``bench.window`` span), each
device operation's own time goes to the innermost ``fl.*`` scope of its
HLO instruction, and each idle gap to the innermost ``fl.*`` host span on
the benchmark's thread over the gap's midpoint. An operation whose
instruction carries no such scope, or that runs outside the engine's
module, is unattributed. ``bench.trace``'s numbers are left as they are:
its busy time equals the attributed plus the unattributed time here, and
its idle gaps the sum of the per-span gaps.

The device events carry the HLO instruction's name only, so the scopes
come from the engine's compiled HLO text (``metadata={op_name=
"jit(engine)/while/body/fl.channel/..."}``), keyed by instruction name; the
device's ``XLA Modules`` line says when the engine's module ran.
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import trace

MODULES_LINE = "XLA Modules"
OTHER = "other"                  # idle gaps under no fl.* host span
_SCOPE = re.compile(r"(?:^|[/(])(fl\.[A-Za-z0-9_]+)")
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"',
                    re.M)


@dataclasses.dataclass
class Stages:
    window_s: float
    busy_s: float                    # mean over the device planes
    calls: int
    # own device time per fl.* scope and unattributed, device mean; None
    # where the program names no stage
    stage_seconds: Optional[Dict[str, float]]
    unattributed_s: float
    unattributed_ops: List[Tuple[str, float]]     # longest first
    # idle seconds per innermost fl.* host span (OTHER: under none), device
    # mean; None where the host trace holds no fl.* span
    span_gaps: Optional[Dict[str, float]]


def innermost(path: str) -> Optional[str]:
    """The innermost ``fl.*`` component of an op_name path, or None."""
    found = _SCOPE.findall(path)
    return found[-1] if found else None


def op_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: innermost fl.* scope}) of one
    compiled HLO module's text; instructions with no scope are left out."""
    m = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M)
    out = {}
    for name, path in _INSTR.findall(hlo_text):
        scope = innermost(path)
        if scope is not None:
            out[name] = scope
    return (m.group(1) if m else ""), out


def _inside(t: float, spans: List[trace.Interval]) -> bool:
    return any(s <= t <= e for s, e in spans)


def reduce_planes(planes, module: str, scopes: Dict[str, str]) -> Stages:
    """``planes`` as ``bench.trace.reduce_planes`` takes them; ``module``
    and ``scopes`` as :func:`op_scopes` gives them for the engine."""
    host_lines, devices = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: trace._events(ln) for ln in plane.lines}
            if trace.OPS_LINE in lines:
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            host_lines.extend(trace._events(ln) for ln in plane.lines)
    if not devices:
        raise ValueError("the trace holds no device operations")
    bench_line = next((evs for evs in host_lines
                       if any(n == trace.WINDOW_SPAN for n, _, _ in evs)),
                      None)
    if bench_line is None:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")
    lo, hi = next((s, e) for n, s, e in bench_line if n == trace.WINDOW_SPAN)
    calls = sum(1 for n, s, e in bench_line
                if n == trace.CALL_SPAN and s >= lo and e <= hi)
    spans = [(n, s, e) for n, s, e in bench_line if innermost(n) == n]

    busy = unattr = 0.0
    stage_ns: Dict[str, float] = {}
    unattr_ops: Dict[str, float] = {}
    gap_ns: Dict[str, float] = {}
    for lines in devices:
        # the engine's module intervals; all of the window where the
        # device plane has no line of modules
        runs = [(s, e) for n, s, e in lines.get(MODULES_LINE, [])
                if n.startswith(module)] or [(lo, hi)]
        inside = []
        for name, s, e in lines[trace.OPS_LINE]:
            if e > lo and s < hi:
                op = trace.op_name(name)
                scope = (scopes.get(op) if _inside(0.5 * (s + e), runs)
                         else None)
                inside.append(((op, scope), max(s, lo), min(e, hi)))
        merged = trace.union([(s, e) for _, s, e in inside])
        busy += sum(e - s for s, e in merged)
        for (op, scope), own in trace.self_times(inside):
            if scope is None:
                unattr += own
                unattr_ops[op] = unattr_ops.get(op, 0.0) + own
            else:
                stage_ns[scope] = stage_ns.get(scope, 0.0) + own
        for s, e in trace.gaps_of(merged, lo, hi):
            label = _innermost_span(spans, 0.5 * (s + e))
            gap_ns[label] = gap_ns.get(label, 0.0) + (e - s)
    k = 1e-9 / len(devices)
    return Stages(
        window_s=(hi - lo) * 1e-9, busy_s=busy * k, calls=calls,
        stage_seconds=({n: v * k for n, v in stage_ns.items()}
                       if scopes else None),
        unattributed_s=unattr * k,
        unattributed_ops=sorted(((n, v * k) for n, v in unattr_ops.items()),
                                key=lambda kv: -kv[1]),
        span_gaps=({n: v * k for n, v in gap_ns.items()} if spans else None))


def _innermost_span(spans, t: float) -> str:
    """The shortest ``fl.*`` span that covers time ``t``."""
    best, best_len = OTHER, float("inf")
    for name, s, e in spans:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def engine_hlo(cell) -> Optional[str]:
    """The compiled HLO text of the engine that a ``run_simulation_scan``
    cell calls, lowered again after the window from the weights' shapes;
    None for another entry.

    It is compiled afresh, past JAX's in-memory and persistent caches:
    the persistent cache's key leaves out the source locations that carry
    the scopes, so the executable that ran may have been compiled from a
    program that differs only in its scopes (the same program before
    they were named), and its text would be that program's. XLA names the
    instructions as it did for the timed calls."""
    if cell.traffic["entry"] != "run_simulation_scan":
        return None
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from repro.fl import runtime as rt
    cfg = cell.entry.cfg
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._get_engine(cfg, wcfg, cell.loss_fn, False)
    jax.clear_caches()
    one = SingleDeviceSharding(jax.devices()[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: cell.mod.init_params(cell.conf, k),
                       jax.random.PRNGKey(0)))
    lowered = engine.lower(jax.random.PRNGKey(cfg.seed),
                           rt.wireless.channel_params(wcfg),
                           rt._resolve_cparams(cfg, params),
                           rt._resolve_aparams(cfg), params, None, None)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def of(ctx) -> Optional[Stages]:
    """The stages of a traced run's window, read once per run and kept on
    the metric context; None where the cell's engine cannot be had."""
    if not hasattr(ctx, "stages"):
        ctx.stages = None
        text = engine_hlo(ctx.cell)
        if text is not None:
            import jax
            from bench import harness
            path = trace.find_xplane(os.path.join(
                harness.OUT, "trace", ctx.cell.workload["name"]))
            planes = list(jax.profiler.ProfileData.from_file(path).planes)
            ctx.stages = reduce_planes(planes, *op_scopes(text))
    return ctx.stages


def stage_ms_per_round(ctx, stage: str) -> Optional[float]:
    """Own device milliseconds of scope ``stage`` per simulated round of
    the traced window's calls."""
    st = of(ctx)
    if st is None or st.stage_seconds is None or ctx.rounds == 0:
        return None
    return st.stage_seconds.get(stage, 0.0) / ctx.rounds * 1e3
