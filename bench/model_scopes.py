"""Per-layer time of the client model, read from a traced run beside
``bench.stages``.

The program names the layers of its client model with ``jax.named_scope``:
``model.attn.sliding`` and ``model.attn.full`` (a layer's attention: its
projections, RoPE and the attention itself), and inside a mixture-of-experts
layer ``model.moe.route`` (router, top-k, the sort and the gather of the
routed rows), ``model.moe.experts`` (the grouped products) and
``model.moe.combine`` (the gated scatter back). The scopes sit inside the
engine's ``fl.local_update`` stage, whose reading they leave as it is.

Each device operation of the engine's module in the traced window gives
its own time to the innermost ``model.*`` scope of its HLO instruction, as
``bench.stages`` does for the ``fl.*`` scopes. XLA's ragged-dot kernels
(instructions named ``ragged-dot*``) carry no source metadata; the only
ragged products of the program are the grouped products of its MoE
layers, so they count as ``model.moe.experts``.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional, Tuple

from bench import stages, trace

_SCOPE = re.compile(r"(?:^|[/(])(model\.[A-Za-z0-9_.]+)")
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"',
                    re.M)
RAGGED_DOT = "ragged-dot"
EXPERTS = "model.moe.experts"


def op_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: innermost model.* scope}) of one
    compiled HLO module's text; instructions under no such scope are left
    out, but for the ragged products."""
    m = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M)
    out = {}
    for name, path in _INSTR.findall(hlo_text):
        found = _SCOPE.findall(path)
        if found:
            out[name] = found[-1]
        elif name.startswith(RAGGED_DOT):
            out[name] = EXPERTS
    return (m.group(1) if m else ""), out


def engine_trace(ctx):
    """(the cell's engine's compiled HLO text, the traced window's
    planes), made once per run and kept on the metric context as
    ``ctx.engine_trace``; None where the cell's engine cannot be had. The
    compile (``stages.engine_hlo``, past every cache) is the costly part:
    ``bench.stages.of`` still makes its own, and can take this pair
    instead."""
    if not hasattr(ctx, "engine_trace"):
        ctx.engine_trace = None
        text = stages.engine_hlo(ctx.cell)
        if text is not None:
            import jax
            from bench import harness
            path = trace.find_xplane(os.path.join(
                harness.OUT, "trace", ctx.cell.workload["name"]))
            planes = list(jax.profiler.ProfileData.from_file(path).planes)
            ctx.engine_trace = (text, planes)
    return ctx.engine_trace


def of(ctx) -> Optional[Dict[str, float]]:
    """Own device seconds per model.* scope in the traced window, read once
    per run and kept on the metric context; None where the cell's engine
    cannot be had or names no model scope."""
    if not hasattr(ctx, "model_scopes"):
        ctx.model_scopes = None
        got = engine_trace(ctx)
        if got is not None:
            text, planes = got
            module, scopes = op_scopes(text)
            if scopes:
                ctx.model_scopes = stages.reduce_planes(
                    planes, module, scopes).stage_seconds
    return ctx.model_scopes


def ms_per_round(ctx, prefix: str) -> Optional[float]:
    """Own device milliseconds per simulated round of the scopes named
    ``prefix`` or under it; None where none of them ran."""
    secs = of(ctx)
    if not secs or ctx.rounds == 0:
        return None
    found = [v for k, v in secs.items()
             if k == prefix or k.startswith(prefix + ".")]
    return sum(found) / ctx.rounds * 1e3 if found else None
