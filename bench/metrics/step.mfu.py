"""Model FLOP utilization of the simulated rounds, from the device trace:
useful client-model FLOPs of the rounds whose calls lie inside the trace's
window span (forward and backward matrix products of the scheduled
clients' tokens only: ``n_scheduled x local_steps x batch x seq`` tokens a
round, see the configuration's ``model_flops_per_token``), over the span's
length in the trace times chips times the chip's bf16 peak, in %. Nothing
is read for a configuration that states no model FLOPs."""


def read(ctx):
    cell = ctx.cell
    data = cell.traffic["data"]
    seq = int(data.get("seq", 1))
    per_token = cell.mod.model_flops_per_token(cell.conf, seq)
    if per_token is None or ctx.rounds == 0 or ctx.trace.window_s <= 0:
        return None
    tokens = (cell.sim["n_scheduled"] * int(data["local_steps"])
              * int(data["batch"]) * seq)
    flops_per_s = per_token * tokens * ctx.rounds / ctx.trace.window_s
    return 100.0 * flops_per_s / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
