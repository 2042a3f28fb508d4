"""Device milliseconds of the top-k row kernel per simulated round: the
summed own device time of its calls in the traced calls over the rounds
completed there. The calls are the operations that
``kernel.topk_rows_roofline`` matches."""


def read(ctx):
    from bench.harness import load_metric
    pattern = load_metric("kernel.topk_rows_roofline").PATTERN
    n, secs = ctx.trace.ops_matching(pattern)
    if n == 0 or ctx.rounds == 0:
        return None
    return secs / ctx.rounds * 1e3
