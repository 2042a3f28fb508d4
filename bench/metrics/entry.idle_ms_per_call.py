"""Device-idle milliseconds per call of the entry point: the traced
window's length less the union of device operation intervals (mean over
the cell's chips), over the entry calls made in that window."""


def read(ctx):
    t = ctx.trace
    if t.calls == 0:
        return None
    return (t.window_s - t.busy_s) / t.calls * 1e3
