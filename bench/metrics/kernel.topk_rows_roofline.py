"""Share of the top-k row kernel's roofline, in %: the least time its
calls could take on the chip over their summed device time in the trace.

One call of the kernel (``repro.kernels.ops.topk_rows``, one block of
clients in one round) reads its (rows, D) float32 operand and writes one
of the same shape; its arithmetic is one comparison per element per
bisection step, far below the bytes' bound, so the least time of a call is
``max(flops / bf16 peak, bytes / HBM bandwidth)`` with ``bytes = 2 * rows *
D * 4``. The calls are found in the trace by their operation name."""

# the HLO name the compiled engine gives the Pallas kernel's custom call
# (``topk_rows.9``), after the function that ``pallas_call`` wraps
PATTERN = r"^topk_rows"


def kernel_cost(rows: int, d: int, itemsize: int = 4,
                steps: int = 25) -> tuple:
    """(flops, bytes) of one call on a (rows, d) operand."""
    return float(steps * rows * d), float(2 * rows * d * itemsize)


def block_rows(cell) -> int:
    n, chunk = cell.sim["n_devices"], cell.conf["chunk_size"]
    return chunk if chunk and chunk < n else n


def read(ctx):
    n, secs = ctx.trace.ops_matching(PATTERN)
    if n == 0 or secs <= 0:
        return None
    flops, nbytes = kernel_cost(block_rows(ctx.cell), ctx.cell.d)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * (n / ctx.trace.n_devices) / secs
