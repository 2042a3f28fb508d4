"""Witness for the top-k fault left open in ``PERF.md``: how many
coordinates a row keeps, counted on the chip for the three top-k
operators at once, and what keeping one more than ``k`` does to a cell's
compared numbers.

    python3 bench/witness_topk.py [--seed 1] [--workload <cell>] [--traffic <name>]

Prints one JSON line per reading:

* ``kept``: for random (rows, d) blocks at the cells' shapes, the least
  and most nonzeros per row kept by the program's row kernel
  (``repro.kernels.ops.topk_rows``, Pallas on the chip), by the program's
  registry operator (``topk`` of ``repro.core.compression.registry``,
  vmapped over rows; the engine's path below 2^20 elements) and by the
  plain reference (``bench/compressors/topk.py``);
* ``k_plus_one``: the reference keeping ``k + 1`` against the reference
  keeping ``k`` over one call of ``--workload`` (with ``--traffic`` in
  place of its own), as ``bench/check.py`` reads a program: what the
  kernel's extra coordinate can move there.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--workload", default="gpt2-small-client.topk-ef")
    ap.add_argument("--traffic", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from bench import check, harness
    from bench.compressors import topk as ref_topk
    from repro.core.compression.registry import (compression_params,
                                                 get_compressor)
    from repro.kernels import ops
    harness.use_compile_cache()
    harness.device_gate(1)

    def counts(rows):
        nz = jnp.sum(rows != 0, axis=1)
        return [int(jnp.min(nz)), int(jnp.max(nz))]

    registry = jax.jit(jax.vmap(get_compressor("topk"), in_axes=(None, 0, 0)))
    for rows, d, frac in ((4096, 32, 0.01), (2, 124402944, 0.01)):
        k = ref_topk.params({"fraction": frac}, d)["k"]
        x = jax.random.normal(jax.random.PRNGKey(args.seed), (rows, d),
                              jnp.float32)
        kept = {"kernel": counts(ops.topk_rows(x, k))}
        keys = jax.random.split(jax.random.PRNGKey(0), rows)
        kept["registry"] = counts(registry(compression_params(k=k), keys,
                                           x)[0])
        kept["reference"] = counts(jax.jit(jax.vmap(
            lambda r: ref_topk.compress(r, {"k": k}, jnp.float32)))(x))
        print(json.dumps({"reading": "kept", "rows": rows, "d": d, "k": k,
                          "seed": args.seed, **kept}), flush=True)
        del x

    wl = harness.find_workload(harness.load_manifest(), args.workload)
    if args.traffic:
        wl = dict(wl, traffic=args.traffic)
    cell = harness.build_cell(wl, args.seed)
    cell.params0 = None
    seed = cell.call_seed(1)
    ref = check.reference_call(cell, seed)
    k = cell.sim["compression_params"]["k"]
    sim = dict(cell.sim, compression_params={"k": k + 1})
    more = check.reference_call(cell, seed, sim)
    reads = check.readings(check.as_logs(more), more["norms"], ref,
                           ref["norms"])
    print(json.dumps({"reading": "k_plus_one", "workload": args.workload,
                      "traffic": wl["traffic"],
                      "seed": args.seed, "k": k,
                      "loss_rel_gap": reads["loss_rel_gap"],
                      "change_rel_gap": reads["change_rel_gap"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
