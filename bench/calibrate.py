"""Readings that the correctness limits are set from, on the chip, at the
cell's own size, in one process:

* the program: the cell's entry, called ``--calls`` times (call seeds)
  for each run seed, each simulation of a call compared with the
  reference;
* the control: the reference computed in bfloat16 put in the program's
  place, compared with the float32 reference on each control seed's
  first call.

    python3 bench/calibrate.py --workload <cell> --seeds 101-104 --calls 3 \\
        --control-seeds 101-103 [--n-devices N] [--traffic NAME] \\
        [--precision highest|default] [--out FILE]

Witness runs: ``--n-devices`` runs the cell at another population size
(where the program takes another path), ``--traffic`` with another
traffic file, and ``--precision`` computes the float32 reference at
another matrix-product precision. One JSON line per reading goes to
standard output and to ``--out``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seed_list(text: str):
    """``"1-3,7"`` -> [1, 2, 3, 7]."""
    out = []
    for part in text.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--calls", type=int, default=1,
                    help="calls (call seeds) per run seed")
    ap.add_argument("--n-devices", type=int, default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--precision", default="highest")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax.numpy as jnp
    import numpy as np
    from bench import check, harness
    harness.use_compile_cache()
    wl = harness.find_workload(harness.load_manifest(), args.workload)
    if args.traffic:
        wl = dict(wl, traffic=args.traffic)
    devices = harness.device_gate(int(wl["chips"]))
    overrides = {"n_devices": args.n_devices} if args.n_devices else None
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    prog_seeds, ctl_seeds = seed_list(args.seeds), seed_list(args.control_seeds)
    for seed in sorted(set(prog_seeds) | set(ctl_seeds)):
        cell = harness.build_cell(wl, seed, devices, overrides)
        for j in range(1, args.calls + 1):
            tag = {"workload": args.workload, "traffic": wl["traffic"],
                   "seed": seed, "call": j,
                   "n_devices": cell.sim["n_devices"],
                   "precision": args.precision}
            t = time.perf_counter()
            ref = check.reference_call(cell, cell.call_seed(j),
                                       precision=args.precision)
            t_ref = time.perf_counter() - t
            if seed in prog_seeds:
                t = time.perf_counter()
                res = cell.call(j)
                for sim, _, logs, final in cell.answers(j, res):
                    norms = np.asarray(cell.leaf_norms(final, cell.params0))
                    emit({**tag, "side": "program",
                          "seconds_program": time.perf_counter() - t,
                          "seconds_reference": t_ref,
                          **check.readings(logs, norms, ref, ref["norms"])})
                del res
            if seed in ctl_seeds and j == 1:
                ctl = check.reference_call(cell, cell.call_seed(j),
                                           dtype=jnp.bfloat16,
                                           precision="default")
                emit({**tag, "side": "control",
                      **check.readings(check.as_logs(ctl), ctl["norms"],
                                       ref, ref["norms"])})
            del ref
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
