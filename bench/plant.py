"""Run a cell with a fault planted under its timed path (``bench/faults.py``)
on the chip, at the cell's own size and against its committed limits, once
per seed in one process; print one JSON line per run.

    python3 bench/plant.py --workload <cell> --fault <name> --seeds 1-3 \
        [--seconds 2] [--out FILE]

A run that comes out correct did not catch the fault (``caught`` false).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import faults, harness
    from bench.calibrate import seed_list
    if args.fault not in faults.NAMES:
        print(f"plant: no fault {args.fault!r} (known: {faults.NAMES})",
              file=sys.stderr)
        return 2
    faults.install(args.fault)
    out = open(args.out, "a") if args.out else None
    for seed in seed_list(args.seeds):
        res = harness.run(args.workload, seed, args.seconds, False,
                          time.perf_counter())
        rec = {"workload": args.workload, "fault": args.fault, "seed": seed,
               "caught": not res["correct"], "attempted": res["attempted"],
               "checks": res["checks"]}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
