"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes
``BENCH_engine.json`` (name -> us_per_call) so the perf trajectory is
machine-trackable across PRs.

Run: ``PYTHONPATH=src python -m benchmarks.run [--fast] [--out PATH]``.
``--fast`` caps simulated round counts for smoke use.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (bench_algorithms, bench_compression,
                        bench_decentralized, bench_faults, bench_fleet,
                        bench_hfl, bench_kernels, bench_privacy,
                        bench_rs_rr_pf, bench_scheduling, bench_sweep,
                        bench_update_aware)
from benchmarks import common
from repro.core import compat

MODULES = [
    ("scheduling(fig1)", bench_scheduling),
    ("update_aware(fig2)", bench_update_aware),
    ("hfl(table1)", bench_hfl),
    ("compression(sec2)", bench_compression),
    ("algorithms(registry)", bench_algorithms),
    ("rs_rr_pf(eqs50-56)", bench_rs_rr_pf),
    ("kernels", bench_kernels),
    ("fleet(chunked-engine)", bench_fleet),
    ("faults(failure-aware)", bench_faults),
    ("privacy(secagg+dp)", bench_privacy),
    ("decentralized(gossip+fog)", bench_decentralized),
    # last: it clears the engine cache to time cold-cache compile+dispatch
    ("sweep(mega)", bench_sweep),
]


def write_json(path: str) -> None:
    """Write the machine-readable table from ``common.ROWS``.

    Metric rows record their actual per-metric ``value`` (final losses,
    speedups, ...); timing rows record ``us_per_call``. Rows with neither a
    value nor a positive timing (string-valued deriveds) are skipped — they
    carry no numeric signal.
    """
    table = {}
    for name, us, value, _ in common.ROWS:
        if value is not None:
            table[name] = float(value)
        elif us > 0:
            table[name] = float(us)
    with open(path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    print(f"# wrote {path} ({len(table)} entries)", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="cap simulated rounds for a quick smoke run")
    ap.add_argument("--out", default=None,
                    help="machine-readable output path (name -> us_per_call);"
                         " defaults to BENCH_engine.json, or"
                         " BENCH_engine_fast.json under --fast so smoke runs"
                         " never clobber the tracked numbers")
    args = ap.parse_args(argv)
    common.FAST = args.fast
    compat.use_compile_cache()
    if args.out is None:
        args.out = "BENCH_engine_fast.json" if args.fast else "BENCH_engine.json"

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in MODULES:
        t0 = time.time()
        try:
            mod.main()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,FAILED:{type(e).__name__}:{e}")
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    if failures:
        print(f"# {failures} module(s) failed; not writing {args.out} "
              "(partial table would clobber tracked numbers)", file=sys.stderr)
        raise SystemExit(1)
    write_json(args.out)


if __name__ == "__main__":
    main()
