"""Record ``trace_v5e_stages.json.gz``: the traced calls (0.1 s of them)
of a small fleet cell with the top-k row kernel and dense error feedback
(32768 clients in blocks of 4096, d = 32, 3 rounds a call), run by the
harness on a TPU, with the engine's op-name scopes beside it.

    python3 bench/tests/record_stages_trace.py      # on the chip

Kept: the device's ``XLA Ops`` and ``XLA Modules`` lines, the
``bench.*`` and ``fl.*`` spans of the benchmark's host thread, and the
innermost ``fl.*`` scope of each engine operation that ran
(``bench/stages.py``).
"""
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "fleet-small.topk-ef"
SEED = 20261017


def _small_bench(bench: str) -> dict:
    """A copy of ``bench/`` that holds the small cell; its manifest."""
    from bench import harness
    shutil.copytree(harness.BENCH, bench)
    conf = harness._load_json("configs", "fleet-1e5.json")
    conf.update(name="fleet-small", n_devices=32768, reference_block=32768)
    traffic = dict(harness.load_traffic("linear-h2b8.topk-ef"),
                   rounds_per_call=3)
    limits = harness._load_json("limits", "fleet-1e5.dense.json")
    for rel, obj in (("configs/fleet-small.json", conf),
                     ("traffic/linear-h2b8r3.topk-ef.json", traffic),
                     ("limits/" + CELL + ".json", limits)):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)
    shutil.copy(os.path.join(bench, "configs", "fleet-1e5.py"),
                os.path.join(bench, "configs", "fleet-small.py"))
    manifest = harness.load_manifest()
    manifest["workloads"] = [{"name": CELL, "config": "fleet-small",
                              "traffic": "linear-h2b8r3.topk-ef",
                              "chips": 1, "why": "a recorded trace"}]
    for m in manifest["per_layer"]:
        m.pop("workloads", None)
    return manifest


def _keep(planes, scopes):
    from bench.trace import op_name
    out = []
    for p in planes:
        if p.name.startswith("/device:"):
            lines = {ln.name: [[op_name(ev.name), ev.start_ns,
                                ev.duration_ns] for ev in ln.events]
                     for ln in p.lines
                     if ln.name in ("XLA Ops", "XLA Modules")}
        else:
            lines = {ln.name: [[ev.name, ev.start_ns, ev.duration_ns]
                               for ev in ln.events
                               if ev.name.startswith(("bench.", "fl."))]
                     for ln in p.lines
                     if any(ev.name == "bench.window" for ev in ln.events)}
        if lines:
            out.append({"name": p.name, "lines": [
                {"name": k, "events": v} for k, v in lines.items()]})
    ran = {ev[0] for p in out for ln in p["lines"] for ev in ln["events"]}
    return out, {k: v for k, v in scopes.items() if k in ran}


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    t0 = time.perf_counter()
    import jax
    from bench import harness, stages, trace
    tmp = tempfile.mkdtemp()
    try:
        manifest = _small_bench(os.path.join(tmp, "bench"))
        harness.BENCH = os.path.join(tmp, "bench")
        res = harness.run(CELL, SEED, 0.1, True, t0, manifest=manifest)
        cell = harness.build_cell(harness.find_workload(manifest, CELL),
                                  SEED)
        module, scopes = stages.op_scopes(stages.engine_hlo(cell))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    path = trace.find_xplane(os.path.join(harness.OUT, "trace", CELL))
    planes, scopes = _keep(jax.profiler.ProfileData.from_file(path).planes,
                           scopes)
    rec = {"source": (f"{res['device']['kind']}, the traced calls of {CELL} "
                      "(32768 clients in blocks of 4096, d = 32, top-k with dense "
                      "EF, 3 rounds) traced by bench/run.py's profiler"),
           "module": module, "scopes": scopes, "planes": planes}
    out = os.path.join(HERE, "trace_v5e_stages.json.gz")
    with gzip.open(out, "wt") as f:
        json.dump(rec, f, separators=(",", ":"))
    print(json.dumps({"metrics": res["metrics"],
                      "bytes": os.path.getsize(out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
