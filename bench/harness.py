"""The benchmark harness: one run of one cell.

Everything that belongs to a cell is found by name. ``BENCHMARK.json``
names the cell's configuration and traffic; the configuration is
``bench/configs/<config>.json`` (its sizes) with ``<config>.py`` beside it
(weights, the program's loss, the plain reference loss, FLOPs); the traffic
is ``bench/traffic/<traffic>.json``, read by ``bench/traffic/gen.py``, and
names the entry point that a call drives (``bench/entries/<entry>.py``)
and the compressor (``bench/compressors/<name>.py``); the configuration
names the scheduling policy (``bench/policies/<name>.py``); each per-layer
metric is ``bench/metrics/<metric>.py``; each cell's correctness limits are
``bench/limits/<cell>.json``; the chip peaks are ``bench/peaks.json``. A
new cell, configuration, entry, policy, compressor or metric is a set of
new files and ``BENCHMARK.json`` entries.

A run: check the device; build the configuration from the seed (weights
and data made on the device) and place it on the cell's chips through the
entry; warm up the cell's one program through the entry; call the entry in
a loop for ``--seconds``, each call ended by ``block_until_ready``; read
the peak memory; free the program's state; and compare a sample of the
window's calls, drawn from the seed, with the plain reference
(``bench/check.py``). A call during which anything is traced or compiled
counts as failed.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, "bench_out")
GIB = float(2 ** 30)
TRACE_SECONDS = 2.0   # the traced part of a --trace 1 window


class BenchError(Exception):
    """A run that cannot produce a result (exit code 2, no result line)."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_manifest() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(*parts) -> Dict:
    path = os.path.join(BENCH, *parts)
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, tag: str):
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in tag)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str):
    """(sizes, module) of ``bench/configs/<name>.{json,py}``."""
    conf = _load_json("configs", name + ".json")
    mod = _load_module(os.path.join(BENCH, "configs", name + ".py"),
                       "config_" + name)
    return conf, mod


def load_traffic(name: str) -> Dict:
    return _load_json("traffic", name + ".json")


def load_limits(cell: str) -> Dict[str, float]:
    return _load_json("limits", cell + ".json")["limits"]


def _load_part(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} {name!r}: bench/{kind}/{name}.py "
                         f"is missing")
    return _load_module(path, kind + "_" + name)


def load_metric(name: str):
    return _load_part("metrics", name)


def load_entry(name: str):
    """``bench/entries/<name>.py``: builds the timed call of a cell."""
    return _load_part("entries", name)


def load_policy(name: str):
    """``bench/policies/<name>.py``: the reference's scheduling policy."""
    return _load_part("policies", name)


def load_compressor(name: str):
    """``bench/compressors/<name>.py``: the reference's compressor."""
    return _load_part("compressors", name)


def load_peaks(kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    table = _load_json("peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def find_workload(manifest: Dict, name: str) -> Dict:
    for wl in manifest["workloads"]:
        if wl["name"] == name:
            return wl
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def derive_seed(seed: int, *tags) -> int:
    """A 31-bit seed for one use of the run's seed, distinct per tag: any
    whole number maps to one of 2^31, so large seeds stay distinct."""
    h = hashlib.blake2b(repr((int(seed),) + tags).encode(), digest_size=8)
    return int.from_bytes(h.digest(), "little") % (2 ** 31)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def device_gate(chips: int, allow_platform: Optional[str] = None):
    """The first ``chips`` TPU devices; anything else is an error naming
    the platform JAX found."""
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu" and plat != allow_platform:
        raise BenchError(f"needs a TPU; JAX found platform {plat!r} "
                         f"({devs[0].device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)} {plat} device(s)")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache, at ``$JAX_COMPILATION_CACHE_DIR``
    where set and else at the fixed ``<checkout>/.jax_cache``; every
    program is written to it, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces, backend compiles and persistent-cache loads, from
    JAX's monitoring events and the engine's own trace counter."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.n = 0

        def on_event(name, *args, **kwargs):
            if name in self.EVENTS:
                self.n += 1
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def count(self) -> int:
        from repro.fl import runtime as rt
        return self.n + rt.ENGINE_STATS["traces"]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    workload: Dict
    conf: Dict
    mod: Any
    traffic: Dict
    seed: int
    sim: Dict                # the simulation as the reference reads it
    loss_fn: Callable        # the program's loss (one identity per run)
    params0: Any
    datagen: Callable
    d: int
    leaf_norms: Callable     # jitted per-leaf change norms
    entry: Any = None        # built by the traffic's entry module

    @property
    def rounds_per_call(self) -> int:
        return self.sim["rounds"]

    def call_seed(self, j: int) -> int:
        return derive_seed(self.seed, "call", j)

    def call(self, j: int):
        """Call ``j`` of the entry point, with the call's own seed. Every
        call has the same sizes and work."""
        return self.entry.call(self.call_seed(j))

    def answers(self, j: int, out):
        """Per simulation of call ``j``: (sim, sim seed, logs, final)."""
        return self.entry.answers(self.call_seed(j), out)


def build_cell(workload: Dict, seed: int, devices=None,
               overrides: Optional[Dict] = None) -> Cell:
    """The cell from the seed, placed on ``devices`` (the first chips JAX
    has, where not given) by the traffic's entry module. ``overrides``
    replaces sizes of the configuration (a witness run at another size)."""
    import jax
    import jax.numpy as jnp
    from bench.traffic.gen import make_datagen

    conf, mod = load_config(workload["config"])
    conf = dict(conf, **(overrides or {}))
    traffic = load_traffic(workload["traffic"])
    entry = load_entry(traffic["entry"])
    comp = traffic["compression"]
    compressor = load_compressor(comp["name"])
    load_policy(conf["policy"])
    params0 = jax.jit(lambda k: mod.init_params(conf, k))(
        jax.random.PRNGKey(derive_seed(seed, "weights")))
    d = sum(x.size for x in jax.tree.leaves(params0))
    model_bits = (32.0 * d if conf["model_bits"] == "32*D"
                  else float(conf["model_bits"]))
    sim = {"n_devices": conf["n_devices"], "n_scheduled": conf["n_scheduled"],
           "rounds": traffic["rounds_per_call"], "policy": conf["policy"],
           "compression": comp["name"],
           "compression_params": compressor.params(comp, d),
           "lr": conf["lr"], "server_lr": conf["server_lr"],
           "model_bits": model_bits, "comp_latency_s": conf["comp_latency_s"]}

    @jax.jit
    def leaf_norms(final, start):
        return jnp.stack([jnp.linalg.norm((a - b).reshape(-1))
                          for a, b in zip(jax.tree.leaves(final),
                                          jax.tree.leaves(start))])

    cell = Cell(workload, conf, mod, traffic, seed, sim,
                mod.program_loss(conf), params0,
                make_datagen(traffic["data"], conf), d, leaf_norms)
    if devices is None:
        devices = jax.devices()[:int(workload["chips"])]
    cell.entry = entry.build(cell, list(devices))
    return cell


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Sample:
    """One simulation of a sampled window call, as the check reads it."""
    sim: Dict
    sim_seed: int
    logs: Any
    leaf_norms: Any


def run(workload_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, allow_platform: Optional[str] = None,
        manifest: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result object (the last stdout
    line). ``allow_platform`` lets the tests drive a run on the CPU."""
    import numpy as np
    manifest = manifest or load_manifest()
    wl = find_workload(manifest, workload_name)
    use_compile_cache()
    devices = device_gate(int(wl["chips"]), allow_platform)
    import jax
    from jax import profiler
    counter = CompileCounter()
    cell = build_cell(wl, seed, devices)
    limits = load_limits(workload_name)

    def sampled(j, out):
        return [Sample(sim, s, logs, np.asarray(
            cell.leaf_norms(final, cell.params0)))
            for sim, s, logs, final in cell.answers(j, out)]

    # warm-up: the window's own program on the window's sizes
    with profiler.TraceAnnotation("bench.warmup"):
        out = cell.call(0)
        jax.block_until_ready(out)
        sampled(0, out)
    del out
    # tracing the engine leaves many objects behind, and a full garbage
    # collection inside the window would pause a call: collect once in
    # set-up and keep the collector off for the window
    gc.collect()
    gc.freeze()
    gc.disable()
    t_setup = time.perf_counter() - t_start

    trace_dir = os.path.join(OUT, "trace", workload_name)
    rng = np.random.default_rng(derive_seed(seed, "sample"))
    samples: List[Sample] = []
    counts = {"attempted": 0, "failed": 0}

    def one_call():
        j = counts["attempted"] + 1
        before = counter.count()
        with profiler.TraceAnnotation("bench.call"):
            out = cell.call(j)
            jax.block_until_ready(out)
        counts["failed"] += counter.count() != before
        counts["attempted"] = j
        # one of the window's calls, drawn from the seed (a reservoir
        # sample of size one: call j replaces the kept one with odds 1/j)
        if not samples or rng.integers(0, j) == 0:
            with profiler.TraceAnnotation("bench.sample"):
                samples[:] = sampled(j, out)

    t0 = time.perf_counter()
    traced = None
    if trace:
        # the profiler covers the window's first calls, TRACE_SECONDS or
        # at least one call; the rest of the window runs untraced
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler.start_trace(trace_dir)
        with profiler.TraceAnnotation("bench.window"):
            while True:
                one_call()
                if time.perf_counter() - t0 >= min(seconds, TRACE_SECONDS):
                    break
        traced = time.perf_counter() - t0
        profiler.stop_trace()
        t0 = time.perf_counter() - traced
    while counts["attempted"] == 0 or time.perf_counter() - t0 < seconds:
        one_call()
    elapsed = time.perf_counter() - t0
    gc.enable()
    gc.unfreeze()
    attempted, failed = counts["attempted"], counts["failed"]
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(_peak_bytes(st) for st in stats)
    rounds = cell.rounds_per_call * cell.entry.variants * attempted

    # free the program's state before the reference runs
    cell.params0 = None
    import bench.check as check
    numbers = check.compare_samples(cell, samples, limits)
    correct = all(v["value"] <= v["limit"] for v in numbers.values())

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": attempted, "failed": failed}
    if trace:
        from bench.trace import find_xplane, reduce_file
        red = reduce_file(find_xplane(trace_dir))
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = MetricContext(
            cell=cell, trace=red,
            rounds=cell.rounds_per_call * cell.entry.variants * red.calls,
            chips=len(devices), peaks=load_peaks(dev0.device_kind))
        result["metrics"] = per_layer_metrics(manifest, wl, ctx)
        result["breakdown"] = red.breakdown()
    else:
        result["metrics"] = {
            "sim_rounds_per_s": {"value": rounds / elapsed,
                                 "unit": "rounds/s"},
            "peak_hbm_gib": {"value": peak / GIB, "unit": "GiB"},
            "setup_s": {"value": t_setup, "unit": "s"},
        }
    result["device"] = device
    result["memory_stats"] = stats
    result["checks"] = numbers
    return result


def _peak_bytes(stats: Dict) -> int:
    """A chip's peak HBM: the allocator's peak of live buffers plus what
    the runtime holds reserved (``bytes_reserved``, where reported)."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("bytes_reserved", 0))


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader sees."""
    cell: Cell
    trace: Any          # bench.trace.Reduced
    rounds: int         # simulated rounds of the calls inside the trace's
                        # window span, over every variant
    chips: int
    peaks: Dict[str, float]


def per_layer_metrics(manifest: Dict, wl: Dict, ctx: MetricContext) -> Dict:
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and wl["name"] not in m["workloads"]:
            continue
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
