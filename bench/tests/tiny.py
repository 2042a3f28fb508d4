"""Tiny copies of the benchmark's cells for the CPU tests: the real
configuration modules, traffic generator and metric readers, with sizes a
test run can hold, in a directory of their own."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLEET = {
    "name": "fleet-tiny", "source": "https://arxiv.org/abs/1902.01046",
    "n_devices": 64, "n_scheduled": 8, "chunk_size": 16, "policy": "random",
    "algorithm": "fedavg", "lr": 0.1, "server_lr": 1.0,
    "model_bits": 1e6, "comp_latency_s": 0.05,
    "model": "linear", "d": 32, "dtype": "float32",
    "reference_block": 64, "reduced": []}

GPT2 = {
    "name": "gpt2-tiny", "source": "https://huggingface.co/openai-community/gpt2",
    "n_layer": 2, "n_embd": 64, "n_head": 2, "head_dim": 32, "n_inner": 256,
    "vocab_size": 128, "n_positions": 32, "layer_norm_epsilon": 1e-5,
    "departures": {"layer_norm_epsilon": 1e-6},
    "initializer_range": 0.02, "dtype": "float32", "remat": True,
    "n_devices": 4, "n_scheduled": 2, "chunk_size": 2, "policy": "random",
    "algorithm": "fedavg", "lr": 0.01, "server_lr": 1.0,
    "model_bits": "32*D", "comp_latency_s": 0.05,
    "model": "gpt2", "reference_block": 1, "reduced": []}

TRAFFIC = {
    "linear-tiny.topk-ef": {
        "entry": "run_simulation_scan", "rounds_per_call": 3,
        "compression": {"name": "topk", "fraction": 0.01},
        "data": {"kind": "linear", "local_steps": 2, "batch": 8,
                 "noise": 0.01, "target_seed": 0}},
    "linear-tiny.dense": {
        "entry": "run_simulation_scan", "rounds_per_call": 3,
        "compression": {"name": "none"},
        "data": {"kind": "linear", "local_steps": 2, "batch": 8,
                 "noise": 0.01, "target_seed": 0}},
    "tokens-tiny.topk-ef": {
        "entry": "run_simulation_scan", "rounds_per_call": 2,
        "compression": {"name": "topk", "fraction": 0.01},
        "data": {"kind": "tokens", "local_steps": 2, "batch": 2, "seq": 16,
                 "n_classes": 4}},
}

CELLS = [("fleet-tiny.topk-ef", "fleet-tiny", "linear-tiny.topk-ef",
          "fleet-1e5"),
         ("fleet-tiny.dense", "fleet-tiny", "linear-tiny.dense", "fleet-1e5"),
         ("gpt2-tiny.topk-ef", "gpt2-tiny", "tokens-tiny.topk-ef",
          "gpt2-small-client")]

# float32 on the CPU: the program reads about 1e-7 on loss and change and
# up to about 1e-5 on the clock (float32 against the reference's float64);
# the bfloat16 control reads 7e-4 to 5e-3 on loss and 2e-3 to 5e-2 on change
LIMITS = {"sched_mismatches": 0, "bits_rel_gap": 1e-5, "clock_rel_gap": 1e-4,
          "loss_rel_gap": 1e-4, "change_rel_gap": 1e-3}


def _dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_bench_dir(root: str) -> dict:
    """A checkout-like ``root`` whose ``bench/`` holds the tiny cells;
    returns its manifest."""
    bench = os.path.join(root, "bench")
    for sub in ("traffic", "metrics", "entries", "policies", "compressors"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub))
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    for conf in (FLEET, GPT2):
        _dump(os.path.join(bench, "configs", conf["name"] + ".json"), conf)
    for name, traffic in TRAFFIC.items():
        _dump(os.path.join(bench, "traffic", name + ".json"), traffic)
    workloads = []
    for cell, conf, traffic, real in CELLS:
        shutil.copy(os.path.join(BENCH, "configs", real + ".py"),
                    os.path.join(bench, "configs", conf + ".py"))
        _dump(os.path.join(bench, "limits", cell + ".json"),
              {"limits": LIMITS})
        workloads.append({"name": cell, "config": conf, "traffic": traffic,
                          "chips": 1, "why": "a CPU test"})
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"] = workloads
    for m in manifest["per_layer"]:
        m.pop("workloads", None)
    _dump(os.path.join(root, "BENCHMARK.json"), manifest)
    return manifest
