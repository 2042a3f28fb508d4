"""A tiny copy of the ``mellum2-client`` cell for the CPU tests: the real
configuration module, configuration and traffic files, loaded by name and
cut to sizes a test run can hold (d 64, 8 of 16 experts held, window 16,
sequences of 64), beside the cells of ``bench/tests/tiny.py``."""
from __future__ import annotations

import json
import os
import shutil

from bench.tests import tiny

CONFIG = "mellum-tiny"
TRAFFIC = "tokens-tiny.dense"
CELL = "mellum-tiny.dense"
COMMITTED = "mellum2-client.dense"


def load(sub: str, name: str) -> dict:
    """A json file of the real ``bench/<sub>/``, read past the harness
    (which a test may point at a tiny directory)."""
    with open(os.path.join(tiny.BENCH, sub, name + ".json")) as f:
        return json.load(f)


def conf() -> dict:
    """``mellum2-client.json`` with its sizes cut; two periods of layers,
    so the period scan runs more than once."""
    c = load("configs", "mellum2-client")
    return dict(
        c, name=CONFIG, hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, moe_intermediate_size=32, num_experts=8,
        num_experts_routed=16, num_experts_per_tok=4, vocab_size=128,
        num_hidden_layers=8, layer_types=c["layer_types"] * 2,
        mlp_layer_types=c["mlp_layer_types"] * 2, sliding_window=16,
        intermediate_size=256, initializer_range=0.2, chunk_size=2,
        reference_block=4)


def traffic() -> dict:
    """``tokens-h2b2s2048.dense`` with sequences of 64, two rounds a call."""
    t = load("traffic", "tokens-h2b2s2048.dense")
    return dict(t, rounds_per_call=2, data=dict(t["data"], seq=64))


def make_bench_dir(root: str) -> dict:
    """``tiny.make_bench_dir`` with the tiny Mellum cell added; returns the
    manifest."""
    manifest = tiny.make_bench_dir(root)
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(tiny.BENCH, "configs", "mellum2-client.py"),
                os.path.join(bench, "configs", CONFIG + ".py"))
    for sub, name, obj in (("configs", CONFIG, conf()),
                           ("traffic", TRAFFIC, traffic()),
                           ("limits", CELL, {"limits": tiny.LIMITS})):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(obj, f, indent=1)
    manifest["workloads"].append({"name": CELL, "config": CONFIG,
                                  "traffic": TRAFFIC, "chips": 1,
                                  "why": "a CPU test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest
