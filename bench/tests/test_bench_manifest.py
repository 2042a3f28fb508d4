"""BENCHMARK.json against the benchmark's contract: names, units, files
found by name, and a cell added from data files alone."""
import json
import os
import re

import pytest

from bench import harness
from bench.tests import tiny

ROOT = os.path.dirname(tiny.BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_CHARS = re.compile(r"^[A-Za-z0-9_./-]+$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert 1 <= manifest["run_seconds"] <= 51


def test_names_and_units(manifest):
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    names += [w["name"] for w in manifest["workloads"]]
    names += [c["name"] for c in manifest["configs"]]
    names += [w["config"] for w in manifest["workloads"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for root, _, files in os.walk(os.path.join(ROOT, "bench")):
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            assert PATH_CHARS.match(rel), rel


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for w in manifest["workloads"]:
        assert w["config"] in configs, w
        conf, mod = harness.load_config(w["config"])
        assert callable(mod.init_params) and callable(mod.reference_loss)
        traffic = harness.load_traffic(w["traffic"])
        assert callable(harness.load_entry(traffic["entry"]).build)
        comp = harness.load_compressor(traffic["compression"]["name"])
        assert callable(comp.compress) and callable(comp.bits)
        assert callable(harness.load_policy(conf["policy"]).schedule)
        assert set(harness.load_limits(w["name"])) == {
            "sched_mismatches", "bits_rel_gap", "clock_rel_gap",
            "loss_rel_gap", "change_rel_gap"}
        assert w["chips"] in (1, 4)
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", ())) <= cells
        assert callable(harness.load_metric(m["name"]).read)


def test_unknown_device_kind_is_refused():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")


def test_seeds_past_32_bits_stay_distinct():
    a = harness.derive_seed(2 ** 33 + 5, "call", 1)
    b = harness.derive_seed(5, "call", 1)
    assert a != b and 0 <= a < 2 ** 31


def test_a_cell_added_from_data_files_alone(tmp_path, monkeypatch):
    """A throwaway cell: one new traffic file, one limits file and one
    manifest entry, no code; the harness runs it by name."""
    manifest = tiny.make_bench_dir(str(tmp_path))
    bench = os.path.join(str(tmp_path), "bench")
    traffic = dict(tiny.TRAFFIC["linear-tiny.dense"], rounds_per_call=2)
    traffic["data"] = dict(traffic["data"], batch=4)
    with open(os.path.join(bench, "traffic", "linear-b4.dense.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "limits", "fleet-tiny.b4.json"), "w") as f:
        json.dump({"limits": tiny.LIMITS}, f)
    manifest["workloads"].append(
        {"name": "fleet-tiny.b4", "config": "fleet-tiny",
         "traffic": "linear-b4.dense", "chips": 1, "why": "a throwaway"})
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "")
    res = harness.run("fleet-tiny.b4", 7, 0.2, False, 0.0,
                      allow_platform="cpu", manifest=manifest)
    assert res["correct"] and res["attempted"] >= 1
    assert res["metrics"]["sim_rounds_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


BEST_CHANNEL = '''"""Policy ``best_channel``: the clients of highest uplink SNR."""
import numpy as np


def schedule(key, n, k, channel):
    mask = np.zeros(n, bool)
    mask[np.argsort(-channel["snr"], kind="stable")[:k]] = True
    return mask
'''

SCAN_ON_LAST_CHIP = '''"""Entry ``scan_on_last_chip``: the scan engine on the cell's last chip."""
import dataclasses


class Entry:
    variants = 1

    def __init__(self, cell, devices):
        import jax
        from repro.core.algorithms.registry import algo_params
        from repro.core.compression.registry import compression_params
        from repro.fl import runtime as rt
        self.cell = cell
        cell.params0 = jax.device_put(cell.params0, devices[-1])
        sim = cell.sim
        self.cfg = rt.SimConfig(
            n_devices=sim["n_devices"], n_scheduled=sim["n_scheduled"],
            rounds=sim["rounds"], policy=sim["policy"],
            algorithm=cell.conf["algorithm"],
            chunk_size=cell.conf["chunk_size"],
            compression=sim["compression"], model_bits=sim["model_bits"],
            comp_latency_s=sim["comp_latency_s"],
            compression_params=compression_params(
                **sim["compression_params"]),
            algo_params=algo_params(lr=sim["lr"], server_lr=sim["server_lr"]),
            datagen=cell.datagen)

    def call(self, seed):
        from repro.fl import runtime as rt
        return rt.run_simulation_scan(dataclasses.replace(self.cfg, seed=seed),
                                      self.cell.loss_fn, self.cell.params0)

    def answers(self, seed, out):
        return [(self.cell.sim, seed, out[1], out[0])]


def build(cell, devices):
    return Entry(cell, devices)
'''


def test_an_entry_and_a_policy_added_from_files_alone(tmp_path, monkeypatch):
    """A throwaway cell with its own entry module, its own reference policy
    and its own configuration, traffic and limits files: no file of the
    harness is edited, and the run is correct."""
    manifest = tiny.make_bench_dir(str(tmp_path))
    bench = os.path.join(str(tmp_path), "bench")
    files = {
        ("policies", "best_channel.py"): BEST_CHANNEL,
        ("entries", "scan_on_last_chip.py"): SCAN_ON_LAST_CHIP,
        ("configs", "fleet-bc.json"): json.dumps(
            dict(tiny.FLEET, name="fleet-bc", policy="best_channel")),
        ("traffic", "linear-last.topk-ef.json"): json.dumps(
            dict(tiny.TRAFFIC["linear-tiny.topk-ef"],
                 entry="scan_on_last_chip")),
        ("limits", "fleet-bc.last.json"): json.dumps({"limits": tiny.LIMITS}),
    }
    for (sub, name), text in files.items():
        with open(os.path.join(bench, sub, name), "w") as f:
            f.write(text)
    with open(os.path.join(bench, "configs", "fleet-tiny.py")) as f:
        text = f.read()
    with open(os.path.join(bench, "configs", "fleet-bc.py"), "w") as f:
        f.write(text)
    manifest["workloads"].append(
        {"name": "fleet-bc.last", "config": "fleet-bc",
         "traffic": "linear-last.topk-ef", "chips": 1, "why": "a throwaway"})
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "")
    res = harness.run("fleet-bc.last", 5, 0.2, False, 0.0,
                      allow_platform="cpu", manifest=manifest)
    assert res["correct"], res["checks"]
    assert res["checks"]["sched_mismatches"]["value"] == 0


def test_a_missing_part_is_named():
    with pytest.raises(harness.BenchError, match="bench/policies/nope.py"):
        harness.load_policy("nope")
