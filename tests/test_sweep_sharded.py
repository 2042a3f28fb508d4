"""Multi-device sharded mega-sweep parity (satellite 4).

``run_sweep(devices=...)`` shards the flattened variant axis over a 1-D
device mesh with ``shard_map``. These tests force 8 host CPU devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — which must be set
before jax initializes its backend, so each case runs in a fresh
subprocess — and assert the sharded path is **bitwise** identical to the
single-device vmap, including when the variant count is ragged (not a
multiple of the mesh size: the dispatcher pads with copies of variant 0
and slices the outputs back).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import numpy as np
from benchmarks.common import make_linear_problem
from repro.fl import runtime as rt

import jax
assert jax.device_count() == 8, jax.devices()

params, loss_fn, make_batches, _ = make_linear_problem(d=16)
rounds, n = 3, 8
cfg = rt.SimConfig(n_devices=n, n_scheduled=3, rounds=rounds,
                   compression="topk", algo_params=rt.algo_params(lr=0.1))
batches = rt.stack_batches(make_batches, rounds, n)

# ragged grid: 2 policies x 3 seeds x 3 lrs = 18 tiled variants, mesh
# size 8 -> padded to 24 internally, outputs sliced back and split into
# per-policy blocks of 9
kw = dict(seeds=[0, 1, 2], policies=["random", "best_channel"],
          aparams_grid=[rt.algo_params(lr=l) for l in (0.05, 0.1, 0.2)])
ref = rt.run_sweep(cfg, loss_fn, params, batches, **kw)
shd = rt.run_sweep(cfg, loss_fn, params, batches, devices="auto", **kw)
for pol in kw["policies"]:
    assert ref[pol].loss.shape == (9, rounds)
    np.testing.assert_array_equal(ref[pol].loss, shd[pol].loss)
    np.testing.assert_array_equal(ref[pol].participation,
                                  shd[pol].participation)
    np.testing.assert_array_equal(ref[pol].latency_s, shd[pol].latency_s)
    np.testing.assert_array_equal(ref[pol].uplink_bits, shd[pol].uplink_bits)

# per-policy loop path shards too (policy_mode="loop")
lp = rt.run_sweep(cfg, loss_fn, params, batches, devices="auto",
                  policy_mode="loop", **kw)
for pol in kw["policies"]:
    np.testing.assert_array_equal(ref[pol].loss, lp[pol].loss)

# explicit int device count and an explicit mesh both work
shd4 = rt.run_sweep(cfg, loss_fn, params, batches, devices=4, **kw)
mesh = rt.compat.make_mesh(jax.devices()[:2], "variants")
shd2 = rt.run_sweep(cfg, loss_fn, params, batches, mesh=mesh, **kw)
for pol in kw["policies"]:
    np.testing.assert_array_equal(ref[pol].loss, shd4[pol].loss)
    np.testing.assert_array_equal(ref[pol].loss, shd2[pol].loss)

print("SHARDED-PARITY-OK")
"""


def _run_forced_8dev(script: str) -> str:
    env = dict(os.environ)
    # the CPU backend's YNNPACK fusions pick kernels by operand shape, so a
    # vmap over 18 variants and one over 3 per device would differ in the
    # last ulp before any sharding happens; with them off, XLA's own CPU
    # kernels give every variant the same arithmetic at any vmap width
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8"
                        " --xla_cpu_experimental_ynn_fusion_type=").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        pytest.fail(f"forced-8-device subprocess failed:\n"
                    f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc.stdout


def test_sharded_sweep_bitwise_parity_forced_8_devices():
    out = _run_forced_8dev(_SCRIPT)
    assert "SHARDED-PARITY-OK" in out
