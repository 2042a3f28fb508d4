"""The command refuses to run anywhere but on a TPU, and without the
program beside it."""
import os
import shutil
import subprocess
import sys

from bench.tests import tiny

ROOT = os.path.dirname(tiny.BENCH)
ARGS = ["--workload", "gpt2-small-client.topk-ef", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_off_a_tpu_the_command_fails_naming_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "no program to run" in p.stderr
    assert p.stdout.strip() == ""
