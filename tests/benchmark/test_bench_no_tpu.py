"""The benchmark refuses to run, and prints no result, without a TPU or
without the system under test."""

import os
import subprocess
import sys

from conftest import ROOT, copy_bench

CMD = ["bench/run.py", "--workload", "product60m-flat-lpq8.batch-k100",
       "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable] + CMD, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no chip" in r.stderr and "tpu" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    root = copy_bench(str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run(root, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr
