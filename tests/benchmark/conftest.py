"""Fixtures of the benchmark's host tests: a copy of the benchmark whose
configurations are cut to a size the CPU runs in seconds."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: rows, and the ivf list count, of the CPU-sized configurations
TINY = {"product60m-flat-lpq8": {"n": 8192},
        "bigann-ivf-lpq8": {"n": 8192, "nlist": 32}}


def copy_bench(dest: str) -> str:
    """A checkout holding only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    return dest


def tiny_copy(dest: str) -> str:
    """A copy of the benchmark with every configuration cut to a few
    thousand rows and every mix to a small pool and check sample."""
    root = copy_bench(dest)
    for name, changes in TINY.items():
        path = os.path.join(root, "bench", "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(changes)
        if "nlist" in changes:
            cfg["factory"] = cfg["factory"].replace(
                "ivf1024", f"ivf{changes['nlist']}")
        with open(path, "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(root, "bench", "traffic")
    for fname in os.listdir(tdir):
        path = os.path.join(tdir, fname)
        with open(path) as f:
            mix = json.load(f)
        mix.update(pool=512, check=64)
        with open(path, "w") as f:
            json.dump(mix, f)
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return tiny_copy(str(tmp_path))
