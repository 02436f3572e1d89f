"""A replicated cell on four virtual CPU devices: four one-chip replicas
behind the program's router (the ``replicas`` layout), served by
closed-loop client threads.  A sound run is correct, every replica serves
and holds its own copy of the index, and the router's readers read; a run
whose last replica answers wrong is not correct.  JAX takes its device
count when it starts, so the runs are made in a process of their own."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, tiny_copy
from bench import harness

NAME = "product60m-flat-lpq8.replicas4"
READERS = ("replica.imbalance", "replica.queue_ms")


def replicated_copy(dest: str) -> str:
    """A tiny copy of the benchmark with a four-chip cell that serves cell
    1's traffic from one replica a chip to eight clients."""
    root = tiny_copy(dest)
    tdir = os.path.join(root, "bench", "traffic")
    with open(os.path.join(tdir, "batch-k100.json")) as f:
        mix = json.load(f)
    mix.update(layout="replicas", clients=8)
    with open(os.path.join(tdir, "replicas4.json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": NAME, "config": "product60m-flat-lpq8",
                               "traffic": "replicas4", "chips": 4,
                               "why": "a replicated cell for the CPU"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "product60m-flat-lpq8.batch-k100" in m.get("workloads", ()):
            m["workloads"].append(NAME)
    bench["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower",
         "source": "program_span", "layer": "replica router", "moves": "qps",
         "workloads": [NAME]}
        for name, unit in zip(READERS, ("ratio", "ms"))]
    with open(path, "w") as f:
        json.dump(bench, f)
    return root

SCRIPT = r"""
import collections, json, sys, time
root = sys.argv[1]
sys.path[:0] = [root, sys.argv[2]]
import jax
from bench import faults, harness, registry

cell = registry.load_cell(sys.argv[3], root)
seen = {}


def spy(searcher, setup):
    r = setup["replica"]
    leaves = jax.tree_util.tree_leaves(setup["index"])
    seen[r] = {"device": str(setup["device"]), "calls": 0,
               "index_on": sorted({str(d) for x in leaves
                                   for d in x.devices()})}
    if r == setup["replicas"] - 1:            # every copy is made by now
        codes = setup["index"].store.data
        held = collections.Counter(
            str(d) for a in jax.live_arrays()
            if a.shape == codes.shape and a.dtype == codes.dtype
            for d in a.devices())
        seen["codes_per_device"] = dict(held)

    def call(q):
        seen[r]["calls"] += 1
        return searcher(q)
    return call


def run(system, with_trace):
    return harness.run_cell(cell, 2 ** 33 + 15, 0.5, with_trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            system=system)


sound = run(spy, True)
broken = run(faults.one_replica, False)
print(json.dumps({"sound": sound, "broken": broken, "seen": seen,
                  "warm": len(harness.traffic.warm_sizes(cell.mix)),
                  "devices": [str(d) for d in jax.devices()]},
                 default=str))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = replicated_copy(str(tmp_path_factory.mktemp("bench")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCRIPT, root,
                        os.path.join(ROOT, "src"), NAME],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_a_replicated_run_is_correct_and_compiles_nothing_in_the_window(runs):
    sound = runs["sound"]
    assert sound["correct"] is True, sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["compiles_in_window"] == 0
    assert sound["device"]["count"] == 4


def test_every_replica_serves_from_its_own_copy_on_its_own_device(runs):
    seen = runs["seen"]
    devices = [seen[str(r)]["device"] for r in range(4)]
    assert devices == runs["devices"]
    for r in range(4):
        assert seen[str(r)]["index_on"] == [devices[r]]
        # warm-up calls every size once; the rest came in the window
        assert seen[str(r)]["calls"] > runs["warm"]
    assert seen["codes_per_device"] == {d: 1 for d in devices}


def test_the_router_s_readers_read(runs):
    metrics = runs["sound"]["metrics"]
    assert metrics["replica.imbalance"]["value"] >= 1.0
    assert metrics["replica.imbalance"]["unit"] == "ratio"
    assert metrics["replica.queue_ms"]["value"] >= 0.0
    assert metrics["replica.queue_ms"]["unit"] == "ms"


def test_one_wrong_replica_is_not_correct(runs):
    broken = runs["broken"]
    assert broken["correct"] is False, broken["checks"]
    assert broken["failed"] == 0 and broken["attempted"] > 0


def test_the_sample_holds_the_longest_request_of_every_replica():
    sizes = [8, 256, 16, 64, 32, 128, 8, 8]
    records = [{"size": s, "done": 1.0, "stats": {"replica": i % 4}}
               for i, s in enumerate(sizes)]
    records.append({"size": 512, "done": None, "stats": {}})
    picks = harness._sample(records, np.random.default_rng(0), 40)
    assert picks[:4] == [records[i] for i in (4, 1, 2, 3)]
    assert {p["stats"]["replica"] for p in picks} == {0, 1, 2, 3}
    # one replica: the longest request first, as ever
    one = [{"size": s, "done": 1.0, "stats": {}} for s in sizes]
    picks = harness._sample(one, np.random.default_rng(0), 300)
    assert picks[0] is one[1] and sum(p["size"] for p in picks) >= 300
