"""Faults planted in the timed path, each a ``system(searcher, setup)``
hook of ``harness.run_cell``: a run with one of them has to come out not
correct.  ``python3 bench/control.py --fault <name>`` reads them on the
chip; the benchmark's own runs never plant them.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np

from bench import reference, traffic


def half_batch(searcher, setup):
    """Half of each request answered, the other half given its answers."""
    def call(q):
        rows = q.shape[0]
        half = max(1, rows // 2)
        res = searcher(q[:half])
        reps = -(-rows // half)
        return SimpleNamespace(scores=jnp.tile(res.scores, (reps, 1))[:rows],
                               ids=jnp.tile(res.ids, (reps, 1))[:rows],
                               stats=res.stats)
    return call


def altered(searcher, setup):
    """One id of every answer changed where it is produced."""
    n = int(setup["cell"].config["n"])

    def call(q):
        res = searcher(q)
        ids = res.ids.at[0, 0].set((res.ids[0, 0] + 1) % n)
        return SimpleNamespace(scores=res.scores, ids=ids, stats=res.stats)
    return call


def one_replica(searcher, setup):
    """The last replica's answers altered as ``altered`` alters them: a
    wrong copy on one chip, with every other replica sound."""
    if setup["replica"] != setup["replicas"] - 1:
        return searcher
    return altered(searcher, setup)


def half_probe(searcher, setup):
    """An ivf plan that probes half the configured lists."""
    nprobe = int(setup["cell"].config["search"]["nprobe"])
    return setup["make_searcher"](setup["index"], nprobe=max(1, nprobe // 2))


def dropped_list(searcher, setup):
    """An ivf plan that has lost one list: the one holding the most best
    answers to the queries the window sends first (one cycle of sizes,
    taken from the pool in order).  The list most queries probe first
    can be a hub whose rows are nobody's neighbours."""
    index, mix = setup["index"], setup["cell"].mix
    first = sum(traffic.size_set(mix["batch"], int(mix["cycle"])))
    best = np.asarray(searcher(setup["pool"].queries[:first]).ids)[:, 0]
    row_list = reference.ivf_table(index.centroids, index.lists,
                                   index.n)["row_list"]
    busiest = int(np.bincount(row_list[best[best >= 0]]).argmax())
    return setup["make_searcher"](dataclasses.replace(
        index, lists=index.lists.at[busiest].set(-1)))


def short_lists(searcher, setup):
    """An ivf plan that gathers only the first half of each list's padded
    width, so the tail of every long list is never scanned."""
    index = setup["index"]
    width = max(128, index.max_list // 2)
    return setup["make_searcher"](dataclasses.replace(
        index, lists=index.lists[:, :width], max_list=width))


#: the faults each kind of cell can have
FLAT = (half_batch, altered)
IVF = FLAT + (half_probe, dropped_list, short_lists)
BY_NAME = {f.__name__: f for f in IVF + (one_replica,)}
