"""The one traffic generator: turns a mix file's parameters into requests.

Every seed gets the same multiset of request sizes, drawn at fixed
quantiles of the mix's distribution; the seed only orders them (each
cycle shuffled anew).  So two seeds do the same work in another order,
and the set of shapes to warm up is fixed.

Mix file keys (``bench/traffic/<name>.json``); each client sends its
next request when its last one is answered:

batch       queries per request:
            ``{"dist": "pareto", "alpha": a, "min": lo, "max": hi}``
            (a Pareto law truncated to [lo, hi])
cycle       how many sizes one shuffled cycle holds; a closed loop ends
            its window at the end of a cycle
k           neighbours per query
pool        distinct queries drawn for the mix
check       queries whose answers are compared with the reference
layout      how the cell's chips hold the index: ``single`` (default), one
            Searcher on the first chip; ``replicas``, a one-chip replica on
            each of the cell's chips behind the program's router
            (``repro.dist.replica.ReplicaSet``)
clients     closed-loop clients, each on a thread of its own (default 1);
            more than one are served through the router

At the defaults one client on the calling thread calls the Searcher
directly (``loop.closed``), with no router and no thread in between.
"""

from __future__ import annotations

import threading
from typing import Iterator

import numpy as np


def _quantiles(count: int) -> np.ndarray:
    return (np.arange(count) + 0.5) / count


def size_set(batch: dict, count: int) -> list[int]:
    """The ``count`` request sizes of one cycle, before shuffling."""
    if batch["dist"] == "pareto":
        a, lo, hi = float(batch["alpha"]), float(batch["min"]), float(batch["max"])
        u = _quantiles(count)
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return [int(min(hi, max(lo, round(v)))) for v in x]
    raise ValueError(f"unknown batch distribution {batch['dist']!r}")


def _shuffled(values: list, rng: np.random.Generator) -> Iterator[list]:
    while True:
        yield [values[i] for i in rng.permutation(len(values))]


def cycles(mix: dict, rng: np.random.Generator) -> Iterator[list[int]]:
    """Endless cycles of request sizes, each a new order of the same set:
    a closed loop sends whole cycles, so every run does the same work."""
    return _shuffled(size_set(mix["batch"], int(mix["cycle"])), rng)


def warm_sizes(mix: dict) -> list[int]:
    """Every request size the mix can send: the shapes set-up warms."""
    return sorted(set(size_set(mix["batch"], int(mix["cycle"]))))


class QueryPool:
    """Host (numpy) queries handed out in request-sized slices, in order,
    wrapping at the end, as requests arrive from the network."""

    def __init__(self, queries: np.ndarray):
        self.queries = np.ascontiguousarray(queries, np.float32)
        self._next = 0
        self._lock = threading.Lock()

    def take(self, size: int) -> tuple[int, np.ndarray]:
        """The next ``size`` queries and where they start; clients on
        several threads get disjoint slices."""
        n = self.queries.shape[0]
        with self._lock:
            start = self._next
            self._next = (start + size) % n
        return start, self.queries[(start + np.arange(size)) % n]
