"""The one traffic generator: turns a mix file's parameters into requests.

Every seed gets the same multiset of request sizes, drawn at fixed
quantiles of the mix's distribution; the seed only orders them (each
cycle shuffled anew).  So two seeds do the same work in another order,
and the set of shapes to warm up is fixed.

Mix file keys (``bench/traffic/<name>.json``); one client sends each
request when the last one is answered (``loop.closed``):

batch       queries per request:
            ``{"dist": "pareto", "alpha": a, "min": lo, "max": hi}``
            (a Pareto law truncated to [lo, hi])
cycle       how many sizes one shuffled cycle holds; a closed loop ends
            its window at the end of a cycle
k           neighbours per query
pool        distinct queries drawn for the mix
check       queries whose answers are compared with the reference
"""

from __future__ import annotations

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

    def take(self, size: int) -> tuple[int, np.ndarray]:
        n = self.queries.shape[0]
        start = self._next
        idx = (start + np.arange(size)) % n
        self._next = (start + size) % n
        return start, self.queries[idx]
