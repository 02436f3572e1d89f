"""Replica groups: query fan-out over data-parallel replicas (DESIGN.md §15).

Two layers, matching the two places replication happens:

  * **inside the jit** — ``replicated_query_plan`` wraps a per-kind
    array function ``(queries) -> (scores, ids)`` in a ``shard_map``
    over the *query* axis: every shard holds a full copy of the index
    (graph walks are not row-shardable) and walks its slice of the
    batch; ``out_specs`` reassemble the full batch with no host
    round-trip.  Per-query independence makes this bit-exact against
    the unsharded run.
  * **outside the jit** — ``ReplicaSet`` is the serving layer: R
    replica searchers (optionally each pinned to its own sub-mesh via
    ``submeshes``), worker threads draining per-replica queues, with
    per-replica admission (bounded queue depth) and per-replica
    telemetry (requests, queue-wait/execute spans, queue-depth peaks)
    flowing into the shared :mod:`repro.runtime.telemetry` registry.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, Optional, Sequence

import numpy as np

__all__ = ["replicated_query_plan", "submeshes", "ReplicaSet"]


def replicated_query_plan(fn, mesh):
    """Fan a query batch out over ``mesh``; the index replicates.

    ``fn`` is a pure array function ``(queries [Q, d]) -> (scores, ids)``
    whose per-row outputs depend only on that row (every walk/scan kind
    satisfies this).  The wrapper pads Q up to a multiple of the mesh
    size, shards the batch over every mesh axis, runs ``fn`` on each
    shard's slice (closed-over index arrays are replicated constants),
    and reassembles — all inside the caller's jit.  Pad queries are
    zeros; their rows are dropped before returning.
    """
    import jax.numpy as jnp

    from repro.dist.sharding import P, corpus_shards, shard_map

    axes, n_shards = corpus_shards(mesh)
    inner = shard_map(
        lambda qs: fn(qs),
        mesh=mesh,
        in_specs=(P(axes, None),),
        out_specs=(P(axes, None), P(axes, None)),
        check_vma=False,
    )

    def run(q):
        Q = q.shape[0]
        pad = (-Q) % n_shards
        if pad:
            q = jnp.pad(q, ((0, pad), (0, 0)))
        s, i = inner(q)
        return s[:Q], i[:Q]

    return run


def submeshes(n_groups: int, devices: Optional[Sequence] = None) -> list:
    """Split the host's devices into ``n_groups`` disjoint 1-axis meshes
    — one per replica, so R replicas x (n_dev // R)-way sharding covers
    the whole host with no device oversubscription.  Groups are
    equal-sized (trailing remainder devices are left unused — replica
    plans must be shape-identical to share compiled executables)."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    n_groups = max(1, min(int(n_groups), len(devs)))
    per = len(devs) // n_groups
    return [Mesh(np.array(devs[g * per:(g + 1) * per]), ("data",))
            for g in range(n_groups)]


class ReplicaSet:
    """R data-parallel serving replicas behind per-replica queues.

    ``make_replica(r)`` builds replica ``r``'s request callable
    (``payload -> result``; serve.py passes a closure over a Searcher +
    ``block_until_ready``).  ``submit`` routes to the least-loaded
    replica (ties to the lowest id), enforcing ``max_queue`` *per
    replica* at the door — a full replica sheds rather than queues
    without bound — and returns a ``Future``.  Workers record one
    telemetry request row per served request (``replica{r}/queue_wait``
    and ``replica{r}/execute`` phases) plus shared counters
    ``replica{r}_requests`` / ``replica{r}_queries`` /
    ``replica{r}_queue_peak`` / ``replica_shed``.

    ``drain()`` blocks until every queued request has executed — the
    write barrier: serve.py drains, applies the mutation, then
    ``rebuild()``s so every replica re-plans against the new manifest
    epoch before traffic resumes.
    """

    _STOP = object()

    def __init__(self, make_replica: Callable[[int], Callable], n_replicas: int,
                 *, max_queue: int = 0, telemetry=None):
        if n_replicas <= 0:
            raise ValueError(f"n_replicas must be positive, got {n_replicas}")
        self._make = make_replica
        self.n_replicas = int(n_replicas)
        self.max_queue = int(max_queue)
        self._telemetry = telemetry
        self._queues = [queue.Queue() for _ in range(self.n_replicas)]
        self._depths = [0] * self.n_replicas
        self._lock = threading.Lock()
        self._seq = 0
        self._replicas = [make_replica(r) for r in range(self.n_replicas)]
        self._workers = [
            threading.Thread(target=self._work, args=(r,), daemon=True)
            for r in range(self.n_replicas)
        ]
        for w in self._workers:
            w.start()

    # -- routing -----------------------------------------------------------
    def submit(self, payload, queries: int = 0) -> Optional[Future]:
        """Enqueue on the least-loaded replica; None == shed (replica
        queues full — per-replica admission)."""
        with self._lock:
            r = min(range(self.n_replicas), key=lambda j: (self._depths[j], j))
            if self.max_queue and self._depths[r] >= self.max_queue:
                if self._telemetry is not None:
                    self._telemetry.counters["replica_shed"] += 1
                return None
            self._depths[r] += 1
            depth = self._depths[r]
            self._seq += 1
            seq = self._seq
        if self._telemetry is not None:
            c = self._telemetry.counters
            c[f"replica{r}_requests"] += 1
            c[f"replica{r}_queries"] += int(queries)
            c[f"replica{r}_queue_peak"] = max(c[f"replica{r}_queue_peak"], depth)
        fut: Future = Future()
        self._queues[r].put((payload, int(queries), fut, seq,
                             time.perf_counter()))
        return fut

    def _work(self, r: int) -> None:
        q = self._queues[r]
        while True:
            item = q.get()
            if item is self._STOP:
                q.task_done()
                return
            payload, nq, fut, seq, t_enq = item
            tr = None
            if self._telemetry is not None:
                tr = self._telemetry.request(seq)
                tr.phase(f"replica{r}/queue_wait", time.perf_counter() - t_enq)
            with (tr.span(f"replica{r}/execute") if tr is not None
                  else contextlib.nullcontext()):
                try:
                    res = self._replicas[r](payload)
                    fut.set_result(res)
                except BaseException as e:  # to the future; serve on
                    fut.set_exception(e)
            if tr is not None:
                tr.annotate(replica=r, queries=nq, outcome="served")
                tr.finish()
            with self._lock:
                self._depths[r] -= 1
            q.task_done()

    # -- lifecycle ---------------------------------------------------------
    def drain(self) -> None:
        """Block until every enqueued request has finished executing."""
        for q in self._queues:
            q.join()

    def rebuild(self) -> None:
        """Write barrier: drain, then re-plan every replica (serve.py
        calls this after a mutation bumps the manifest epoch)."""
        self.drain()
        self._replicas = [self._make(r) for r in range(self.n_replicas)]

    def close(self) -> None:
        self.drain()
        for q in self._queues:
            q.put(self._STOP)
        for w in self._workers:
            w.join(timeout=10.0)
