"""The request loops that drive the system under test through the window.

They return one record per request sent in the window:

size      queries in the request
offset    where its queries start in the query pool
start     when the call was made, on the host clock
done      when its answer was on the host, or None
stats     the Searcher's stats of the call (bucket, padded_q, bytes_read)
          and, behind a router, the replica that served it
result    (scores, ids) as host arrays, or None
error     the exception's text, for a call that raised

Host spans (``jax.profiler.TraceAnnotation``) mark the window and each
call, so a traced run can say what the host was doing while the device
sat idle.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from bench import traffic

STAT_KEYS = ("bucket", "padded_q", "bytes_read", "replica")


def answer(res) -> tuple:
    """The answer as the caller receives it: on the host.  Copying it
    there waits for the device and frees the device's copy."""
    return np.asarray(res.scores), np.asarray(res.ids)


def stats_of(res) -> dict:
    return {k: res.stats[k] for k in STAT_KEYS if k in res.stats}


def _call(system, queries) -> dict:
    """One request through ``system``, timed until its answer is on the
    host."""
    rec = {"start": time.perf_counter(), "result": None, "error": None,
           "stats": {}}
    try:
        with jax.profiler.TraceAnnotation("bench.call"):
            res = system(queries)
            rec["result"] = answer(res)
        rec["done"] = time.perf_counter()
        rec["stats"] = stats_of(res)
    except Exception as e:             # a failed request is counted, not fatal
        rec["done"] = None
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


def _client(system, pool: traffic.QueryPool, cycles, end: float,
            out: list) -> None:
    """One closed-loop client: the next request goes out when the last is
    answered; whole cycles of sizes are sent until ``end``."""
    while time.perf_counter() < end:
        for size in next(cycles):
            offset, q = pool.take(size)
            rec = _call(system, q)
            rec.update(size=size, offset=offset)
            out.append(rec)


def closed(system, pool: traffic.QueryPool, cycles, seconds: float) -> list:
    """One client on the calling thread, until the window's time is up."""
    out = []
    with jax.profiler.TraceAnnotation("bench.window"):
        _client(system, pool, cycles, time.perf_counter() + seconds, out)
    return out


def closed_pool(system, pool: traffic.QueryPool, cycles_per_client,
                seconds: float) -> list:
    """One closed-loop client per entry of ``cycles_per_client``, each on
    a thread of its own and with its own order of sizes, all sending
    until the window's time is up.  The records of all clients, in the
    order their calls were made."""
    outs = [[] for _ in cycles_per_client]
    errors = []

    def client(cycles, out):
        try:
            _client(system, pool, cycles, end, out)
        except BaseException as e:     # re-raised on the calling thread
            errors.append(e)

    with jax.profiler.TraceAnnotation("bench.window"):
        end = time.perf_counter() + seconds
        threads = [threading.Thread(target=client, args=(cycles, out),
                                    name=f"bench-client-{c}")
                   for c, (cycles, out) in enumerate(
                       zip(cycles_per_client, outs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    if errors:
        raise errors[0]
    return sorted((rec for out in outs for rec in out),
                  key=lambda rec: rec["start"])
