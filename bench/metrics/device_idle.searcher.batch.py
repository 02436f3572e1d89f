"""device_idle.searcher.batch (%): share of the traced window in which no
operation ran on the device while the host was inside a Searcher call
(a ``searcher.call`` span) and outside its wait on the device (its
``searcher.wait`` spans); busy time is the union of the ``XLA Ops``
intervals, averaged over the devices that ran any.  What is left of
``device_idle.batch`` is the client's: copying the answer, sending the
next request.

The spans are the program's own records (``repro.runtime.telemetry``),
stamped on the host clock.  Each is placed on the trace's clock by the
request it ran in: the i-th ``bench.call`` of the trace is the i-th
request record, and the two starts give that call's offset.  A program
that keeps no such records reads nothing, and so does a window whose
counts of calls, requests and answers do not match.
"""

import bisect

from bench import trace


def read(run):
    if run.trace is None:
        return None
    from repro.runtime import telemetry

    recorded = getattr(telemetry, "recorded_spans", None)
    if recorded is None:
        return None
    return idle_share(run.records, run.trace, recorded())


def idle_share(records, rec, spans):
    host_calls = sorted(s for name, s, _d in rec["host"]
                        if name == "bench.call")
    done = [r for r in records if r["done"] is not None]
    if not done or len(host_calls) != len(records):
        return None
    starts = [r["start"] * 1e9 for r in records]
    last_done = max(r["done"] for r in done) * 1e9
    calls = {s["id"]: s for s in spans if s["name"] == "searcher.call"
             and starts[0] <= s["start_ns"] <= last_done}
    if len(calls) != len(done):
        return None
    waits = {i: [] for i in calls}
    for s in spans:
        if s["name"] == "searcher.wait" and s["parent"] in waits:
            waits[s["parent"]].append((s["start_ns"], s["end_ns"]))

    w0, w1 = trace.window(rec)
    exposed = []                   # on the trace's clock, in the window
    for i, s in calls.items():
        k = bisect.bisect_right(starts, s["start_ns"]) - 1
        offset = host_calls[k] - starts[k]
        edges = [s["start_ns"]]
        for a, b in sorted(waits[i]):
            edges += [a, b]
        edges.append(s["end_ns"])
        for a, b in zip(edges[0::2], edges[1::2]):
            lo, hi = max(a + offset, w0), min(b + offset, w1)
            if hi > lo:
                exposed.append((lo, hi))
    exposed.sort()

    idle = []
    for ops in rec["devices"].values():
        busy = _union([(max(s, w0), min(s + d, w1)) for _n, s, d, _l in ops
                       if s + d > w0 and s < w1])
        if busy:
            idle.append(sum(b - a for a, b in exposed)
                        - _overlap(exposed, busy))
    if not idle:
        return None
    return 100.0 * sum(idle) / len(idle) / (w1 - w0)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a, b):
    """Total length shared by two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
