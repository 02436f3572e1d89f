"""searcher.host_ms.batch (ms): host time of a Searcher call outside its
wait on the device, per call: each ``searcher.call`` span that starts in
the window (from the first request's start to the last answer), less
the ``searcher.wait`` spans under it, averaged over those calls.

The spans are the program's own records (``repro.runtime.telemetry``),
kept while the profiler traced the window, on the host clock the
request records are stamped with.  A program that keeps no such records
reads nothing, and so does a window whose count of calls is not its
count of answered requests.
"""


def read(run):
    from repro.runtime import telemetry

    recorded = getattr(telemetry, "recorded_spans", None)
    if recorded is None:
        return None
    return host_ms(run.records, recorded())


def host_ms(records, spans):
    done = [r for r in records if r["done"] is not None]
    if not done:
        return None
    lo = min(r["start"] for r in records) * 1e9
    hi = max(r["done"] for r in done) * 1e9
    calls = {s["id"]: s for s in spans
             if s["name"] == "searcher.call" and lo <= s["start_ns"] <= hi}
    if len(calls) != len(done):
        return None
    host_ns = {i: s["end_ns"] - s["start_ns"] for i, s in calls.items()}
    for s in spans:
        if s["name"] == "searcher.wait" and s["parent"] in host_ns:
            host_ns[s["parent"]] -= s["end_ns"] - s["start_ns"]
    return sum(host_ns.values()) / len(host_ns) / 1e6
