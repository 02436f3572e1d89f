"""fused_topk.merge_steps_per_tile (steps/tile): top-k merge steps the
fused scan kernel took per corpus tile it visited, over the window's
answered requests: Σ``merge_steps`` / Σ``merge_tiles``.

The two counters are device values in the Searcher's stats, which its
``searcher.call`` span records carry in their fields (the program's own
records, ``repro.runtime.telemetry``, kept while the profiler traced the
window); they are read to the host here, after the window.  Calls are
those that start in the window, from the first request's start to the
last answer.  A program that keeps no such records or counters reads
nothing, and so does a window whose count of calls is not its count of
answered requests.
"""


def read(run):
    from repro.runtime import telemetry

    recorded = getattr(telemetry, "recorded_spans", None)
    if recorded is None:
        return None
    return steps_per_tile(run.records, recorded())


def steps_per_tile(records, spans):
    done = [r for r in records if r["done"] is not None]
    if not done:
        return None
    lo = min(r["start"] for r in records) * 1e9
    hi = max(r["done"] for r in done) * 1e9
    calls = [s["fields"] for s in spans
             if s["name"] == "searcher.call" and lo <= s["start_ns"] <= hi]
    if len(calls) != len(done) or not all(
            "merge_steps" in f and "merge_tiles" in f for f in calls):
        return None
    tiles = sum(int(f["merge_tiles"]) for f in calls)
    if not tiles:
        return None
    return sum(int(f["merge_steps"]) for f in calls) / tiles
