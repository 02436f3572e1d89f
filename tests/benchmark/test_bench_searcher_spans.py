"""The readers of the Searcher's own spans (``searcher.host_ms.batch``,
``device_idle.searcher.batch``) on a synthetic run, and which runs keep
span records: a traced run does, an untraced one keeps none."""

import time

import pytest

from bench import harness, registry
from repro.runtime import telemetry

NAME = "product60m-flat-lpq8.batch-k100"
MS = 1_000_000                       # ns


def _span(id_, name, start_ms, end_ms, parent=None):
    # program clock: perf_counter_ns, here 1 s past its zero
    return {"name": name, "id": id_, "parent": parent,
            "request": parent or id_, "fields": {},
            "start_ns": 1_000 * MS + start_ms * MS,
            "end_ns": 1_000 * MS + end_ms * MS}


def _synthetic():
    """Two requests at 0 and 20 ms of the program's clock; each call
    waits on the device inside it.  On the trace's clock the window
    opens 5 s later, and the second call's offset is 2 ms larger."""
    records = [{"start": 1.000, "done": 1.010, "size": 8, "stats": {}},
               {"start": 1.020, "done": 1.030, "size": 8, "stats": {}}]
    spans = [_span(1, "searcher.call", 1, 9),
             _span(2, "searcher.prepare", 1, 2, parent=1),
             _span(3, "searcher.wait", 3, 7, parent=1),
             _span(4, "searcher.call", 21, 29),
             _span(5, "searcher.wait", 22, 27, parent=4)]
    w0 = 6_000 * MS
    trace = {"host": [["bench.window", w0, 40 * MS],
                      ["bench.call", w0, 10 * MS],
                      ["bench.call", w0 + 22 * MS, 10 * MS]],
             # idle 4-5 ms, inside the first wait: the program's wait
             "devices": {0: [["fused_topk_pallas", w0 + 2 * MS, 2 * MS, ""],
                             ["fused_topk_pallas", w0 + 5 * MS, 3 * MS, ""],
                             ["fused_topk_pallas", w0 + 22 * MS, 7 * MS, ""]],
                         # ran nothing in the window: not averaged in
                         1: [["copy", w0 + 50 * MS, 1 * MS, ""]]}}
    return records, spans, trace


def _view(records, trace):
    return harness.RunView(records=records, n=1000, d=128, row_bytes=128,
                           device_kind="TPU v5 lite", trace=trace)


@pytest.mark.parametrize("metric", ["searcher.host_ms.batch",
                                    "device_idle.searcher.batch"])
def test_readers_on_a_synthetic_run(metric, monkeypatch):
    records, spans, trace = _synthetic()
    monkeypatch.setattr(telemetry, "recorded_spans", lambda: spans)
    # host time: (8 - 4) ms and (8 - 5) ms a call.  Idle: outside the
    # waits, on the trace's clock, are 1-3 and 7-9 ms, 23-24 and 29-31
    # ms; the device ran 2-4, 5-8 and 22-29 ms, so it sat idle
    # 1 + 1 + 0 + 2 ms of the 40 ms window there
    want = {"searcher.host_ms.batch": 3.5,
            "device_idle.searcher.batch": 100.0 * 4.0 / 40.0}[metric]
    assert registry.reader(metric)(_view(records, trace)) == pytest.approx(
        want)


@pytest.mark.parametrize("metric", ["searcher.host_ms.batch",
                                    "device_idle.searcher.batch"])
@pytest.mark.parametrize("broken", ["lost_call", "extra_request",
                                    "no_records"])
def test_readers_read_nothing_when_counts_differ(metric, broken,
                                                 monkeypatch):
    records, spans, trace = _synthetic()
    if broken == "lost_call":
        spans = [s for s in spans if s["id"] != 4]
    elif broken == "extra_request":
        records.append({"start": 1.040, "done": 1.050, "size": 8,
                        "stats": {}})
        trace["host"].append(["bench.call", 6_040 * MS, 10 * MS])
    if broken == "no_records":           # a program without span records
        monkeypatch.delattr(telemetry, "recorded_spans")
    else:
        monkeypatch.setattr(telemetry, "recorded_spans", lambda: spans)
    assert registry.reader(metric)(_view(records, trace)) is None


def _run(root, with_trace):
    cell = registry.load_cell(NAME, root)
    return harness.run_cell(cell, 2 ** 32 + 7, 0.3, with_trace,
                            t_start=time.perf_counter(), require_tpu=False)


def test_an_untraced_run_keeps_no_span_records(tiny_root):
    t0 = time.perf_counter_ns()
    out = _run(tiny_root, False)
    assert out["attempted"] > 0
    assert telemetry.recorded_spans(t0) == []


def test_a_traced_run_reads_the_searcher_s_host_time(tiny_root):
    t0 = time.perf_counter_ns()
    out = _run(tiny_root, True)
    calls = [s for s in telemetry.recorded_spans(t0)
             if s["name"] == "searcher.call"]
    assert len(calls) == out["attempted"]
    assert out["metrics"]["searcher.host_ms.batch"]["value"] > 0
    assert out["metrics"]["searcher.host_ms.batch"]["unit"] == "ms"
