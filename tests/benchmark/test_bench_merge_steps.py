"""The reader of the fused kernel's merge counter
(``fused_topk.merge_steps_per_tile``): exact on a fabricated run, nothing
where the counts differ or the program keeps no counter, and the reading
of a real Searcher's calls on the fused path (interpret mode)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, registry
from repro.runtime import telemetry

METRIC = "fused_topk.merge_steps_per_tile"
MS = 1_000_000                       # ns


def _call(id_, start_ms, steps, tiles):
    # program clock: perf_counter_ns, here 1 s past its zero; the counter
    # is a device value in the program's span fields
    return {"name": "searcher.call", "id": id_, "parent": None,
            "request": id_, "start_ns": 1_000 * MS + start_ms * MS,
            "end_ns": 1_000 * MS + (start_ms + 8) * MS,
            "fields": {"queries": 8, "slices": 1,
                       "merge_steps": jnp.int32(steps),
                       "merge_tiles": jnp.int32(tiles)}}


def _fabricated():
    records = [{"start": 1.000, "done": 1.010, "size": 8, "stats": {}},
               {"start": 1.020, "done": 1.030, "size": 200, "stats": {}}]
    # a one-query-tile call over 40 corpus tiles, then a two-tile one
    spans = [_call(1, 1, 130, 40), _call(2, 21, 150, 80),
             {**_call(3, 22, 0, 0), "name": "searcher.wait", "parent": 2}]
    return records, spans


def _view(records):
    return harness.RunView(records=records, n=20000, d=256, row_bytes=256,
                           device_kind="TPU v5 lite")


def test_reader_sums_steps_over_tiles(monkeypatch):
    records, spans = _fabricated()
    monkeypatch.setattr(telemetry, "recorded_spans", lambda: spans)
    assert registry.reader(METRIC)(_view(records)) == pytest.approx(
        (130 + 150) / (40 + 80))


@pytest.mark.parametrize("broken", ["no_counter", "lost_call",
                                    "no_records"])
def test_reader_reads_nothing_without_a_counter_a_call(broken, monkeypatch):
    records, spans = _fabricated()
    if broken == "no_counter":           # a program without the counter
        for s in spans:
            s["fields"] = {"queries": 8, "slices": 1}
    elif broken == "lost_call":
        spans = spans[1:]
    if broken == "no_records":           # a program without span records
        monkeypatch.delattr(telemetry, "recorded_spans")
    else:
        monkeypatch.setattr(telemetry, "recorded_spans", lambda: spans)
    assert registry.reader(METRIC)(_view(records)) is None


def test_reader_reads_a_searcher_s_fused_calls(monkeypatch):
    """Calls of a flat int8 plan on the fused kernel: the reading is the
    steps over the tiles of the calls' stats, read after the calls."""
    from repro.kernels import ops
    from repro.knn import SearchParams, make_index

    # steer the flat scan onto the fused kernel, in interpret mode: the
    # engine takes it only on a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    corpus = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1000, 32)))
    searcher = make_index("flat,lpq8", corpus).searcher(
        10, SearchParams(chunk=256), batch_sizes=(8,))
    queries = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (20, 32)))
    searcher(queries[:8])                               # compile outside
    records, stats = [], []
    with telemetry.recording():
        for size in (8, 12):                           # one slice, two
            start = time.perf_counter()
            res = searcher(queries[:size])
            jax.block_until_ready(res.ids)
            records.append({"start": start, "done": time.perf_counter(),
                            "size": size, "stats": {}})
            stats.append(res.stats)
    monkeypatch.setattr(telemetry, "recorded_spans",
                        lambda t=telemetry.recorded_spans(): t)
    # ceil(1000 / 256) = 4 corpus tiles for each 8-row query tile
    assert [int(s["merge_tiles"]) for s in stats] == [4, 8]
    steps = sum(int(s["merge_steps"]) for s in stats)
    assert registry.reader(METRIC)(_view(records)) == pytest.approx(
        steps / 12)
