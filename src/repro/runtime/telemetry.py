"""Structured serve telemetry: host spans on the profiler's clock, a
shared counter registry, and the JSON event log an operator reads.

Host spans: ``span(name, **fields)`` is the one span primitive.  It
always opens a ``jax.profiler.TraceAnnotation(name)``, so any profile
shows the span on the device trace's own clock.  While recording is on
(a JAX profiler session is active, or inside ``recording()``) it also
keeps one record per span in a bounded in-memory buffer: its name, its
start and end in ``time.perf_counter_ns()``, its parent, a request id
shared by every span under one outermost span, and its fields.  Only
readers write the records out: ``recorded_spans()`` returns them, and
``Telemetry.to_json`` adds its session's records as events.  With
recording off nothing is kept.

One ``Telemetry`` object per serving session.  Three surfaces:

  * **counters** — a plain ``Counter`` shared *by reference* with the
    cache tiers and the admission controller, so every subsystem
    increments into one registry and the final report is one dict, not
    a reconciliation exercise.
  * **request traces** — ``telemetry.request(id)`` yields a
    ``RequestTrace``; phases (``queue_wait`` / ``execute`` / ...) are
    timed with ``trace.span(name)`` or recorded directly with
    ``trace.phase(name, seconds)`` (for durations measured elsewhere,
    e.g. queue wait), annotations carry the engine stats; ``finish``
    appends one event row.
  * **ad-hoc spans** — ``telemetry.span("maintenance/compact")`` times
    off-request work (the background compactor) into the same log.

``to_json`` writes ``{meta, counters, summary, events}`` where ``meta``
embeds the runtime-profile stamp, so the file carries the same
provenance as the ``BENCH_*.json`` files CI uploads beside it.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

try:                       # private: whether a profiler session is active
    from jax._src.lib import _profiler

    _profiling = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):    # an older or newer jaxlib
    def _profiling() -> bool:
        return False

#: span records kept while recording; the oldest go once it is full
SPAN_BUFFER = 65536

_spans: collections.deque = collections.deque(maxlen=SPAN_BUFFER)
_span_ids = itertools.count(1)
_open = threading.local()          # .stack: this thread's open records
_recording_lock = threading.Lock()
_recording_depth = 0


@contextlib.contextmanager
def recording():
    """Keep span records inside this block, in every thread, even with
    no profiler session active."""
    global _recording_depth
    with _recording_lock:
        _recording_depth += 1
    try:
        yield
    finally:
        with _recording_lock:
            _recording_depth -= 1


def recorded_spans(since_ns: int = 0) -> list[dict]:
    """The finished span records that started at or after ``since_ns``
    (``time.perf_counter_ns()``), in the order they started."""
    return sorted((r for r in list(_spans) if r["start_ns"] >= since_ns),
                  key=lambda r: r["id"])


class _Span:
    __slots__ = ("name", "fields", "_annotation", "_record")

    def __init__(self, name: str, fields: dict):
        self.name = name
        self.fields = fields

    def __enter__(self) -> dict:
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._record = None
        if _recording_depth or _profiling():
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            parent = stack[-1] if stack else None
            span_id = next(_span_ids)
            self._record = {
                "name": self.name, "id": span_id,
                "parent": parent["id"] if parent else None,
                "request": parent["request"] if parent else span_id,
                "start_ns": time.perf_counter_ns(), "end_ns": None,
                "fields": self.fields,
            }
            stack.append(self._record)
        return self.fields

    def __exit__(self, *exc) -> None:
        record = self._record
        if record is not None:
            record["end_ns"] = time.perf_counter_ns()
            _open.stack.pop()
            _spans.append(record)
        self._annotation.__exit__(*exc)


def span(name: str, **fields) -> _Span:
    """A host span named ``name``: ``with span("searcher.call") as f:``.
    ``f`` is the span's fields, which the body may add to."""
    return _Span(name, fields)


class RequestTrace:
    """Span accumulator for one request; append-only until ``finish``."""

    def __init__(self, req_id, telemetry: "Telemetry"):
        self.req_id = req_id
        self._t = telemetry
        self.phases: dict[str, float] = {}
        self.fields: dict[str, Any] = {}
        self._done = False

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = self._t.clock()
        try:
            with span(name, req_id=self.req_id):
                yield self
        finally:
            self.phase(name, self._t.clock() - t0)

    def phase(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + float(seconds)

    def annotate(self, **fields) -> None:
        self.fields.update(fields)

    def finish(self) -> dict:
        if not self._done:                      # idempotent
            self._done = True
            self._t._finish_request(self)
        return {"type": "request", "id": self.req_id,
                **{f"{k}_s": v for k, v in self.phases.items()},
                **self.fields}


class Telemetry:
    """The session-wide event log + counter registry."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 meta: Optional[dict] = None):
        self.clock = clock
        self.meta = dict(meta or {})
        self.counters: collections.Counter = collections.Counter()
        self.events: list[dict] = []
        self._phase_samples: dict[str, list[float]] = collections.defaultdict(list)
        self._since_ns = time.perf_counter_ns()

    # -- request path ------------------------------------------------------
    def request(self, req_id) -> RequestTrace:
        return RequestTrace(req_id, self)

    def _finish_request(self, trace: RequestTrace) -> None:
        self.counters["requests"] += 1
        for name, dur in trace.phases.items():
            self._phase_samples[name].append(dur)
        self.events.append({"type": "request", "id": trace.req_id,
                            **{f"{k}_s": v for k, v in trace.phases.items()},
                            **trace.fields})

    # -- ad-hoc (maintenance path) -----------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **fields):
        t0 = self.clock()
        row = {"type": "span", "name": name, **fields}
        try:
            with span(name, **fields):
                yield row
        finally:
            row["dur_s"] = self.clock() - t0
            self._phase_samples[name].append(row["dur_s"])
            self.events.append(row)

    def event(self, type_: str, **fields) -> None:
        self.events.append({"type": type_, **fields})

    # -- rollups -----------------------------------------------------------
    def percentiles(self, name: str, qs=(50, 95, 99)) -> dict[str, float]:
        xs = self._phase_samples.get(name)
        if not xs:
            return {}
        return {f"p{q}_ms": float(np.percentile(xs, q)) * 1e3 for q in qs}

    def summary(self) -> dict:
        return {
            name: {"count": len(xs), "total_s": float(np.sum(xs)),
                   **self.percentiles(name)}
            for name, xs in sorted(self._phase_samples.items())
        }

    def to_json(self, path) -> dict:
        """Serialize ``{meta, counters, summary, events}``; returns the
        payload (path may be a filesystem path or a file-like object).
        The span records kept since this session began follow its own
        events, one ``{"type": "host_span", ...}`` event each."""
        payload = {
            "meta": self.meta,
            "counters": dict(self.counters),
            "summary": self.summary(),
            "events": self.events + [
                {"type": "host_span", **r}
                for r in recorded_spans(self._since_ns)],
        }
        text = json.dumps(payload, indent=2, sort_keys=True, default=_scalar)
        if hasattr(path, "write"):
            path.write(text)
        else:
            with open(path, "w") as f:
                f.write(text)
        return payload


def _scalar(x):
    if isinstance(x, jax.Array):       # a span's device field, read at last
        x = np.asarray(x)
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    return str(x)
