"""From a profiler trace to device metrics.

``record`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the metrics need, as plain lists: each device's operations (line
``XLA Ops``), and the benchmark's own host spans (``bench.*``).
``reduce`` turns that record into busy and idle time, kernel time by
name and the breakdown of where the device time and the idle gaps went.
Both are plain functions of their input, so a small recorded trace under
``tests/benchmark/`` checks them without a chip.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
#: operations that only hold others (their bodies are traced too): they
#: count towards busy time but not in the breakdown's operations
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``fused_topk_pallas`` of ``%fused_topk_pallas.1 = (f32[..]) ...``:
    the HLO instruction's name without its number."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def record(trace_dir: str) -> dict:
    """{"devices": {ordinal: [[name, start_ns, dur_ns, label], ...]},
    "host": [[name, start_ns, dur_ns], ...]} of the newest trace."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    label = " ".join(str(stats.get(key, "")) for key in
                                     ("long_name", "tf_op", "hlo_op"))
                    ops.append([ev.name, float(ev.start_ns),
                                float(ev.duration_ns), label])
            devices[int(m.group(2))] = ops
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window(rec: dict) -> tuple[float, float]:
    """(start, end) in ns of the benchmark's window span."""
    spans = [(s, s + d) for name, s, d in rec["host"] if name == "bench.window"]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def kernel_seconds(rec: dict, kernel: str) -> float:
    """Device seconds of every operation whose name or label holds
    ``kernel``, summed over the devices."""
    return sum(d for ops in rec["devices"].values()
               for name, _s, d, label in ops
               if kernel in name or kernel in label) / 1e9


def _host_at(rec: dict, t: float) -> str:
    """The innermost (shortest) host span around ``t``."""
    inside = [(d, name) for name, s, d in rec["host"] if s <= t <= s + d]
    return min(inside)[1] if inside else "none"


def reduce(rec: dict, chips: int) -> dict:
    """busy_s and window_s (busy averaged over the ``chips`` devices
    used), the idle share, and the breakdown's two top-10 lists."""
    w0, w1 = window(rec)
    ordinals = sorted(rec["devices"])[:chips]
    busy, gaps, by_op = [], [], {}
    for o in ordinals:
        spans = []
        for name, s, d, _label in rec["devices"][o]:
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                spans.append((lo, hi))
                base = op_name(name)
                if base not in CONTAINERS:
                    by_op[base] = by_op.get(base, 0.0) + (hi - lo) / 1e9
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_host_at(rec, (a + b) / 2), (b - a) / 1e9))
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / max(1, len(busy))
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in top_gaps]}
