"""The trace reduction, the roofline counts and the peaks table."""

import json
import os

import pytest

from bench import roofline, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _synthetic():
    # window 0..100 ns on the host; device ops at 10-30, 20-40 (overlap)
    # and 60-70, plus one op outside the window
    return {"host": [["bench.window", 0.0, 100.0],
                     ["bench.call", 5.0, 40.0],
                     ["bench.other", 45.0, 14.0]],
            "devices": {0: [["fused_topk_pallas.3", 10.0, 20.0, ""],
                            ["fusion.12", 20.0, 20.0, "jit(run)/pad"],
                            ["copy.1", 60.0, 10.0, "fused_topk_pallas"],
                            ["fusion.12", 150.0, 5.0, ""]]}}


def test_reduce_busy_idle_and_breakdown():
    out = trace.reduce(_synthetic(), chips=1)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(40e-9)           # 10-40, 60-70
    assert out["idle_share"] == pytest.approx(0.6)
    assert dict((n, s) for n, s in out["device_ops"]) == pytest.approx(
        {"fused_topk_pallas": 20e-9, "fusion": 20e-9, "copy": 10e-9})
    gaps = dict((round(s * 1e9), n) for n, s in out["idle_gaps"])
    # 0-10 under the call, 40-60 under the innermost span that holds its
    # middle, 70-100 under the window only
    assert gaps == {10: "bench.call", 20: "bench.other", 30: "bench.window"}


def test_kernel_seconds_match_name_or_label():
    assert trace.kernel_seconds(_synthetic(), "fused_topk_pallas") == \
        pytest.approx(30e-9)


def test_reduce_needs_the_window_span():
    rec = _synthetic()
    rec["host"] = rec["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(rec, chips=1)


def test_peaks_are_keyed_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v4")


def test_roofline_of_an_int8_scan():
    p = roofline.peaks("TPU v5 lite")
    ops, nbytes = roofline.int8_scan(32, 5_000_000, 256)
    assert nbytes == 1.28e9 and ops == 2 * 32 * 5e6 * 256
    t, bound = roofline.least_seconds(ops, nbytes, p)
    assert bound == "hbm" and t == pytest.approx(1.28e9 / 819e9)
    t, bound = roofline.least_seconds(*roofline.int8_scan(4096, 10, 256), p)
    assert bound == "int8_ops"


def _recorded():
    # a traced window of product60m-flat-lpq8.batch-k100 on one TPU v5
    # lite: 32 requests, their device ops (HLO text cut short) and the
    # benchmark's host spans
    with open(os.path.join(HERE, "data", "trace_v5e_batch_k100.json")) as f:
        rec = json.load(f)
    rec["devices"] = {int(k): v for k, v in rec["devices"].items()}
    return rec


def test_reduce_a_recorded_chip_trace():
    rec = _recorded()
    out = trace.reduce(rec, chips=1)
    assert out["window_s"] == pytest.approx(12.174523646)
    assert out["busy_s"] == pytest.approx(12.083978576)
    assert 0 < out["idle_share"] < 0.01
    ops = dict(out["device_ops"])
    assert list(ops)[:2] == ["fused_topk_pallas", "pad"]
    assert ops["fused_topk_pallas"] == pytest.approx(11.95864525)
    assert trace.kernel_seconds(rec, "fused_topk_pallas") == pytest.approx(
        11.95864525)
    assert {n for n, _ in out["idle_gaps"]} <= {"bench.call", "bench.window"}
    assert len(out["device_ops"]) == 8 and len(out["idle_gaps"]) == 10
