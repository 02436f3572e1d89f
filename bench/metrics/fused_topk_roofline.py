"""fused_topk_roofline (%): the least time the chip could take for the
window's scans (each request's codes read once, or its int8 multiply-adds
at peak, whichever is longer; ``bench/roofline.py``) over the device time
of the ``fused_topk_pallas`` kernel's events in the trace."""

from bench import roofline, trace


def read(run):
    if run.trace is None:
        return None
    kernel_s = trace.kernel_seconds(run.trace, "fused_topk_pallas")
    if kernel_s <= 0:
        return None
    peak = roofline.peaks(run.device_kind)
    least = sum(roofline.least_seconds(
        *roofline.int8_scan(r["size"], run.n, run.d), peak)[0]
        for r in run.records if r["done"] is not None)
    return 100.0 * least / kernel_s
