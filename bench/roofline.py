"""Operations and bytes a kernel call needs, from its shapes alone, and
the least time the chip could take for it.

The counts are of the algorithm, not of an implementation: a scan of an
int8 code matrix reads every code once and does one multiply and one add
per query, row and dimension.  So they stay the same whatever kernel,
tiling or padding a later change uses, and a roofline share built on
them can only rise by doing the same work faster.
"""

from __future__ import annotations

import json
import os

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, not a default."""
    with open(PEAKS_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_PATH}; add them with their source")
    return table[device_kind]


def int8_scan(queries: int, rows: int, d: int) -> tuple[float, float]:
    """(int8 operations, bytes) of scoring ``queries`` against ``rows``
    int8 codes of width ``d``: 2 ops per multiply-add, the codes read once."""
    return 2.0 * queries * rows * d, float(rows) * d


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline time of a call and which bound sets it."""
    t_ops = ops / peak["int8_ops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "int8_ops") if t_ops >= t_bytes else (t_bytes, "hbm")
