"""Finds a cell's parts by name, so that a new configuration, traffic mix
or per-layer metric is a new file and never an edit.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic; their files are ``bench/configs/<config>.json``
and ``bench/traffic/<traffic>.json``, and the reader of a per-layer metric
is ``bench/metrics/<name>.py``, which defines ``read(run)`` returning a
number or None.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list
    root: str = ROOT      # the checkout the parts were read from


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, else every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it moves (a per-layer metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its parts."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    config = _load_json(os.path.join(root, "bench", "configs",
                                     w["config"] + ".json"))
    mix = _load_json(os.path.join(root, "bench", "traffic",
                                  w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=layer, root=root)


def reader(metric: str, root: str = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
