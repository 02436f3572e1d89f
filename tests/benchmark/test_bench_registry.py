"""The benchmark finds a cell's parts by name, and BENCHMARK.json keeps
to the shape its readers expect."""

import json
import os
import re

import pytest

from conftest import ROOT, copy_bench
from bench import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = copy_bench(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "product60m-flat-lpq8.json")) as f:
        cfg = json.load(f)
    cfg["n"] = 4096
    with open(os.path.join(b, "configs", "new-config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "new-mix.json"), "w") as f:
        json.dump({"cycle": 4, "k": 7,
                   "batch": {"dist": "pareto", "alpha": 1.2, "min": 3, "max": 3},
                   "pool": 64, "check": 8}, f)
    with open(os.path.join(b, "metrics", "new_metric.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    bench = _bench()
    bench["workloads"].append({"name": "new-config.new-mix",
                               "config": "new-config", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "x",
                               "better": "lower", "source": "host_clock",
                               "layer": "l", "moves": "qps",
                               "workloads": ["new-config.new-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = registry.load_cell("new-config.new-mix", root)
    assert cell.config["n"] == 4096 and cell.mix["k"] == 7
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert registry.reader("new_metric", root)(None) == 42.0
    # the new cell reports setup_s and the metrics with no workloads list
    assert {"setup_s", "recall_at_k", "hbm_bytes_per_row"} <= {
        m["name"] for m in cell.end_to_end}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        registry.load_cell("no-such-cell")


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_loads_and_reports(cell):
    c = registry.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(registry.reader(m["name"]))
    assert c.config["checks"], "every configuration states its checks"


def test_benchmark_json_names_units_and_bounds():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"] + b["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for c in b["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg), c["name"]
