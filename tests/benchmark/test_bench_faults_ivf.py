"""The ivf cell's faults and control, as ``test_bench_faults.py`` checks
the flat cell (a file of its own, so another test worker runs it): a plan
that probes fewer lists, loses a list or skips the tail of long lists
returns rows that are scored right and sorted, and still fails."""

import pytest

from bench import control, faults, registry
from test_bench_faults import _run

NAME = "bigann-ivf-lpq8.batch-k10"


@pytest.mark.parametrize("fault", (None,) + faults.IVF,
                         ids=lambda f: getattr(f, "__name__", "sound"))
def test_ivf_sound_run_is_correct_and_broken_runs_are_not(tiny_root, fault):
    out = _run(tiny_root, NAME, fault)
    assert out["correct"] is (fault is None), out["checks"]
    if fault in (faults.half_probe, faults.dropped_list, faults.short_lists):
        # the rows it did return are scored right: only the rank gap sees it
        assert out["checks"]["score_err"]["value"] == 0.0
        assert out["checks"]["rank_gap"]["value"] > 0.1


def test_ivf_control_is_not_correct(tiny_root):
    out = _run(tiny_root, NAME, control.system(
        registry.load_cell(NAME, tiny_root)), seconds=0.5)
    assert not out["correct"], out["checks"]
