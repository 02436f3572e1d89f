"""A run with the timed path broken underneath comes out not correct, and
the control (the reference one precision lower) does too; the same run
unbroken comes out correct.  The runs skip the look for a chip and use
CPU-sized configurations."""

import time

import pytest

from bench import control, faults, harness, registry

NAME = "product60m-flat-lpq8.batch-k100"


def _run(root, name, system=None, seconds=0.3):
    cell = registry.load_cell(name, root)
    return harness.run_cell(cell, 2 ** 32 + 5, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            system=system)


@pytest.mark.parametrize("fault", (None,) + faults.FLAT,
                         ids=lambda f: getattr(f, "__name__", "sound"))
def test_sound_run_is_correct_and_broken_runs_are_not(tiny_root, fault):
    out = _run(tiny_root, NAME, fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_control_is_not_correct(tiny_root):
    cell = registry.load_cell(NAME, tiny_root)
    out = _run(tiny_root, NAME, control.system(cell), seconds=0.5)
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0


def test_serving_memory_is_reported_beside_the_build_peak(tiny_root):
    out = _run(tiny_root, NAME)
    device = out["device"]
    assert device["memory_peak_bytes"] >= 0
    assert device["build_peak_bytes"] >= 0
    hbm = out["metrics"]["hbm_bytes_per_row"]["value"]
    n = registry.load_cell(NAME, tiny_root).config["n"]
    assert hbm * n == pytest.approx(device["memory_peak_bytes"])
