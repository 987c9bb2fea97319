"""Whole runs of the tiny cells on the CPU: set-up, window, release and
check, through the real drivers, with the look for a chip skipped."""

import time

import pytest

import run
from conftest import TINY_CELLS


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("cell", [f"{c}.{t}" for c, t in TINY_CELLS])
def test_tiny_cell_runs_and_is_correct(tiny_bench, cell, trace):
    c = tiny_bench.cell(cell)
    result, info = run.run_cell(tiny_bench, c, seed=2**31 + 11, seconds=0.6,
                                trace=trace, t_process=time.perf_counter(),
                                require_tpu=False)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert info["window_compiles"] == 0
    names = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    assert set(result["metrics"]) == names
    assert list(result)[-1] == "check"
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert "breakdown" in result
