"""The control -- the float64 reference computed one precision below the
configurations' float32 (matrix and answer rounded to bfloat16) -- must
fail the check at every cell's limits.  Run here at the tiny cells' sizes
with the real configurations' limits; on the chip it was run at each
cell's own size (``PERF.md``)."""

import time

import numpy as np
import pytest

import run
from conftest import TINY_CELLS
from harness import reference


@pytest.mark.parametrize("cell", [f"{c}.{t}" for c, t in TINY_CELLS])
def test_bf16_control_fails_the_check(tiny_bench, cell):
    result, _ = run.run_cell(tiny_bench, tiny_bench.cell(cell), seed=9,
                             seconds=0.4, trace=False,
                             t_process=time.perf_counter(),
                             require_tpu=False, control=True)
    assert not result["correct"]
    num = result["check"]["eig_err"]
    assert num["value"] > 3 * num["limit"]


def test_reference_matches_dense_eigh():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((40, 40))
    a = (a + a.T) / 2
    lam, vecs = reference.reference_topk(a, 3, largest=True)
    full, v = np.linalg.eigh(a)
    np.testing.assert_allclose(lam, full[-3:], atol=1e-10)
    np.testing.assert_allclose(np.abs(np.sum(vecs * v[:, -3:].T, axis=1)),
                               1.0, atol=1e-8)
    nums = reference.numbers(a, full[-3:], lam, vecs)
    assert nums["eig_err"] < 1e-12 and nums["residual"] < 1e-12
