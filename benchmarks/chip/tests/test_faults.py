"""The check must come out false when the timed path is broken underneath:
once for each fault a cell can have.  Each test drives a whole run of a
tiny cell on the CPU (the look for a chip skipped) with one fault planted
in the program, and sees ``correct`` false.

Faults: an answer altered where it is produced (every cell); half of a
stack's answers left out, replaced by the other half's (the batched
cell).  The cells run on one chip, so there is no exchange between chips
to leave out, and no session or training state to leave unchanged.
"""

import time

import jax
import pytest

import run
from conftest import TINY_CELLS


def _wrap_programs(monkeypatch, alter):
    """Route every compiled server program's output through ``alter``."""
    from repro.engine import server as server_mod

    get = server_mod.ProgramCache.get

    def broken_get(self, bucket, plan, dtype, *, verify=False):
        program = get(self, bucket, plan, dtype, verify=verify)
        return lambda *ops: alter(program(*ops))

    monkeypatch.setattr(server_mod.ProgramCache, "get", broken_get)


def _shift_eigenvalues(out):
    result, flags = out
    return result._replace(eigenvalues=result.eigenvalues * 1.001), flags


def _half_batch(out):
    result, flags = out
    b = result.eigenvalues.shape[0]
    if b < 2:
        return out
    keep = jax.numpy.arange(b) % (b // 2)  # rows b/2.. answer as rows 0..
    return jax.tree.map(lambda x: x[keep], result), flags


def _run(bench, name):
    result, _ = run.run_cell(bench, bench.cell(name), seed=5, seconds=0.6,
                             trace=False, t_process=time.perf_counter(),
                             require_tpu=False)
    return result


CELLS = [f"{c}.{t}" for c, t in TINY_CELLS]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_caught(tiny_bench, monkeypatch, cell):
    _wrap_programs(monkeypatch, _shift_eigenvalues)
    result = _run(tiny_bench, cell)
    assert not result["correct"]
    assert result["check"]["eig_err"]["value"] > \
        result["check"]["eig_err"]["limit"]


@pytest.mark.parametrize("cell", [c for c in CELLS if c.endswith(".burst")])
def test_half_of_the_batch_left_out_is_caught(tiny_bench, monkeypatch, cell):
    _wrap_programs(monkeypatch, _half_batch)
    assert not _run(tiny_bench, cell)["correct"]
