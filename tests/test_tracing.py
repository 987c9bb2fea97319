"""Spans and counters of the serving path, and the stage scopes of the
programs it runs.

Every span of ``EeiServer`` is at once a ``jax.profiler`` host event named
``eei.<span>`` (carrying the id of the stack it belongs to) and a flat
integer counter ``<span>_ns`` in ``stats()``; the serving program of a
Krylov plan reports its Lanczos steps, which retire sums over the stack's
real rows.
"""

import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EeiServer, ProgramCache, SolverPlan
from repro.engine import engine as engine_mod
from repro.engine import server as server_mod
from repro.linalg.lanczos import krylov_shift_invert_reduce, lanczos_partial

KRYLOV = SolverPlan(method="eei_krylov", backend="jnp", spectrum="windowed",
                    krylov_m=80)
KRYLOV_SI = SolverPlan(method="eei_krylov_si", backend="jnp",
                       spectrum="windowed", krylov_m=80)
TRIDIAG = SolverPlan(method="eei_tridiag", backend="jnp", spectrum="windowed")
N, K = 96, 2

#: The spans one served solve walks through, in order.
SERVED = ("assemble", "copy_in", "launch", "device_wait", "fetch", "retire")


def _sym(seed: int, n: int = N) -> np.ndarray:
    a = np.random.default_rng(seed).standard_normal((n, n))
    return ((a + a.T) / 2).astype(np.float32)


def _spiked(seed: int, n: int = N) -> np.ndarray:
    """Two eigenvalues far above a bulk in [0, 1]: Lanczos meets its
    tolerance at the first Ritz check, well before the band cap."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    lam = np.concatenate([rng.uniform(0.0, 1.0, n - 2), [8.0, 10.0]])
    a = (q * lam) @ q.T
    return ((a + a.T) / 2).astype(np.float32)


def _solve(server: EeiServer, mats) -> list:
    futs = [server.submit(a, K) for a in mats]
    server.flush()
    return [f.result(timeout=120) for f in futs]


def test_served_solve_counts_each_span_and_reset_zeroes_them():
    server = EeiServer(KRYLOV, max_batch=2, cache=ProgramCache())
    _solve(server, [_sym(0)])
    stats = server.stats()
    for name in SERVED:
        assert stats[f"{name}_ns"] > 0, name
    # The compile runs inside the launch that needed it.
    assert stats["program_compiles"] == 1
    assert stats["launch_ns"] >= stats["program_compile_ns"] > 0
    assert stats["fallback_ns"] == 0
    assert stats["session_update_ns"] == 0
    assert stats["queue_wait_ns"] >= 0
    assert stats["lanczos_steps"] > 0
    counters = [key for key in stats
                if key.endswith("_ns") or key == "lanczos_steps"]
    assert all(type(stats[key]) is int for key in counters)
    server.reset_stats()
    after = server.stats()
    assert all(after[key] == 0 for key in counters)


def test_profiler_trace_joins_a_stacks_spans_by_its_id(tmp_path):
    server = EeiServer(KRYLOV, max_batch=2, cache=ProgramCache())
    _solve(server, [_sym(1)])  # warm: the traced solve compiles nothing
    jax.profiler.start_trace(str(tmp_path))
    try:
        _solve(server, [_sym(2)])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stacks = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("eei."):
                    stacks.setdefault(ev.name, set()).add(
                        dict(ev.stats)["stack"])
    assert {f"eei.{name}" for name in SERVED} <= set(stacks)
    ids = {frozenset(stacks[f"eei.{name}"]) for name in SERVED}
    assert len(ids) == 1 and len(next(iter(ids))) == 1


def _steps(plan: SolverPlan, a: np.ndarray) -> int:
    """Lanczos steps of one matrix, outside the serving path."""
    a = jnp.asarray(a)
    if plan.method == "eei_krylov_si":
        return int(krylov_shift_invert_reduce(a, K, True, plan.krylov_m)[-1])
    return int(lanczos_partial(a, plan.krylov_m, K, True).steps)


def _sharded(plan: SolverPlan) -> SolverPlan:
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return dataclasses.replace(plan, backend="sharded", mesh=mesh)


@pytest.mark.parametrize("plan", [KRYLOV, KRYLOV_SI, "sharded", TRIDIAG],
                         ids=["krylov", "krylov_si", "krylov_sharded",
                              "tridiag"])
def test_lanczos_steps_count_the_real_rows_only(plan):
    """Three requests ride a stack of four rows; the padding row repeats
    the first matrix (the one that converges early) and its steps are not
    counted."""
    plan = _sharded(KRYLOV) if plan == "sharded" else plan
    mats = [_spiked(10), _sym(11), _sym(12)]
    server = EeiServer(plan, max_batch=4, cache=ProgramCache())
    _solve(server, mats)
    assert server.stats()["stacks_dispatched"] == 1
    expected = 0
    if plan.method != "eei_tridiag":
        expected = sum(_steps(plan, a) for a in mats)
        assert 0 < expected <= 3 * plan.krylov_m
    assert server.stats()["lanczos_steps"] == expected


@pytest.mark.parametrize("plan", [KRYLOV, TRIDIAG], ids=["krylov", "tridiag"])
def test_stage_roles_name_the_ops_of_a_topk_program(plan):
    program = engine_mod.topk_program(plan, K, True, True)
    text = program.lower(
        jax.ShapeDtypeStruct((2, N, N), jnp.float32)).as_text(debug_info=True)
    for role in ("reduce", "spectrum", "components", "recover", "verify"):
        assert f"/{role}/" in text, role


def test_fallback_and_session_update_spans_are_counted():
    server = EeiServer(TRIDIAG, max_batch=2, cache=ProgramCache())
    bad = _sym(20, 16)
    bad[0, 0] = np.nan  # nothing can verify it: every link is tried
    fut = server.submit(bad, K)
    server.flush()
    with pytest.raises(np.linalg.LinAlgError):  # the last link's error
        fut.result(timeout=120)
    sid = server.open_session(_sym(21, 16).astype(np.float64), K)
    server.submit_update(sid, np.ones(16)).result(timeout=120)
    stats = server.stats()
    assert stats["requests_degraded"] + stats["requests_failed"] == 1
    assert stats["fallback_ns"] > 0
    assert stats["session_updates"] == 1
    assert stats["session_update_ns"] > 0


def test_latency_record_keeps_the_most_recent_requests(monkeypatch):
    monkeypatch.setattr(server_mod, "LATENCY_WINDOW", 4)
    server = EeiServer(TRIDIAG, max_batch=2, cache=ProgramCache())
    _solve(server, [_sym(s, 16) for s in range(10)])
    assert len(server.latencies_ms) == 4
    stats = server.stats()
    assert stats["requests_completed"] == 10
    assert stats["p99_latency_ms"] >= stats["p50_latency_ms"] > 0.0
    server.reset_stats()
    _solve(server, [_sym(s, 16) for s in range(6)])
    assert len(server.latencies_ms) == 4
