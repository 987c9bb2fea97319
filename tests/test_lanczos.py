"""Lanczos partial tridiagonalization: basis orthonormality, Ritz
interlacing, the krylov stage compositions, and planner routing.

The robustness contract of the krylov reduce stage (see
``src/repro/linalg/lanczos.py``):

* full (CGS2) reorthogonalization keeps ``max |Q^T Q - I|`` at
  machine-epsilon level across random / SPD / clustered-spectrum /
  rank-deficient matrices — the property that rules out ghost Ritz values;
* the active band's Ritz values satisfy the Poincare separation bounds
  against the full spectrum (``lam[i] <= theta[i] <= lam[i + n - m]`` —
  Cauchy interlacing generalized to rank-(n-m) compression);
* breakdown (an exact invariant subspace) restarts in a fresh orthogonal
  direction through an exactly-zero band junction, so rank-deficient
  matrices still fill a k-window wider than their rank;
* a stack runs one loop whose control it shares: each member stops on its
  own and matches the single-matrix reduce row by row, and the Ritz check
  runs only at its check steps;
* the ``eei_krylov`` / ``eei_krylov_si`` compositions run the *existing*
  windowed chain on the band and match the ``eigh`` oracle through every
  backend library.
"""

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.engine import (
    CalibrationTable,
    SolverEngine,
    SolverPlan,
    available_compositions,
    get_composition,
    plan_for,
    set_table,
)
from repro.linalg import (
    default_m,
    default_si_m,
    krylov_reduce,
    krylov_reduce_batched,
    krylov_shift_invert_reduce,
    krylov_shift_invert_reduce_batched,
    lanczos_partial,
    ritz_interlacing_holds,
    shift_invert_sigma,
)
from repro.linalg import sturm

BACKENDS = ["reference", "jnp", "pallas"]


def _matrix(kind: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n))
    a = (a + a.T) / 2
    if kind == "goe":
        return a
    q = np.linalg.qr(rng.standard_normal((n, n)))[0]
    if kind == "spd":
        lam = rng.uniform(0.1, 10.0, n)
    elif kind == "clustered":
        lam = np.concatenate([
            np.linspace(0.0, 1.0, n - 3),
            2.0 + 1e-8 * np.arange(3.0)])
    elif kind == "rank_deficient":
        lam = np.concatenate([
            np.zeros(n - max(2, n // 4)),
            rng.uniform(1.0, 5.0, max(2, n // 4))])
    else:
        raise ValueError(kind)
    return q @ np.diag(lam) @ q.T


_KINDS = ("goe", "spd", "clustered", "rank_deficient")


# ---------------------------------------------------------------------------
# Properties of the iteration itself
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    kind=st.sampled_from(_KINDS),
    n=st.integers(min_value=8, max_value=40),
    m_raw=st.integers(min_value=2, max_value=40),
    k_raw=st.integers(min_value=1, max_value=40),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_reorthogonalization_keeps_basis_orthonormal(
        kind, n, m_raw, k_raw, seed):
    """``max |Q^T Q - I|`` over the retained basis stays at eps level for
    every matrix class — full CGS2 reorthogonalization's contract."""
    m = min(m_raw, n)
    k = min(k_raw, m)
    a = jnp.asarray(_matrix(kind, n, seed))
    res = lanczos_partial(a, m, k)
    steps = int(res.steps)
    assert 1 <= steps <= m
    q = np.asarray(res.q)[:, :steps]  # active columns only
    gram = q.T @ q
    assert np.max(np.abs(gram - np.eye(steps))) < 1e-12
    # Columns beyond the active block are exactly zero by construction.
    assert not np.any(np.asarray(res.q)[:, steps:])


@settings(max_examples=12, deadline=None)
@given(
    kind=st.sampled_from(_KINDS),
    n=st.integers(min_value=8, max_value=40),
    m_raw=st.integers(min_value=2, max_value=40),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_ritz_values_interlace_full_spectrum(kind, n, m_raw, seed):
    """Active-band Ritz values obey the Poincare separation bounds
    ``lam[i] <= theta[i] <= lam[i + n - m]`` — orthonormality's spectral
    consequence (a ghost Ritz value from lost orthogonality breaks it)."""
    m = min(m_raw, n)
    a = _matrix(kind, n, seed)
    res = lanczos_partial(jnp.asarray(a), m, min(2, m))
    steps = int(res.steps)
    d = np.asarray(res.d)[:steps]
    e = np.asarray(res.e)[: steps - 1]
    theta = np.linalg.eigvalsh(
        np.diag(d) + np.diag(e, 1) + np.diag(e, -1))
    lam = np.linalg.eigvalsh(a)
    assert bool(ritz_interlacing_holds(
        jnp.asarray(lam), jnp.asarray(theta), rtol=1e-9))


def test_ritz_interlacing_holds_rejects_ghosts():
    lam = jnp.asarray(np.linspace(0.0, 1.0, 16))
    theta = jnp.asarray([0.2, 5.0])  # 5.0 sits far above lam[-1]
    assert not bool(ritz_interlacing_holds(lam, theta))
    assert bool(ritz_interlacing_holds(lam, jnp.asarray([0.2, 0.9])))


def test_breakdown_restart_fills_window_past_rank():
    """A rank-r matrix breaks down after ~r steps; the restart must keep
    filling the band so a k > r window still reports the near-zero tail."""
    n, r, k = 48, 4, 8
    rng = np.random.default_rng(3)
    low = rng.standard_normal((n, r))
    a = jnp.asarray(low @ low.T)
    lam = np.linalg.eigvalsh(np.asarray(a))
    out = SolverEngine(SolverPlan(method="eei_krylov",
                                  backend="jnp")).topk(a, k)
    np.testing.assert_allclose(
        np.asarray(out.eigenvalues), lam[-k:], atol=1e-8 * lam[-1])


def test_guard_filled_band_entries_stay_out_of_the_window():
    """Early convergence guard-fills unused band slots outside the active
    spectrum on the side away from the extreme — the windowed spectrum
    stage must never select one."""
    n, m, k = 24, 16, 4
    a = jnp.asarray(_matrix("rank_deficient", n, seed=7))
    res = lanczos_partial(a, m, k, largest=True)
    steps = int(res.steps)
    if steps < m:  # breakdown restarts can still fill all m slots
        active_min = float(np.min(np.asarray(res.d)[:steps]))
        guards = np.asarray(res.d)[steps:]
        assert np.all(guards < active_min)


def test_shift_invert_sigma_sits_outside_the_spectrum():
    a = jnp.asarray(_matrix("goe", 32, seed=11))
    lam = np.linalg.eigvalsh(np.asarray(a))
    assert float(shift_invert_sigma(a, largest=True)) > lam[-1]
    assert float(shift_invert_sigma(a, largest=False)) < lam[0]


def test_default_band_sizes():
    assert default_m(4096, 16) == 256
    assert default_m(4096, 1) == 128
    assert default_m(64, 16) == 64  # capped at n
    assert default_si_m(4096, 16) == 128
    # krylov_reduce honors an explicit m override (band shape = m).
    a = jnp.asarray(_matrix("goe", 32, seed=0))
    d, e, q, steps = krylov_reduce(a, 2, True, m=8)
    assert d.shape == (8,) and e.shape == (7,) and q.shape == (32, 8)
    assert steps.shape == () and 1 <= int(steps) <= 8


# ---------------------------------------------------------------------------
# One loop for a stack: shared control, per-member stopping
# ---------------------------------------------------------------------------


def _mixed_stack(lead: tuple, early: str = "spiked",
                 n: int = 64) -> np.ndarray:
    """float32 stack alternating a matrix that stops at the first Ritz
    check and a Wishart matrix (no gap at the spectrum's edge, so it runs
    to the ``m`` cap).  The early one is a spiked Wishart matrix, or with
    ``early="invariant"`` a diagonal of four distinct values: an exact
    invariant subspace after four steps, so the loop restarts (the values
    are exact in float32, so the breakdown is clean)."""
    rng = np.random.default_rng(17)
    mats = []
    for i in range(int(np.prod(lead))):
        x = rng.standard_normal((n, 2 * n))
        if i % 2 == 0 and early == "invariant":
            mats.append(np.diag(np.repeat([1.0, 2.0, 3.0, 4.0], n // 4)))
            continue
        if i % 2 == 0:
            x[:4] *= 3.0
        mats.append(x @ x.T / (2 * n))
    return np.stack(mats).reshape(lead + (n, n)).astype(np.float32)


_REDUCES = {
    "direct": (krylov_reduce_batched, krylov_reduce),
    "shift_invert": (krylov_shift_invert_reduce_batched,
                     krylov_shift_invert_reduce),
}


@pytest.mark.parametrize("mode,early,lead", [
    ("direct", "spiked", (2,)),
    ("direct", "spiked", (2, 2)),
    ("direct", "invariant", (2,)),
    ("shift_invert", "spiked", (2,)),
    ("shift_invert", "spiked", (2, 2)),
], ids=["direct-b2", "direct-b2x2", "direct-restart-b2",
        "shift_invert-b2", "shift_invert-b2x2"])
def test_stack_reduce_matches_per_matrix_reduce(mode, early, lead):
    """A stack where one member stops at the first check and the other runs
    to the cap gives, row by row, the single-matrix reduce's steps and
    (within float32 rounding) its band, basis and shift."""
    a = _mixed_stack(lead, early)
    batched, single = _REDUCES[mode]
    k, m = 4, 64
    out = batched(jnp.asarray(a), k, True, m)
    steps = np.asarray(out[-1])
    assert steps.shape == lead
    assert steps.min() < m and steps.max() == m  # members stop apart
    for idx in np.ndindex(*lead):
        ref = single(jnp.asarray(a[idx]), k, True, m)
        assert int(ref[-1]) == int(steps[idx])
        for got, want in zip(out[:-1], ref[:-1]):
            np.testing.assert_allclose(
                np.asarray(got[idx]), np.asarray(want),
                rtol=1e-4, atol=1e-4 * float(np.max(np.abs(want))))


def test_stack_lanczos_partial_matches_per_matrix_resid():
    """The Ritz residuals that decide each member's stop are the
    single-matrix loop's, member by member."""
    a = _mixed_stack((2,))
    res = lanczos_partial(jnp.asarray(a), 64, 4)
    assert int(res.steps[0]) < 64 == int(res.steps[1])
    for i in range(2):
        ref = lanczos_partial(jnp.asarray(a[i]), 64, 4)
        assert int(ref.steps) == int(res.steps[i])
        # A converged pair's residual is rounding; agree to the stop
        # rule's resolution (float32 rtol 1e-5).
        np.testing.assert_allclose(np.asarray(res.resid[i]),
                                   np.asarray(ref.resid), rtol=1e-3,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(res.d[i]), np.asarray(ref.d),
                                   rtol=1e-4, atol=1e-4)


def test_stack_ritz_check_runs_only_at_its_check_steps(monkeypatch):
    """The Ritz check's bisection runs once per check step for the whole
    stack, not at every Lanczos step: a loop vmapped over the stack turns
    the check's ``cond`` into a ``select`` that runs it every step."""
    calls = []
    bisect = sturm.bisect_eigenvalues_windowed_batched

    def counted(d, e, k, largest=True, n_iter=0):
        jax.debug.callback(lambda: calls.append(1))
        return bisect(d, e, k, largest, n_iter)

    monkeypatch.setattr(sturm, "bisect_eigenvalues_windowed_batched", counted)
    rng = np.random.default_rng(23)
    a = rng.standard_normal((2, 64, 64)).astype(np.float32)
    a = jnp.asarray(a + np.swapaxes(a, 1, 2))
    # rtol no residual meets: both members run to m = 64, steps 1..64 hold
    # two check steps (32 and 64).
    krylov_reduce_batched.clear_cache()  # trace again, with the counter
    d, e, q, steps = krylov_reduce_batched(a, 4, True, 64, rtol=1e-30)
    jax.effects_barrier()
    assert np.asarray(steps).tolist() == [64, 64]
    assert len(calls) == 2


# ---------------------------------------------------------------------------
# Stage-graph integration: compositions, backends, oracle conformance
# ---------------------------------------------------------------------------


def test_krylov_compositions_registered_and_validate():
    names = available_compositions()
    assert {"eei_krylov", "eei_krylov_si"} <= set(names)
    for name in ("eei_krylov", "eei_krylov_si"):
        comp = get_composition(name)
        comp.validate()
        assert comp.solve is None  # a partial basis has no full table


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", ["eei_krylov", "eei_krylov_si"])
def test_krylov_topk_matches_eigh_oracle(method, backend):
    rng = np.random.default_rng(5)
    b, n, k = 2, 96, 4
    a = rng.standard_normal((b, n, n))
    a = jnp.asarray((a + np.swapaxes(a, 1, 2)) / 2)
    lam_o, v_o = jax.vmap(jnp.linalg.eigh)(a)
    span = float(jnp.max(lam_o[:, -1] - lam_o[:, 0]))
    out = SolverEngine(SolverPlan(method=method, backend=backend)).topk(a, k)
    assert out.eigenvalues.shape == (b, k)
    assert out.vectors.shape == (b, k, n)
    assert float(jnp.max(jnp.abs(
        out.eigenvalues - lam_o[:, -k:]))) / span < 1e-10
    dots = jnp.abs(jnp.einsum("bkn,bnk->bk", out.vectors, v_o[:, :, -k:]))
    assert float(jnp.min(dots)) > 1.0 - 1e-8


@pytest.mark.parametrize("method", ["eei_krylov", "eei_krylov_si"])
def test_krylov_smallest_window(method):
    a = jnp.asarray(_matrix("goe", 80, seed=9))
    lam = np.linalg.eigvalsh(np.asarray(a))
    out = SolverEngine(SolverPlan(method=method, backend="jnp")).topk(
        a, 3, largest=False)
    np.testing.assert_allclose(np.asarray(out.eigenvalues), lam[:3],
                               atol=1e-9 * (lam[-1] - lam[0]))


def test_krylov_clustered_spectrum_shift_invert():
    """The si mode's raison d'etre: a clustered extremal group resolved
    through the inverted operator's separated theta spectrum."""
    a = jnp.asarray(_matrix("clustered", 64, seed=13))
    lam = np.linalg.eigvalsh(np.asarray(a))
    out = SolverEngine(SolverPlan(method="eei_krylov_si",
                                  backend="jnp")).topk(a, 3)
    np.testing.assert_allclose(np.asarray(out.eigenvalues), lam[-3:],
                               atol=1e-9 * (lam[-1] - lam[0]))


@pytest.mark.parametrize("method", ["eei_krylov", "eei_krylov_si"])
def test_krylov_eigenvalues_program(method):
    a = jnp.asarray(_matrix("spd", 72, seed=2))
    lam = np.linalg.eigvalsh(np.asarray(a))
    ev = SolverEngine(SolverPlan(method=method, backend="jnp")).eigenvalues(
        a, k=4)
    np.testing.assert_allclose(np.asarray(ev), lam[-4:],
                               atol=1e-9 * (lam[-1] - lam[0]))


def test_krylov_solve_raises():
    """No full-table solve exists for a partial basis — the engine must
    say so, not silently produce an incomplete table."""
    a = jnp.asarray(_matrix("goe", 16, seed=0))
    eng = SolverEngine(SolverPlan(method="eei_krylov", backend="jnp"))
    with pytest.raises(ValueError, match="no 'solve' chain"):
        eng.solve(a)


def test_krylov_plan_hashable_and_m_override_runs():
    plan = SolverPlan(method="eei_krylov", backend="jnp", krylov_m=24)
    hash(plan)  # program caches key on the plan
    a = jnp.asarray(_matrix("goe", 48, seed=1))
    lam = np.linalg.eigvalsh(np.asarray(a))
    out = SolverEngine(plan).topk(a, 2)
    np.testing.assert_allclose(np.asarray(out.eigenvalues), lam[-2:],
                               atol=1e-6 * (lam[-1] - lam[0]))


# ---------------------------------------------------------------------------
# Planner routing
# ---------------------------------------------------------------------------


def test_planner_routes_narrow_large_windows_to_krylov():
    table = CalibrationTable(
        eigh_crossover_n=4, dense_crossover_n=8,
        prod_diff_blocks=(32, 32, 32), sturm_blocks=(8, 64),
        windowed_k_frac=1.0, krylov_n_min=64)
    set_table(table)
    try:
        # Past the calibrated crossover with a narrow window: krylov.
        assert plan_for((128, 128), k=4).method == "eei_krylov"
        # Below the size crossover: the dense Householder reduce.
        assert plan_for((32, 32), k=2).method == "eei_tridiag"
        # Window too wide relative to n (k > n/16): band ~ n, dense wins.
        assert plan_for((128, 128), k=32).method == "eei_tridiag"
        # No window at all: nothing to truncate the band for.
        assert plan_for((128, 128)).method == "eei_tridiag"
        # Explicit method always wins over the heuristics.
        assert plan_for((128, 128), k=4,
                        method="eei_tridiag").method == "eei_tridiag"
    finally:
        set_table(None)


def test_planner_krylov_n_min_falls_back_without_table():
    table = CalibrationTable(
        eigh_crossover_n=4, dense_crossover_n=8,
        prod_diff_blocks=(32, 32, 32), sturm_blocks=(8, 64),
        windowed_k_frac=1.0)  # v3-style: no krylov_n_min measured
    set_table(table)
    try:
        from repro.engine.plan import KRYLOV_N_MIN, resolved_krylov_n_min

        assert resolved_krylov_n_min() == KRYLOV_N_MIN
        # Below the static fallback: stays on the dense reduce.
        assert plan_for((256, 256), k=4).method == "eei_tridiag"
    finally:
        set_table(None)
