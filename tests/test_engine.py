"""SolverEngine: batched solve/topk vs a vmapped eigh oracle on every
backend, planner heuristics, registry dispatch, and the batched
Cauchy-interlacing property on stacked minor spectra."""

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import (
    SolveResult,
    SolverEngine,
    SolverPlan,
    available_backends,
    get_backend,
    plan_for,
)
from repro.linalg import interlace

B, N = 3, 18


def _stack(seed: int, b: int = B, n: int = N) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((b, n, n))
    return jnp.asarray((a + np.swapaxes(a, 1, 2)) / 2)


def _oracle(a):
    lam, v = jax.vmap(jnp.linalg.eigh)(a)
    return lam, jnp.swapaxes(v * v, -1, -2)


def _host_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def _plan(backend: str, method: str = "eei_tridiag") -> SolverPlan:
    mesh = _host_mesh() if backend == "sharded" else None
    return SolverPlan(method=method, backend=backend, mesh=mesh)


BACKENDS = ["reference", "jnp", "pallas", "sharded"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", ["eigh", "eei_dense", "eei_tridiag"])
def test_batched_solve_matches_vmapped_eigh(backend, method):
    a = _stack(0)
    lam_ref, mags_ref = _oracle(a)
    lam, mags = SolverEngine(_plan(backend, method)).solve(a)
    assert lam.shape == (B, N) and mags.shape == (B, N, N)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_ref),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(mags), np.asarray(mags_ref),
                               rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_topk_matches_vmapped_eigh(backend):
    a = _stack(1)
    lam_ref, v_ref = jax.vmap(jnp.linalg.eigh)(a)
    k = 4
    lam, vecs = SolverEngine(_plan(backend)).topk(a, k)
    assert lam.shape == (B, k) and vecs.shape == (B, k, N)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_ref[:, -k:]),
                               rtol=1e-6, atol=1e-8)
    ref = np.asarray(jnp.swapaxes(v_ref[..., :, -k:], -1, -2))
    got = np.asarray(vecs)
    err = np.minimum(np.abs(got - ref), np.abs(got + ref)).max()
    assert err < 1e-5, err
    # residual check: A v = lam v per pair
    res = jnp.einsum("bij,bkj->bki", a, vecs) - lam[..., None] * vecs
    assert float(jnp.abs(res).max()) < 1e-5


def test_single_matrix_round_trip():
    a = _stack(2, b=1)[0]
    lam_ref, v_ref = jnp.linalg.eigh(a)
    engine = SolverEngine(SolverPlan(method="eei_tridiag"))
    lam, mags = engine.solve(a)
    assert lam.shape == (N,) and mags.shape == (N, N)
    np.testing.assert_allclose(np.asarray(mags),
                               np.asarray((v_ref * v_ref).T),
                               rtol=1e-4, atol=1e-7)
    ev, vecs = engine.topk(a, 2)
    assert ev.shape == (2,) and vecs.shape == (2, N)


def test_eigenvalues_only_and_microbatching():
    a = _stack(3, b=5)
    lam_ref, _ = _oracle(a)
    engine = SolverEngine(SolverPlan(method="eei_tridiag", max_batch=2))
    lam = engine.eigenvalues(a)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_ref),
                               rtol=1e-6, atol=1e-8)
    lam2, _ = engine.solve(a)  # 5 -> chunks of 2, 2, 1
    np.testing.assert_allclose(np.asarray(lam2), np.asarray(lam_ref),
                               rtol=1e-6, atol=1e-8)


def test_microbatch_ragged_tail_reuses_one_program_shape():
    """b=5 with max_batch=2 must run every chunk at the (2, n, n) step shape
    — the ragged 1-row tail is padded up and sliced, not recompiled."""
    engine = SolverEngine(SolverPlan(method="eei_tridiag", max_batch=2))
    seen = []

    def fake_program(a):
        seen.append(a.shape)
        return SolveResult(jnp.zeros(a.shape[:2]), jnp.zeros(a.shape))

    engine._run(fake_program, _stack(6, b=5))
    assert seen == [(2, N, N)] * 3  # uniform shapes: one executable
    # and the padded-tail path is numerically invisible
    a = _stack(6, b=5)
    lam_ref, _ = _oracle(a)
    lam, _ = engine.solve(a)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_ref),
                               rtol=1e-6, atol=1e-8)


def test_sharded_backend_pads_indivisible_stack():
    mesh = _host_mesh()
    a = _stack(4, b=3)
    lam_ref, mags_ref = _oracle(a)
    plan = SolverPlan(method="eei_tridiag", backend="sharded", mesh=mesh)
    lam, mags = SolverEngine(plan).solve(a)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_ref),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(mags), np.asarray(mags_ref),
                               rtol=1e-4, atol=1e-7)


def test_batched_minor_spectra_interlace():
    """Cauchy interlacing holds for every matrix and minor in the stack."""
    a = _stack(5)
    plan = SolverPlan(method="eei_tridiag", backend="jnp")
    stages = get_backend(plan)
    d, e, _ = stages.tridiagonalize(a, False)
    lam = stages.tridiag_eigenvalues(d, e)
    mu = stages.tridiag_minor_spectra(d, e)  # (b, n, n-1)
    assert mu.shape == (B, N, N - 1)
    for bi in range(B):
        for j in range(N):
            assert bool(interlace.interlacing_holds(lam[bi], mu[bi, j])), \
                (bi, j)


# ---------------------------------------------------------------------------
# Planner + registry
# ---------------------------------------------------------------------------


def test_planner_heuristics():
    # Whatever the crossovers resolve to (calibrated or fallback), the
    # method choice must respect them.
    from repro.engine import resolved_crossovers

    eigh_x, dense_x = resolved_crossovers()
    assert plan_for((eigh_x, eigh_x)).method == "eigh"
    if dense_x > eigh_x:
        assert plan_for((dense_x, dense_x)).method == "eei_dense"
    big = max(eigh_x, dense_x) + 1
    assert plan_for((4, big, big)).method == "eei_tridiag"
    assert plan_for((big, big), k=big).method == "eigh"
    # off-TPU hosts get the portable fused-jnp backend
    assert plan_for((big, big)).backend in ("jnp", "pallas")
    mesh = _host_mesh()
    # 1-device data axis -> not worth sharding
    assert plan_for((4, big, big), mesh=mesh).backend != "sharded"


def test_planner_reads_calibration_table():
    """SolverPlan resolution consults the calibration table when set, and
    falls back to the static constants when none is available."""
    from repro.engine import CalibrationTable, plan, set_table

    try:
        set_table(CalibrationTable(
            eigh_crossover_n=4, dense_crossover_n=10,
            prod_diff_blocks=(32, 32, 32), sturm_blocks=(8, 64)))
        assert plan.resolved_crossovers() == (4, 10)
        assert plan_for((8, 8)).method == "eei_dense"  # 4 < 8 <= 10
        assert plan_for((12, 12)).method == "eei_tridiag"
        # Pallas backend picks its tile shapes up from the same table.
        stages = get_backend(SolverPlan(backend="pallas"))
        assert stages.name == "pallas"
    finally:
        set_table(None)  # back to the resolution chain
    assert plan.resolved_crossovers()[0] >= 1


def test_plan_validation():
    with pytest.raises(ValueError):
        SolverPlan(method="nope")
    with pytest.raises(ValueError):
        SolverPlan(backend="nope")
    with pytest.raises(ValueError):
        SolverPlan(backend="sharded")  # mesh required
    with pytest.raises(ValueError):
        SolverEngine(SolverPlan()).topk(_stack(0), 0)


def test_float64_pallas_plan_fails_at_planning():
    """The Pallas kernels take float32 only: a float64 pallas plan is
    refused when it is built, not deep inside a dispatch."""
    with pytest.raises(ValueError, match="float64"):
        SolverPlan(backend="pallas", precision="float64")
    with pytest.raises(ValueError, match="float64"):
        plan_for((64, 64), k=4, backend="pallas", precision="float64")
    assert plan_for((64, 64), k=4, backend="jnp",
                    precision="float64").precision == "float64"


def test_registry_lists_all_backends():
    assert set(available_backends()) >= {"reference", "jnp", "pallas",
                                         "sharded"}
    for name in ["reference", "jnp", "pallas"]:
        stages = get_backend(SolverPlan(backend=name))
        assert stages.name == name


def test_spectral_engine_shim_removed():
    """The deprecated SpectralEngine façade is gone; the engine is the API."""
    import repro.core as core

    assert not hasattr(core, "SpectralEngine")
    with pytest.raises(ImportError):
        from repro.core import spectral  # noqa: F401


def test_dense_signs_one_lu_matches_per_pair_solves():
    """Batched one-LU sign recovery == the per-(matrix, pair) solve oracle."""
    from repro.core.directions import (
        inverse_iteration_signs,
        inverse_iteration_signs_batched,
    )

    a = _stack(7, b=4, n=20)
    lam, v = jax.vmap(jnp.linalg.eigh)(a)
    k = 5
    lam_sel = lam[:, -k:]
    mags_sel = jnp.swapaxes(v * v, -1, -2)[:, -k:, :]
    batched = inverse_iteration_signs_batched(a, lam_sel, mags_sel)
    per_pair = jax.vmap(
        jax.vmap(inverse_iteration_signs, in_axes=(None, 0, 0))
    )(a, lam_sel, mags_sel)
    assert batched.shape == (4, k, 20)
    np.testing.assert_allclose(np.asarray(batched), np.asarray(per_pair),
                               rtol=1e-10, atol=1e-12)
