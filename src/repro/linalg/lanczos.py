"""Lanczos partial tridiagonalization — the Krylov ``reduce`` stage.

Dense Householder reduction costs O(n^3) with a sequential outer loop — the
wall the EEI pipeline hits at n >= 4096 even though everything downstream of
the reduce stage is O(n k) on the windowed path.  For a top-k window a
Krylov subspace of dimension m << n suffices: m Lanczos steps build an
orthonormal basis ``Q (n, m)`` and a tridiagonal band ``T = Q^T A Q`` whose
extremal Ritz pairs converge to A's extremal eigenpairs long before m
reaches n.  The stage graph makes this *just another reduce stage*: the
``(d, e, q)`` it emits feed the existing windowed Sturm spectrum stage, the
minor-determinant components stage and the sign-recurrence recover stage
unchanged — all of them are band-size agnostic, and the back-transform with
``Q`` lifts band eigenvectors to the dense basis exactly as it does for
Householder's square ``Q``.

Robustness follows the classical playbook:

* **Full reorthogonalization** (CGS2 — "twice is enough") against every
  retained basis vector keeps ``max |Q^T Q - I|`` at machine-epsilon level
  so no ghost Ritz values appear (property-tested across SPD / clustered /
  rank-deficient matrices in ``tests/test_lanczos.py``).
* **Residual-based stopping**: every ``check_every`` steps the windowed
  Ritz values of the current band are bisected and the Ritz residual bound
  ``|A y - theta y| = beta_j |s_j[last]|`` evaluated; the loop exits when
  every windowed pair meets ``rtol`` (relative to the band's spectral
  scale) — or at the ``m`` cap.
* **Breakdown restart**: ``beta_j ~ 0`` means an exact invariant subspace
  was captured.  The iteration restarts with a fresh pseudo-random
  direction orthogonalized against the basis; the band decouples through an
  exactly-zero junction — the same decoupling the serving runtime's
  guard-diagonal embedding relies on — so matrices whose first Krylov space
  is deficient (rank-deficient / high-multiplicity spectra) still fill the
  band.

Unused band slots (early convergence) are filled with a guard value
strictly outside the active band's spectrum on the side *away* from the
requested extreme — the EeiServer guard-embedding convention — so the
downstream windowed stages can never select them.

Shift-and-invert mode runs the same iteration on ``B = (A - sigma I)^{-1}``
via one LU factorization (the same batched ``lu_factor``/``lu_solve``
program shape sign recovery uses): clustered extremal spectra that direct
Lanczos separates slowly spread out as ``theta = 1/(lambda - sigma)``, and
the recover chain maps Ritz values back with ``lambda = sigma + 1/theta``
(see the ``shift_invert_map`` stage in ``engine/engine.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import identity
from repro.linalg import sturm

#: Krylov band sizing for a k-window: ``m = min(n, max(FACTOR * k, MIN))``.
#: Measured on the reference container (GOE f32): the top-k=16 window at
#: n = 4096 needs m ~ 16k for a ~1e-3-relative spectrum (m = 128 leaves
#: ~2e-2); k = 4 converges by m = 128.  ``SolverPlan.krylov_m`` overrides.
KRYLOV_M_FACTOR = 16
KRYLOV_M_MIN = 128

#: Shift-and-invert band sizing: the inverted operator separates the target
#: cluster, so far fewer steps are needed per converged pair.
KRYLOV_SI_M_FACTOR = 8
KRYLOV_SI_M_MIN = 64

#: Shift margin for shift-and-invert, as a fraction of the Gershgorin span:
#: sigma sits this far outside the spectrum on the requested side (small, so
#: ``theta = 1/(lambda - sigma)`` strongly amplifies the extremal cluster).
SI_MARGIN_FRAC = 1e-3


def default_m(n: int, k: int) -> int:
    """Default Krylov band size for a direct top-k window at size ``n``."""
    return min(n, max(KRYLOV_M_FACTOR * k, KRYLOV_M_MIN))


def default_si_m(n: int, k: int) -> int:
    """Default band size for the shift-and-invert mode."""
    return min(n, max(KRYLOV_SI_M_FACTOR * k, KRYLOV_SI_M_MIN))


def _resolve_m(n: int, k: int, m: int, si: bool = False) -> int:
    if m:
        return min(n, max(int(m), k))
    return default_si_m(n, k) if si else default_m(n, k)


def _default_rtol(dtype) -> float:
    return 1e-12 if jnp.dtype(dtype) == jnp.float64 else 1e-5


class LanczosResult(NamedTuple):
    """One partial tridiagonalization, guard-masked and engine-oriented."""

    d: jax.Array  # (m,) band diagonal; guard value beyond `steps`
    e: jax.Array  # (m-1,) band off-diagonal; 0 beyond the active block
    q: jax.Array  # (n, m) columns are the Lanczos basis; 0 beyond `steps`
    steps: jax.Array  # () int32 — Lanczos steps actually taken
    resid: jax.Array  # (k,) last windowed Ritz residual bound (relative)


def _band_bounds(d: jax.Array, e_band: jax.Array, active: jax.Array):
    """Gershgorin ``(lo, hi)`` of the *active* rows of a masked band."""
    m = d.shape[0]
    rad = jnp.zeros((m,), d.dtype)
    if m > 1:
        rad = rad.at[:-1].add(jnp.abs(e_band))
        rad = rad.at[1:].add(jnp.abs(e_band))
    lo = jnp.min(jnp.where(active, d - rad, jnp.inf))
    hi = jnp.max(jnp.where(active, d + rad, -jnp.inf))
    return lo, hi


def _guard_value(d, e_band, active, largest: bool):
    """Guard for inactive band slots: strictly outside the active block's
    spectrum, on the side away from the requested extreme (the serving
    runtime's guard-diagonal convention)."""
    lo, hi = _band_bounds(d, e_band, active)
    floor = jnp.asarray(jnp.finfo(d.dtype).tiny, d.dtype) ** 0.5
    margin = 0.01 * (hi - lo) + 1e-3 * (jnp.abs(hi) + jnp.abs(lo)) + floor
    return lo - margin if largest else hi + margin


def _mask_band(d, e, j, m, largest: bool):
    """Guard-fill band entries beyond ``j`` active steps; returns the
    ``(m,)`` diagonal and ``(m-1,)`` off-diagonal the spectrum stage sees."""
    idx = jnp.arange(m)
    e_band = (jnp.where(idx[: m - 1] < j - 1, e[: m - 1], 0.0)
              if m > 1 else e[:0])
    active = idx < j
    guard = _guard_value(d, e_band, active, largest)
    return jnp.where(active, d, guard), e_band


def lanczos_iterate(
    a: jax.Array,
    m: int,
    *,
    window: Optional[Tuple[int, bool]] = None,
    matvec=None,
    rtol: float = 0.0,
    check_every: int = 32,
    seed: int = 0,
):
    """Raw m-step Lanczos loop on one matrix (or abstract ``matvec``).

    Returns ``(d (m,), e (m,), Q (m+1, n) rows, steps, resid)`` — the
    unmasked internals; :func:`lanczos_partial` is the masked public form.
    ``window=(k, largest)`` enables the windowed Ritz residual stop.
    """
    n = a.shape[-1]
    dtype = a.dtype
    mv = matvec if matvec is not None else (lambda v: a @ v)
    if not 1 <= m <= n:
        raise ValueError(f"Krylov band m={m} out of range for n={n}")
    if window is not None and not 1 <= window[0] <= m:
        raise ValueError(f"window k={window[0]} out of range for m={m}")
    rtol = float(rtol) if rtol else _default_rtol(dtype)
    eps = jnp.asarray(jnp.finfo(dtype).eps, dtype)
    floor = jnp.asarray(jnp.finfo(dtype).tiny, dtype) ** 0.5

    key = jax.random.PRNGKey(seed)
    v0 = jax.random.normal(key, (n,), dtype)
    v0 = v0 / jnp.linalg.norm(v0)
    k_win = window[0] if window is not None else 1

    def ritz_resid(d, e, j1, beta):
        """Relative Ritz residual bound for the k windowed pairs of the
        current masked band: ``beta_j |s_i[j-1]| / scale``."""
        k, largest = window
        d_m, e_m = _mask_band(d, e, j1, m, largest)
        theta = sturm.bisect_eigenvalues_windowed(d_m, e_m, k, largest)
        mags = identity.tridiag_windowed_magnitudes(d_m, e_m, theta)
        s_last = jnp.sqrt(jnp.maximum(mags[:, j1 - 1], 0.0))
        lo, hi = _band_bounds(d_m, e_m, jnp.arange(m) < j1)
        scale = jnp.maximum(jnp.maximum(jnp.abs(lo), jnp.abs(hi)), floor)
        return beta * s_last / scale

    def body(carry):
        Q, d, e, j, resid, done = carry
        qj = Q[j]
        w = mv(qj)
        alpha = jnp.dot(qj, w)
        w = w - alpha * qj
        # Full reorthogonalization, CGS2: rows of Q beyond the basis are
        # exactly zero, so no masking is needed in the projections.
        w = w - Q.T @ (Q @ w)
        w = w - Q.T @ (Q @ w)
        beta = jnp.linalg.norm(w)
        d = d.at[j].set(alpha)
        scale = jnp.maximum(jnp.max(jnp.abs(d)), jnp.max(jnp.abs(e)))
        breakdown = beta <= jnp.maximum(100.0 * eps * scale, floor)

        def restart(_):
            # Invariant subspace captured: continue in a fresh direction
            # orthogonal to the basis (one projection pass suffices for a
            # random vector), through an exactly-zero band junction.
            r = jax.random.normal(jax.random.fold_in(key, j + 1), (n,), dtype)
            r = r - Q.T @ (Q @ r)
            rn = jnp.linalg.norm(r)
            return jnp.where(rn > floor, r / jnp.maximum(rn, floor), 0.0)

        qn = jax.lax.cond(
            breakdown, restart,
            lambda _: w / jnp.maximum(beta, floor), None)
        e = e.at[j].set(jnp.where(breakdown, 0.0, beta))
        Q = Q.at[j + 1].set(qn)
        j1 = j + 1
        if window is not None:
            do_check = (j1 % check_every == 0) & (j1 >= k_win + 1)
            resid = jax.lax.cond(
                do_check,
                lambda _: ritz_resid(d, e, j1, beta),
                lambda _: resid, None)
            done = jnp.all(resid <= rtol)
        return Q, d, e, j1, resid, done

    def cond(carry):
        _, _, _, j, _, done = carry
        return (j < m) & (~done)

    carry0 = (
        jnp.zeros((m + 1, n), dtype).at[0].set(v0),
        jnp.zeros((m,), dtype),
        jnp.zeros((m,), dtype),
        jnp.asarray(0, jnp.int32),
        jnp.full((k_win,), jnp.inf, dtype),
        jnp.asarray(False),
    )
    Q, d, e, j, resid, _ = jax.lax.while_loop(cond, body, carry0)
    return d, e, Q, j, resid


def lanczos_partial(
    a: jax.Array,
    m: int,
    k: int,
    largest: bool = True,
    *,
    matvec=None,
    rtol: float = 0.0,
    check_every: int = 32,
    seed: int = 0,
) -> LanczosResult:
    """Guard-masked m-step Lanczos band + basis for a ``(k, largest)`` window.

    ``d (m,)`` / ``e (m-1,)`` carry the active block with inactive slots
    guard-filled away from the window; ``q (n, m)`` columns are the basis
    (zero beyond ``steps``).  The triple plugs directly into the windowed
    spectrum/components/recover stages.
    """
    d, e, Q, j, resid = lanczos_iterate(
        a, m, window=(k, largest), matvec=matvec, rtol=rtol,
        check_every=check_every, seed=seed)
    d_m, e_m = _mask_band(d, e, j, m, largest)
    # Row `steps` of Q was written by the last body step but is outside the
    # retained basis — zero everything beyond the active block.
    q = jnp.where(jnp.arange(m)[:, None] < j, Q[:m], 0.0)
    return LanczosResult(d_m, e_m, jnp.swapaxes(q, -1, -2), j, resid)


# ---------------------------------------------------------------------------
# Engine stage entry points (batched)
# ---------------------------------------------------------------------------


def krylov_reduce(a: jax.Array, k: int, largest: bool = True, m: int = 0,
                  rtol: float = 0.0):
    """Single-matrix krylov reduce stage: ``(d, e, q, steps)`` for a top-k
    window; ``steps`` is the number of Lanczos steps the loop took."""
    n = a.shape[-1]
    mm = _resolve_m(n, k, m)
    res = lanczos_partial(a, mm, min(k, mm), largest, rtol=rtol)
    return res.d, res.e, res.q, res.steps


@functools.partial(jax.jit, static_argnames=("k", "largest", "m", "rtol"))
def krylov_reduce_batched(a: jax.Array, k: int, largest: bool = True,
                          m: int = 0, rtol: float = 0.0):
    """Leading-axis batched :func:`krylov_reduce`."""
    from repro.linalg.batching import vmap_leading

    fn = lambda aa: krylov_reduce(aa, k, largest, m, rtol)
    return vmap_leading(fn, a.ndim - 2)(a)


def shift_invert_sigma(a: jax.Array, largest: bool = True):
    """Gershgorin shift strictly outside the spectrum on the target side."""
    radius = jnp.sum(jnp.abs(a), axis=-1) - jnp.abs(jnp.diagonal(a))
    diag = jnp.diagonal(a)
    lo = jnp.min(diag - radius)
    hi = jnp.max(diag + radius)
    floor = jnp.asarray(jnp.finfo(a.dtype).tiny, a.dtype) ** 0.5
    margin = SI_MARGIN_FRAC * (hi - lo) + 1e-6 * (
        jnp.abs(hi) + jnp.abs(lo)) + floor
    return hi + margin if largest else lo - margin


def krylov_shift_invert_reduce(a: jax.Array, k: int, largest: bool = True,
                               m: int = 0, rtol: float = 0.0):
    """Shift-and-invert krylov reduce: ``(d, e, q, sigma, steps)`` in
    theta-space.

    Lanczos runs on ``B = (A - sigma I)^{-1}`` through one LU
    factorization; the band's Ritz values are ``theta = 1/(lambda - sigma)``
    and the *opposite* extreme of theta corresponds to the requested extreme
    of lambda (the ``shift_invert_map`` recover stage undoes both).
    """
    n = a.shape[-1]
    mm = _resolve_m(n, k, m, si=True)
    sigma = shift_invert_sigma(a, largest)
    lu, piv = jax.scipy.linalg.lu_factor(
        a - sigma * jnp.eye(n, dtype=a.dtype))
    mv = lambda v: jax.scipy.linalg.lu_solve((lu, piv), v)
    res = lanczos_partial(a, mm, min(k, mm), not largest, matvec=mv,
                          rtol=rtol)
    return res.d, res.e, res.q, sigma, res.steps


@functools.partial(jax.jit, static_argnames=("k", "largest", "m", "rtol"))
def krylov_shift_invert_reduce_batched(a: jax.Array, k: int,
                                       largest: bool = True, m: int = 0,
                                       rtol: float = 0.0):
    """Leading-axis batched :func:`krylov_shift_invert_reduce`."""
    from repro.linalg.batching import vmap_leading

    fn = lambda aa: krylov_shift_invert_reduce(aa, k, largest, m, rtol)
    return vmap_leading(fn, a.ndim - 2)(a)
