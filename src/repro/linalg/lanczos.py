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
  ``|A y - theta y| = beta_j |s_j[last]|`` evaluated; a matrix stops when
  every windowed pair meets ``rtol`` (relative to the band's spectral
  scale) — or at the ``m`` cap.  The loop runs natively on a stack
  ``(b, n, n)`` with its control shared by the stack: one unbatched step
  counter, so the check is a real ``cond`` that runs only at its check
  steps, and a per-matrix stop flag that freezes a converged member's
  carry until every member has stopped.  The loop is not ``vmap``-ped:
  vmap batches a while loop's predicate when the stop flag differs per
  matrix, which turns every ``cond`` in the body into a ``select`` that
  runs the check (a bisection and two recurrences) at every step.
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

import math
from typing import NamedTuple, Tuple

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
    """One partial tridiagonalization, guard-masked and engine-oriented.

    Shapes are per matrix; a stack's leading axes come first."""

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
    window: Tuple[int, bool],
    *,
    matvec=None,
    rtol: float = 0.0,
    check_every: int = 32,
    seed: int = 0,
):
    """Raw m-step Lanczos loop on a stack ``a (b, n, n)`` (or an abstract
    ``matvec`` mapping the stack's vectors ``(b, n)`` to ``(b, n)``).

    Returns ``(d (b, m), e (b, m), Q (b, m+1, n) rows, steps (b,),
    resid (b, k))`` — the unmasked internals; :func:`lanczos_partial` is
    the masked public form.  ``window=(k, largest)`` drives the windowed
    Ritz residual stop.

    The loop's control is shared by the stack (see the module docstring for
    why it is not vmapped): one unbatched step counter ``j``, so the Ritz
    check's ``cond`` stays a real conditional.  A member that has met
    ``rtol`` keeps its carry frozen and its ``steps`` at the step it
    stopped; the loop ends when every member has stopped or at the ``m``
    cap.
    """
    b, n = a.shape[0], a.shape[-1]
    dtype = a.dtype
    mv = matvec if matvec is not None else (
        lambda v: jax.vmap(jnp.matmul)(a, v))
    k_win, largest = window
    if not 1 <= m <= n:
        raise ValueError(f"Krylov band m={m} out of range for n={n}")
    if not 1 <= k_win <= m:
        raise ValueError(f"window k={k_win} out of range for m={m}")
    rtol = float(rtol) if rtol else _default_rtol(dtype)
    eps = jnp.asarray(jnp.finfo(dtype).eps, dtype)
    floor = jnp.asarray(jnp.finfo(dtype).tiny, dtype) ** 0.5

    key = jax.random.PRNGKey(seed)
    v0 = jax.random.normal(key, (n,), dtype)
    v0 = v0 / jnp.linalg.norm(v0)

    def ritz_resid(d, e, j1, beta):
        """Relative Ritz residual bound for the k windowed pairs of each
        member's current masked band: ``beta_j |s_i[j-1]| / scale``."""
        d_m, e_m = jax.vmap(
            lambda dd, ee: _mask_band(dd, ee, j1, m, largest))(d, e)
        theta = sturm.bisect_eigenvalues_windowed_batched(
            d_m, e_m, k_win, largest)
        mags = identity.tridiag_windowed_magnitudes_batched(d_m, e_m, theta)
        s_last = jnp.sqrt(jnp.maximum(mags[..., j1 - 1], 0.0))
        lo, hi = jax.vmap(
            lambda dd, ee: _band_bounds(dd, ee, jnp.arange(m) < j1))(
                d_m, e_m)
        scale = jnp.maximum(jnp.maximum(jnp.abs(lo), jnp.abs(hi)), floor)
        return beta[:, None] * s_last / scale[:, None]

    def orthogonalize(Q, qj, w):
        """One member's three-term step and full reorthogonalization."""
        alpha = jnp.dot(qj, w)
        w = w - alpha * qj
        # Full reorthogonalization, CGS2: rows of Q beyond the basis are
        # exactly zero, so no masking is needed in the projections.
        w = w - Q.T @ (Q @ w)
        w = w - Q.T @ (Q @ w)
        return alpha, w, jnp.linalg.norm(w)

    def body(carry):
        Q, d, e, j, steps, resid, done = carry
        live = ~done
        qj = Q[:, j]
        alpha, w, beta = jax.vmap(orthogonalize)(Q, qj, mv(qj))
        d_j = jnp.where(live, alpha, d[:, j])
        d = d.at[:, j].set(d_j)
        scale = jnp.maximum(jnp.max(jnp.abs(d), axis=-1),
                            jnp.max(jnp.abs(e), axis=-1))
        breakdown = beta <= jnp.maximum(100.0 * eps * scale, floor)

        def restart(qn):
            # Invariant subspace captured: continue in a fresh direction
            # orthogonal to the basis (one projection pass suffices for a
            # random vector), through an exactly-zero band junction.
            r = jax.random.normal(jax.random.fold_in(key, j + 1), (n,), dtype)
            r = jnp.broadcast_to(r, (b, n))  # projected member by member
            r = r - jax.vmap(lambda Qb, rb: Qb.T @ (Qb @ rb))(Q, r)
            rn = jnp.linalg.norm(r, axis=-1, keepdims=True)
            fresh = jnp.where(rn > floor, r / jnp.maximum(rn, floor), 0.0)
            return jnp.where(breakdown[:, None], fresh, qn)

        qn = jax.lax.cond(
            jnp.any(breakdown & live), restart, lambda qn: qn,
            w / jnp.maximum(beta, floor)[:, None])
        e_j = jnp.where(live, jnp.where(breakdown, 0.0, beta), e[:, j])
        e = e.at[:, j].set(e_j)
        Q = Q.at[:, j + 1].set(jnp.where(live[:, None], qn, Q[:, j + 1]))
        j1 = j + 1
        do_check = (j1 % check_every == 0) & (j1 >= k_win + 1)
        resid = jax.lax.cond(
            do_check,
            lambda r: jnp.where(live[:, None], ritz_resid(d, e, j1, beta), r),
            lambda r: r, resid)
        steps = jnp.where(live, j1, steps)
        done = done | jnp.all(resid <= rtol, axis=-1)
        return Q, d, e, j1, steps, resid, done

    def cond(carry):
        j, done = carry[3], carry[-1]
        return (j < m) & ~jnp.all(done)

    carry0 = (
        jnp.zeros((b, m + 1, n), dtype).at[:, 0].set(v0),
        jnp.zeros((b, m), dtype),
        jnp.zeros((b, m), dtype),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.full((b, k_win), jnp.inf, dtype),
        jnp.zeros((b,), bool),
    )
    Q, d, e, _, steps, resid, _ = jax.lax.while_loop(cond, body, carry0)
    return d, e, Q, steps, resid


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

    ``a`` is one matrix ``(n, n)`` or a stack ``(..., n, n)``; the leading
    axes are flattened into the one batch axis of :func:`lanczos_iterate`
    (a single matrix is the ``b = 1`` stack), and ``matvec``, if given,
    maps that flattened stack's vectors ``(b, n)`` to ``(b, n)``.  Per
    matrix, ``d (m,)`` / ``e (m-1,)`` carry the active block with inactive
    slots guard-filled away from the window; ``q (n, m)`` columns are the
    basis (zero beyond ``steps``).  The triple plugs directly into the
    windowed spectrum/components/recover stages.
    """
    lead, n = a.shape[:-2], a.shape[-1]
    d, e, Q, steps, resid = lanczos_iterate(
        a.reshape((math.prod(lead), n, n)), m, (k, largest), matvec=matvec,
        rtol=rtol, check_every=check_every, seed=seed)
    d_m, e_m = jax.vmap(
        lambda dd, ee, s: _mask_band(dd, ee, s, m, largest))(d, e, steps)
    # Row `steps` of Q was written by the last body step but is outside the
    # retained basis — zero everything beyond the active block.
    q = jnp.where(jnp.arange(m)[:, None] < steps[:, None, None], Q[:, :m], 0.0)
    res = LanczosResult(d_m, e_m, jnp.swapaxes(q, -1, -2), steps, resid)
    return LanczosResult(*(x.reshape(lead + x.shape[1:]) for x in res))


# ---------------------------------------------------------------------------
# Engine stage entry points (batched)
# ---------------------------------------------------------------------------


def krylov_reduce(a: jax.Array, k: int, largest: bool = True, m: int = 0,
                  rtol: float = 0.0):
    """Krylov reduce stage: ``(d, e, q, steps)`` for a top-k window of a
    matrix ``(n, n)`` or a stack ``(..., n, n)``; ``steps`` is the number
    of Lanczos steps each matrix took."""
    n = a.shape[-1]
    mm = _resolve_m(n, k, m)
    res = lanczos_partial(a, mm, min(k, mm), largest, rtol=rtol)
    return res.d, res.e, res.q, res.steps


#: Jitted :func:`krylov_reduce` — the engine's entry for a stack.
krylov_reduce_batched = jax.jit(
    krylov_reduce, static_argnames=("k", "largest", "m", "rtol"))


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
    theta-space, for a matrix ``(n, n)`` or a stack ``(..., n, n)``.

    Lanczos runs on ``B = (A - sigma I)^{-1}`` through one LU
    factorization per matrix; the band's Ritz values are
    ``theta = 1/(lambda - sigma)`` and the *opposite* extreme of theta
    corresponds to the requested extreme of lambda (the
    ``shift_invert_map`` recover stage undoes both).
    """
    lead, n = a.shape[:-2], a.shape[-1]
    mm = _resolve_m(n, k, m, si=True)
    a3 = a.reshape((math.prod(lead), n, n))
    sigma = jax.vmap(lambda x: shift_invert_sigma(x, largest))(a3)
    lu, piv = jax.scipy.linalg.lu_factor(
        a3 - sigma[:, None, None] * jnp.eye(n, dtype=a.dtype))
    solve = jax.vmap(
        lambda lu_b, piv_b, v: jax.scipy.linalg.lu_solve((lu_b, piv_b), v))
    res = lanczos_partial(a, mm, min(k, mm), not largest,
                          matvec=lambda v: solve(lu, piv, v), rtol=rtol)
    return res.d, res.e, res.q, sigma.reshape(lead), res.steps


#: Jitted :func:`krylov_shift_invert_reduce` — the engine's entry for a
#: stack.
krylov_shift_invert_reduce_batched = jax.jit(
    krylov_shift_invert_reduce, static_argnames=("k", "largest", "m", "rtol"))
