"""SpectralSession — stateful streaming maintenance of a top-k window.

Production traffic is rarely i.i.d. fresh matrices: a covariance / Gram
matrix drifts by rank-1 data updates (``A <- A + sign * u u^T``) and the
caller wants the *same* top-k window back after every step.  Re-solving
from scratch pays O(n^3) (or an m-step Lanczos) per update; this module
pays O(m' n^2) with ``m' ~ k + buffer + ext << n`` by maintaining a session:

* the current matrix ``a`` (device-resident),
* a retained Ritz window: ``basis (m_keep, n)`` / ``theta (m_keep,)`` —
  the ``m_keep = k + buffer`` extremal eigenpairs from the last solve,
* a **drift monitor**: accumulated ``|rho| / ||A||_F`` since the last full
  solve, an update-count cadence cap, and the verify flags of every fast
  update (``engine/verify.py`` runs inside the update program).

The fast path is the engine's ``update`` program kind (see
``backends._UPDATE_CHAIN``): project the updated matrix onto the retained
basis augmented with the update direction + a few Lanczos extensions,
tridiagonalize the small compression, bisect its spectrum from
interlacing/secular warm brackets, and recover vectors through the shared
minor-determinant + sign-recurrence stages.  Any of the monitor's three
triggers — drift past ``drift_bound``, a failed verify, ``max_updates``
updates since the last solve — forces a **full re-solve** through
``engine.topk`` that rebuilds the retained window from scratch.  The fast
path can therefore never silently return stale eigenpairs: every answer is
either residual-verified against the updated matrix or freshly solved.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.verify import verify_topk_host


class SessionVerifyError(RuntimeError):
    """A session's *full re-solve* failed residual verification — the
    matrix itself is pathological (non-finite / non-symmetric drift), not
    just the warm start.  The serving layer maps this onto its fallback
    chain; direct engine callers see the error."""


class Rank1Update(NamedTuple):
    """One symmetric rank-1 perturbation ``A <- A + sign * u u^T``."""

    u: np.ndarray
    sign: int = 1


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    """Tuning knobs of a session (all static per session).

    ``buffer``       extra Ritz pairs retained beyond ``k`` — the guard
                     band that lets eigenvalues rotate into the window
                     between full solves.
    ``ext``          Lanczos extension directions appended to the basis
                     per update (beyond the update direction itself).
    ``drift_bound``  accumulated ``sum |rho_i| / ||A||_F`` since the last
                     full solve that forces a re-solve.
    ``max_updates``  fast updates allowed between full solves (cadence
                     cap — bounds worst-case staleness even when drift
                     and verify stay green).
    ``verify``       host-check the update program's verify flags every
                     fast update (on by default; the drift monitor's
                     residual leg).
    """

    buffer: int = 4
    ext: int = 3
    drift_bound: float = 0.25
    max_updates: int = 128
    verify: bool = True

    def __post_init__(self):
        if self.buffer < 0:
            raise ValueError(f"buffer must be >= 0, got {self.buffer}")
        if self.ext < 0:
            raise ValueError(f"ext must be >= 0, got {self.ext}")
        if self.drift_bound <= 0:
            raise ValueError(
                f"drift_bound must be > 0, got {self.drift_bound}")
        if self.max_updates < 1:
            raise ValueError(
                f"max_updates must be >= 1, got {self.max_updates}")


class SpectralSession:
    """Mutable session state; create via ``SolverEngine.open_session``.

    All heavy state (matrix, basis) stays device-resident between updates.
    Not thread-safe — the serving layer serializes per-session access.
    """

    def __init__(self, k: int, largest: bool, config: SessionConfig,
                 n: int, m_keep: int, n_aug: int, dtype):
        self.k = k
        self.largest = largest
        self.config = config
        self.n = n
        self.m_keep = m_keep
        self.n_aug = n_aug
        self.dtype = dtype
        # Device state, refreshed by every update / re-solve.
        self.a: Optional[jax.Array] = None
        self.basis: Optional[jax.Array] = None  # (m_keep, n)
        self.theta: Optional[jax.Array] = None  # (m_keep,)
        self.lam: Optional[jax.Array] = None  # (k,)
        self.vecs: Optional[jax.Array] = None  # (k, n)
        # Drift monitor.
        self.scale = 0.0  # ||A||_F at the last full solve
        self.drift = 0.0  # sum |rho| / scale since the last full solve
        self.updates_since_resolve = 0
        # Counters.
        self.updates_total = 0
        self.fast_updates = 0
        self.full_resolves = 0
        self.resolves_by_cause: dict = {}
        # Windows rebuilt by host LAPACK instead of a device solve (a
        # re-solve that failed verification, or the server's degrade rung).
        self.host_reseeds = 0

    def result(self):
        """The current top-k window as a ``TopkResult``."""
        from repro.engine.engine import TopkResult

        return TopkResult(self.lam, self.vecs)

    def stats(self) -> dict:
        return {
            "k": self.k, "n": self.n, "m_keep": self.m_keep,
            "updates_total": self.updates_total,
            "fast_updates": self.fast_updates,
            "full_resolves": self.full_resolves,
            "resolves_by_cause": dict(self.resolves_by_cause),
            "host_reseeds": self.host_reseeds,
            "drift": self.drift,
            "updates_since_resolve": self.updates_since_resolve,
        }


def _plan_dtype(plan):
    if plan.precision is not None:
        return jnp.dtype({"float32": jnp.float32,
                          "float64": jnp.float64}[plan.precision])
    return jnp.dtype(jnp.float32)


def open_session(engine, a, k: int, largest: bool = True,
                 config: Optional[SessionConfig] = None) -> SpectralSession:
    """Seed a session with a full solve of the ``m_keep`` retained window."""
    cfg = config if config is not None else SessionConfig()
    dtype = _plan_dtype(engine.plan)
    a = jnp.asarray(a, dtype)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"expected one (n, n) matrix, got {a.shape}")
    n = a.shape[0]
    if not 1 <= k <= n:
        raise ValueError(f"k={k} out of range for n={n}")
    m_keep = min(n, k + cfg.buffer)
    # Augmentation directions: u plus `ext` Lanczos extensions, clipped so
    # the augmented frame never exceeds n (m_keep == n means the retained
    # basis is already the whole space and the update is exact).
    n_aug = min(n - m_keep, 1 + cfg.ext)
    session = SpectralSession(int(k), bool(largest), cfg, n, m_keep, n_aug,
                              dtype)
    _full_resolve(engine, session, a, cause="open")
    return session


def _slice_window(session, lam_m, vecs_m):
    k = session.k
    if session.largest:
        return lam_m[..., -k:], vecs_m[..., -k:, :]
    return lam_m[..., :k], vecs_m[..., :k, :]


def _host_eigh_window(session, a_new):
    """Last-rung exact solve: float64 LAPACK eigh on the host."""
    from repro.engine.engine import TopkResult

    lam, v = np.linalg.eigh(np.asarray(a_new, np.float64))
    m = session.m_keep
    if session.largest:
        lam, v = lam[-m:], v[:, -m:]
    else:
        lam, v = lam[:m], v[:, :m]
    return TopkResult(jnp.asarray(lam, session.dtype),
                      jnp.asarray(v.T, session.dtype))


def _commit_resolve(session, a_new, res, cause: str) -> None:
    """Install a fresh full-solve window and reset the drift monitor."""
    session.a = jnp.asarray(a_new, session.dtype)
    session.basis = res.vectors
    session.theta = res.eigenvalues
    session.lam, session.vecs = _slice_window(
        session, res.eigenvalues, res.vectors)
    session.scale = float(np.linalg.norm(np.asarray(a_new)))
    session.drift = 0.0
    session.updates_since_resolve = 0
    if cause != "open":
        session.full_resolves += 1
        session.resolves_by_cause[cause] = \
            session.resolves_by_cause.get(cause, 0) + 1


def host_reseed(session, a_new, cause: str = "degrade") -> None:
    """Rebuild the session entirely on the host (float64 LAPACK eigh).

    The serving layer's terminal degrade rung: no engine, no XLA, no
    compile — usable even when the fast path's whole backend is broken.
    Raises :class:`SessionVerifyError` only when LAPACK itself cannot
    produce a verifiable window (a genuinely pathological matrix).
    """
    res = _host_eigh_window(session, a_new)
    flags = verify_topk_host(
        np.asarray(a_new), np.asarray(res.eigenvalues),
        np.asarray(res.vectors))
    if not bool(np.all(flags.ok)):
        raise SessionVerifyError(
            f"session host re-solve (cause={cause!r}) failed residual "
            "verification; the session matrix is pathological")
    _commit_resolve(session, a_new, res, cause)
    session.host_reseeds += 1


def _full_resolve(engine, session, a_new, cause: str) -> None:
    """Rebuild the retained window from scratch and reset the monitor."""
    res = engine.topk(a_new, session.m_keep, session.largest)
    if session.config.verify:
        flags = verify_topk_host(
            np.asarray(a_new), np.asarray(res.eigenvalues),
            np.asarray(res.vectors))
        if not bool(np.all(flags.ok)):
            # The plan's method missed tolerance on this matrix (e.g. a
            # dominant spike at float32) — escalate to host eigh rather
            # than surface a method artifact as a session failure.
            host_reseed(session, a_new, cause)
            return
    _commit_resolve(session, a_new, res, cause)


def _pad_batch(engine, x):
    """Lift session state to the program's batch shape (mesh-divisible)."""
    mult = engine.plan.batch_axis_size
    x = x[None]
    if mult > 1:
        x = jnp.broadcast_to(x, (mult,) + x.shape[1:])
    return x


def _normalize_deltas(delta):
    if isinstance(delta, Rank1Update):
        return [delta]
    if isinstance(delta, tuple) and len(delta) == 2 and \
            np.ndim(delta[1]) == 0:
        return [Rank1Update(delta[0], int(delta[1]))]
    if isinstance(delta, (list,)) or (
            isinstance(delta, Sequence) and not hasattr(delta, "shape")):
        out = []
        for item in delta:
            out.extend(_normalize_deltas(item))
        return out
    return [Rank1Update(delta, 1)]


def apply_update(engine, session: SpectralSession,
                 delta: Union[Rank1Update, tuple, Sequence, np.ndarray]):
    """Apply rank-1 update(s) to a session; returns the refreshed window.

    Rank-r updates decompose into r sequential rank-1 applications — each
    one re-verified, so a large compound update degrades to full re-solves
    exactly like a large single one.
    """
    if session.a is None:
        raise ValueError("session is not seeded; use engine.open_session")
    for upd in _normalize_deltas(delta):
        _apply_rank1(engine, session, upd)
    return session.result()


def _apply_rank1(engine, session, upd: Rank1Update) -> None:
    from repro.engine.engine import update_program

    cfg = session.config
    sign = int(upd.sign)
    if sign not in (-1, 1):
        raise ValueError(f"sign must be +1 or -1, got {upd.sign}")
    u = jnp.asarray(upd.u, session.dtype)
    if u.shape != (session.n,):
        raise ValueError(
            f"expected update vector of shape ({session.n},), got {u.shape}")
    nrm2 = float(jnp.vdot(u, u))
    if not np.isfinite(nrm2):
        raise ValueError("update vector is not finite")
    session.updates_total += 1
    if nrm2 == 0.0:
        return  # A + 0 = A: nothing to do, nothing drifts
    rho = sign * nrm2
    new_drift = session.drift + abs(rho) / max(session.scale, 1e-30)

    # Drift monitor, legs 1+2: accumulated movement bound and cadence cap.
    if new_drift > cfg.drift_bound or \
            session.updates_since_resolve + 1 > cfg.max_updates:
        cause = "drift" if new_drift > cfg.drift_bound else "cadence"
        a_new = session.a + (sign * u)[:, None] * u[None, :]
        _full_resolve(engine, session, a_new, cause=cause)
        return

    # Fast path: the warm-started update program.
    program = update_program(
        engine.plan, session.k, session.largest, session.m_keep,
        session.n_aug)
    u_hat = u / jnp.sqrt(nrm2)
    operands = [session.a, session.basis, session.theta, u_hat,
                jnp.asarray(rho, session.dtype)]
    padded = [_pad_batch(engine, x) for x in operands[:4]]
    rho_b = jnp.broadcast_to(
        operands[4][None], (padded[0].shape[0],))
    result, flags, a_new, basis, theta = program(*padded, rho_b)
    take = lambda t: jax.tree.map(lambda x: x[0], t)
    result, flags, a_new, basis, theta = (
        take(result), take(flags), take(a_new), take(basis), take(theta))

    # Drift monitor, leg 3: residual verification of the fast answer.
    if cfg.verify and not bool(np.asarray(flags.ok)):
        _full_resolve(engine, session, a_new, cause="verify")
        return

    session.a = a_new
    session.basis = basis
    session.theta = theta
    session.lam, session.vecs = result.eigenvalues, result.vectors
    session.drift = new_drift
    session.updates_since_resolve += 1
    session.fast_updates += 1
