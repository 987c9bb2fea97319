"""SpectralSession: the streaming rank-1 update path against the eigh
oracle, the drift monitor's three triggers, and stateful serving sessions.

The session contract: every window a session hands back is either
residual-verified against the *updated* matrix or freshly re-solved —
the warm path can never silently return stale eigenpairs.  The property
suite drives random rank-1 perturbation streams through all four
backends and checks eigh-oracle conformance after every step, including
the adversarial case where the perturbation pushes an out-of-window
eigenvalue across the window boundary (an eigenvalue-ordering swap the
warm brackets cannot track without the monitor).

Serving coverage rides along: per-session sticky execution in
``EeiServer`` (both threaded and caller-driven pumps), degrade-to-host
when the fast path's backend is broken, fleet stickiness + failover
reopen, and the adaptive-linger regression test (a hot coalesce key must
stop waiting out the full linger timeout).
"""

import threading
import time

import jax
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.engine import (
    DegradedResult,
    EeiFleet,
    EeiServer,
    ProgramCache,
    Rank1Update,
    ServerClosed,
    SessionConfig,
    SolverEngine,
    SolverPlan,
    verify_topk_host,
)

PLAN = SolverPlan(method="eei_tridiag", backend="jnp")
BACKENDS = ["reference", "jnp", "pallas", "sharded"]

#: One cache across the module (mirrors test_server): serving tests reuse
#: compiled programs instead of recompiling per test.
SHARED_CACHE = ProgramCache()


def _plan(backend: str) -> SolverPlan:
    mesh = jax.make_mesh((1, 1), ("data", "model")) \
        if backend == "sharded" else None
    return SolverPlan(method="eei_tridiag", backend=backend, mesh=mesh)


def _sym(rng, n: int) -> np.ndarray:
    a = rng.standard_normal((n, n))
    return (a + a.T) / 2


def _oracle_window(a: np.ndarray, k: int, largest: bool = True):
    lam = np.linalg.eigvalsh(np.asarray(a, np.float64))
    return lam[-k:] if largest else lam[:k]


def _assert_conformant(a: np.ndarray, res, k: int, largest: bool = True,
                       rtol: float = 5e-3) -> None:
    """The session's window must match the float64 eigh oracle on the
    accumulated matrix: eigenvalues to ``rtol`` of the spectral scale,
    eigenvectors through the residual check (sign/degeneracy safe)."""
    lam = np.asarray(res.eigenvalues, np.float64)
    vec = np.asarray(res.vectors, np.float64)
    ref = _oracle_window(a, k, largest)
    scale = max(np.linalg.norm(a), 1e-30)
    np.testing.assert_allclose(lam, ref, atol=rtol * scale, rtol=0)
    flags = verify_topk_host(np.asarray(a), lam, vec)
    assert bool(np.all(flags.ok)), \
        f"window failed residual verification: {flags}"


# ---------------------------------------------------------------------------
# Engine-level update path: oracle conformance on all four backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_update_stream_matches_eigh_oracle(backend, rng):
    """A stream of random rank-1 updates tracks the eigh oracle at every
    step, on every backend, mixing warm-path and monitor-forced solves."""
    n, k = 16, 3
    engine = SolverEngine(_plan(backend))
    a = _sym(rng, n)
    session = engine.open_session(a, k)
    _assert_conformant(a, session.result(), k)
    for step in range(6):
        u = rng.standard_normal(n) * (0.3 if step % 2 else 1.5)
        sign = -1 if step == 4 else 1
        a = a + sign * np.outer(u, u)
        res = engine.update(session, Rank1Update(u, sign))
        _assert_conformant(a, res, k)
    stats = session.stats()
    assert stats["updates_total"] == 6
    assert stats["fast_updates"] + stats["full_resolves"] == 6
    assert stats["fast_updates"] >= 1, \
        "no update took the warm path — brackets or verify are broken"


@pytest.mark.parametrize("backend", BACKENDS)
def test_update_survives_window_crossing_swap(backend, rng):
    """Adversarial eigenvalue-ordering swap: the update is aligned with an
    eigenvector *outside* the retained window and lifts its eigenvalue
    across the window boundary.  A warm start that blindly trusted the old
    ordering would return the stale window; the monitor (drift bound or
    the residual verify) must force a re-solve instead."""
    n, k = 12, 2
    engine = SolverEngine(_plan(backend))
    a = _sym(rng, n)
    lam, v = np.linalg.eigh(a)
    session = engine.open_session(
        a, k, config=SessionConfig(buffer=2, drift_bound=100.0))
    # Lift the *smallest* eigenvalue far above the current top: its
    # eigenvector is invariant, so A' = A + c^2 v0 v0^T swaps it to rank 1.
    c = np.sqrt(lam[-1] - lam[0] + 5.0)
    u = c * v[:, 0]
    a_new = a + np.outer(u, u)
    res = engine.update(session, Rank1Update(u, 1))
    _assert_conformant(a_new, res, k)
    # The new top eigenvalue is the lifted one — the ordering really swapped.
    assert abs(float(np.asarray(res.eigenvalues)[-1]) -
               (lam[0] + c * c)) < 1e-2 * np.linalg.norm(a_new)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.sampled_from([8, 16]),
       k=st.integers(1, 4), sign=st.sampled_from([-1, 1]),
       scale_exp=st.integers(-2, 1))
def test_property_update_is_oracle_conformant(seed, n, k, sign, scale_exp):
    """Random rank-1 perturbations of random magnitude (1e-2 .. 1e1 of the
    spectral scale) stay eigh-oracle-conformant to float32 tolerance —
    warm path and monitor-forced path alike."""
    rng = np.random.default_rng(seed)
    engine = SolverEngine(PLAN)
    a = _sym(rng, n)
    session = engine.open_session(a, k)
    u = rng.standard_normal(n) * float(10.0 ** scale_exp)
    a_new = a + sign * np.outer(u, u)
    res = engine.update(session, Rank1Update(u, sign))
    _assert_conformant(a_new, res, k)


def test_property_update_all_backends_one_seed(rng):
    """The same perturbation stream is oracle-conformant on every backend
    (the hypothesis property above fuzzes the jnp backend; this pins the
    other three to the identical stream)."""
    n, k = 8, 2
    a0 = _sym(rng, n)
    us = [rng.standard_normal(n) for _ in range(3)]
    for backend in BACKENDS:
        engine = SolverEngine(_plan(backend))
        a = a0.copy()
        session = engine.open_session(a, k)
        for u in us:
            a = a + np.outer(u, u)
            _assert_conformant(a, engine.update(session, Rank1Update(u, 1)),
                               k)


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------


def test_drift_monitor_forces_full_resolve(rng):
    """k consecutive updates past the drift bound each force a verified
    full re-solve — the warm path never runs on an over-drifted session."""
    n, k = 12, 2
    engine = SolverEngine(PLAN)
    a = _sym(rng, n)
    session = engine.open_session(
        a, k, config=SessionConfig(drift_bound=1e-9))
    for _ in range(4):
        u = rng.standard_normal(n)
        a = a + np.outer(u, u)
        _assert_conformant(a, engine.update(session, Rank1Update(u, 1)), k)
    stats = session.stats()
    assert stats["fast_updates"] == 0
    assert stats["full_resolves"] == 4
    assert stats["resolves_by_cause"].get("drift") == 4


def test_host_reseed_counted_apart_from_device_resolves(rng):
    """A device re-solve and a host LAPACK reseed both rebuild the window,
    but only the reseed counts in ``host_reseeds`` — the counter a chip
    run reads to prove no answer came from the host."""
    from repro.engine import session as session_mod

    n, k = 12, 2
    engine = SolverEngine(PLAN)
    a = _sym(rng, n)
    session = engine.open_session(
        a, k, config=SessionConfig(drift_bound=1e-9))
    u = rng.standard_normal(n)
    a = a + np.outer(u, u)
    engine.update(session, Rank1Update(u, 1))
    assert session.stats()["full_resolves"] == 1
    assert session.stats()["host_reseeds"] == 0
    session_mod.host_reseed(session, a)
    _assert_conformant(a, session.result(), k)
    stats = session.stats()
    assert stats["host_reseeds"] == 1
    assert stats["resolves_by_cause"] == {"drift": 1, "degrade": 1}


def test_drift_accumulates_across_small_updates(rng):
    """The bound is on *accumulated* |rho|/||A||_F: many small updates,
    each individually under the bound, must still trip it."""
    n, k = 12, 2
    engine = SolverEngine(PLAN)
    a = _sym(rng, n) * 10.0
    session = engine.open_session(
        a, k, config=SessionConfig(drift_bound=0.05))
    per_step = []
    for _ in range(12):
        u = rng.standard_normal(n) * 0.3
        a = a + np.outer(u, u)
        engine.update(session, Rank1Update(u, 1))
        per_step.append(session.stats()["full_resolves"])
    assert session.stats()["resolves_by_cause"].get("drift", 0) >= 1
    assert per_step[0] == 0, \
        "first tiny update should not trip an accumulation bound"
    _assert_conformant(a, session.result(), k)


def test_cadence_cap_bounds_staleness(rng):
    """Even with drift and verify green, ``max_updates`` fast updates force
    a re-solve — worst-case staleness is bounded."""
    n, k = 12, 2
    engine = SolverEngine(PLAN)
    a = _sym(rng, n) * 100.0
    session = engine.open_session(
        a, k, config=SessionConfig(drift_bound=1e9, max_updates=2))
    for _ in range(6):
        u = rng.standard_normal(n) * 1e-3
        a = a + np.outer(u, u)
        engine.update(session, Rank1Update(u, 1))
    stats = session.stats()
    assert stats["resolves_by_cause"].get("cadence") == 2
    assert stats["fast_updates"] == 4
    _assert_conformant(a, session.result(), k)


# ---------------------------------------------------------------------------
# Update request surface / edge cases
# ---------------------------------------------------------------------------


def test_rank_r_update_decomposes_sequentially(rng):
    """A sequence of Rank1Updates applies as r sequential rank-1 steps."""
    n, k = 10, 2
    engine = SolverEngine(PLAN)
    a = _sym(rng, n)
    session = engine.open_session(a, k)
    us = [rng.standard_normal(n) for _ in range(3)]
    signs = [1, -1, 1]
    for u, s in zip(us, signs):
        a = a + s * np.outer(u, u)
    res = engine.update(
        session, [Rank1Update(u, s) for u, s in zip(us, signs)])
    assert session.stats()["updates_total"] == 3
    _assert_conformant(a, res, k)


def test_update_rejects_malformed_requests(rng):
    n = 8
    engine = SolverEngine(PLAN)
    a = _sym(rng, n)
    session = engine.open_session(a, 2)
    with pytest.raises(ValueError, match="shape"):
        engine.update(session, Rank1Update(np.ones(n + 1)))
    with pytest.raises(ValueError, match="finite"):
        engine.update(session, Rank1Update(np.full(n, np.nan)))
    with pytest.raises(ValueError, match="sign"):
        engine.update(session, Rank1Update(np.ones(n), 2))
    # Zero vector: A + 0 = A — a no-op, not an error, and drifts nothing.
    before = session.stats()["drift"]
    engine.update(session, Rank1Update(np.zeros(n)))
    assert session.stats()["drift"] == before
    _assert_conformant(a, session.result(), 2)


def test_tuple_and_array_update_forms(rng):
    """``(u, sign)`` tuples and bare arrays coerce to Rank1Update."""
    n = 8
    engine = SolverEngine(PLAN)
    a = _sym(rng, n)
    session = engine.open_session(a, 2)
    u = rng.standard_normal(n)
    a = a + np.outer(u, u)
    _assert_conformant(a, engine.update(session, (u, 1)), 2)
    w = rng.standard_normal(n)
    a = a + np.outer(w, w)
    _assert_conformant(a, engine.update(session, w), 2)


# ---------------------------------------------------------------------------
# EeiServer stateful sessions
# ---------------------------------------------------------------------------


def _server(**kwargs) -> EeiServer:
    kwargs.setdefault("plan", PLAN)
    kwargs.setdefault("cache", SHARED_CACHE)
    return EeiServer(**kwargs)


@pytest.mark.parametrize("threaded", [False, True])
def test_server_session_update_stream(threaded, rng):
    """Sticky session updates through the server resolve in order and
    match the oracle — caller-driven and threaded pumps alike."""
    n, k = 12, 2
    kwargs = dict(linger_ms=1.0) if threaded else {}
    with _server(**kwargs) as server:
        a = _sym(rng, n)
        sid = server.open_session(a, k)
        futs = []
        for _ in range(4):
            u = rng.standard_normal(n)
            a = a + np.outer(u, u)
            futs.append((a.copy(), server.submit_update(sid, u)))
        for a_t, fut in futs:
            _assert_conformant(a_t, fut.result(timeout=60), k)
        snap = server.session_result(sid)
        _assert_conformant(a, snap, k)
        stats = server.stats()
        assert stats["sessions_open"] == 1
        assert stats["session_updates"] == 4
        assert stats["session_fast_updates"] + \
            stats["session_full_resolves"] == 4
        assert server.session_stats(sid)["updates_total"] == 4
        server.close_session(sid)
        assert server.stats()["sessions_open"] == 0
        with pytest.raises(KeyError):  # the sid no longer resolves
            server.submit_update(sid, rng.standard_normal(n))


def test_server_session_degrades_to_host_solve(rng):
    """A broken fast path degrades to a host eigh from the mirror: the
    future resolves with a flagged DegradedResult, never an error, and
    the window still matches the oracle (PR-7 fallback semantics)."""
    n, k = 10, 2
    with _server() as server:
        a = _sym(rng, n)
        sid = server.open_session(a, k)
        rec = server._sessions[sid]

        class _Broken:
            def update(self, *a, **kw):
                raise RuntimeError("backend down")

        rec.engine = _Broken()
        u = rng.standard_normal(n)
        a = a + np.outer(u, u)
        res = server.submit_update(sid, u).result(timeout=60)
        assert isinstance(res, DegradedResult)
        assert res.fallback == "host_reseed"
        _assert_conformant(a, res, k)
        assert server.stats()["session_degraded"] == 1
        assert server.session_stats(sid)["host_reseeds"] == 1


def test_server_session_malformed_update_fails_future(rng):
    """Bad requests fail the future directly — degrading cannot fix a
    wrong-shaped vector, and masking it would hide a caller bug."""
    n = 8
    with _server() as server:
        sid = server.open_session(_sym(rng, n), 2)
        with pytest.raises(ValueError):
            server.submit_update(sid, np.ones(n + 3)).result(timeout=60)
        assert server.stats()["requests_failed"] == 1


def test_server_close_fails_pending_session_ops(rng):
    """A non-draining close resolves queued session updates with
    ServerClosed instead of dropping them."""
    n = 8
    server = _server(linger_ms=50.0)
    sid = server.open_session(_sym(rng, n), 2)
    # Park the executor inside an update so followers stay queued.
    release = threading.Event()
    real_engine = server._sessions[sid].engine

    class _Slow:
        def update(self, *a, **kw):
            release.wait(10.0)
            return real_engine.update(*a, **kw)

    server._sessions[sid].engine = _Slow()
    rng_u = np.random.default_rng(7)
    futs = [server.submit_update(sid, rng_u.standard_normal(n))
            for _ in range(3)]
    time.sleep(0.05)  # let the executor pick up the first op
    server.close(drain=False, timeout=10.0)
    release.set()
    outcomes = []
    for f in futs:
        try:
            f.result(timeout=10)
            outcomes.append("ok")
        except ServerClosed:
            outcomes.append("closed")
    assert outcomes.count("closed") >= 2, outcomes
    assert all(o in ("ok", "closed") for o in outcomes)


# ---------------------------------------------------------------------------
# Adaptive linger
# ---------------------------------------------------------------------------


def test_adaptive_linger_trims_hot_key(rng):
    """Regression: a hot coalesce key (arrivals every ~2 ms) must not wait
    out a 2000 ms linger for its partial stacks.  The per-key EWMA arrival
    rate shrinks the effective linger to a few expected gaps, so the whole
    stream resolves in well under one base linger."""
    n, k = 8, 2
    # max_batch far above the stream size: the stack stays *partial*
    # forever, so without the adaptive trim it would sit the full 2 s.
    with _server(linger_ms=2000.0, max_batch=64,
                 record_dispatches=True) as server:
        futs = []
        for _ in range(20):
            futs.append(server.submit(_sym(rng, n), k))
            time.sleep(0.002)
        for f in futs:
            f.result(timeout=300)
        stats = server.stats()
        # Admission (linger) wait: queue-pop minus head submit — measured
        # pre-compile, so XLA time never pollutes the assertion.
        head_wait = max(rec.t_dispatch - min(r.t_submit
                                             for r in rec.requests)
                        for rec in server.dispatch_log)
    assert stats["linger_trims"] >= 1, \
        "hot key never trimmed its linger"
    assert head_wait < 1.0, \
        f"the partial stack waited out the base linger ({head_wait:.2f}s)"


def test_adaptive_linger_off_preserves_base_linger(rng):
    """With adaptive linger disabled the sparse-traffic contract is
    untouched: a lone partial stack waits the full (short) linger."""
    n, k = 8, 2
    with _server(linger_ms=120.0, max_batch=16,
                 adaptive_linger=False) as server:
        fut = server.submit(_sym(rng, n), k)
        t0 = time.monotonic()
        fut.result(timeout=60)
        assert time.monotonic() - t0 >= 0.08
        assert server.stats()["linger_trims"] == 0


# ---------------------------------------------------------------------------
# EeiFleet sticky sessions + failover
# ---------------------------------------------------------------------------


def _fleet(n_replicas: int = 3, **kwargs) -> EeiFleet:
    kwargs.setdefault("server_kwargs", dict(plan=PLAN))
    kwargs.setdefault("cache", SHARED_CACHE)
    kwargs.setdefault("probe_interval_s", 0.01)
    return EeiFleet(n_replicas, **kwargs)


def test_fleet_session_is_sticky(rng):
    """Updates for one session all land on its rendezvous-routed owner;
    results match the oracle end to end."""
    n, k = 10, 2
    with _fleet(3, salt=0) as fleet:
        a = _sym(rng, n)
        sid = fleet.open_session(a, k)
        owner = fleet._sessions[sid].rid
        for _ in range(3):
            u = rng.standard_normal(n)
            a = a + np.outer(u, u)
            res = fleet.submit_update(sid, u).result(timeout=120)
            _assert_conformant(a, res, k)
            assert fleet._sessions[sid].rid == owner
        _assert_conformant(a, fleet.session_result(sid), k)
        stats = fleet.stats()
        assert stats["session_updates"] == 3
        assert stats["session_failovers"] == 0
        fleet.close_session(sid)
        assert fleet.stats()["sessions_open"] == 0


def test_fleet_session_failover_reopens_from_mirror(rng):
    """Killing the owner mid-stream must not lose the session: the update
    resolves as a flagged DegradedResult from a reopen on a healthy
    replica (the mirror already contains the failed update), and the
    warm path then resumes on the new owner."""
    n, k = 10, 2
    with _fleet(3, salt=0) as fleet:
        a = _sym(rng, n)
        sid = fleet.open_session(a, k)
        rec = fleet._sessions[sid]
        old_owner = rec.rid
        u = rng.standard_normal(n)
        a = a + np.outer(u, u)
        fleet._kill_replica(old_owner, reason="test: kill session owner")
        res = fleet.submit_update(sid, u).result(timeout=120)
        assert isinstance(res, DegradedResult)
        assert res.fallback == "session_reopen"
        _assert_conformant(a, res, k)
        assert rec.rid != old_owner
        assert fleet.stats()["session_failovers"] == 1
        # Warm resumption on the new owner: a plain (non-degraded) window.
        w = rng.standard_normal(n) * 0.1
        a = a + np.outer(w, w)
        res2 = fleet.submit_update(sid, w).result(timeout=120)
        assert not isinstance(res2, DegradedResult)
        _assert_conformant(a, res2, k)
        assert rec.rid != old_owner
