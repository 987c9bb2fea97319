"""EeiFleet: multi-replica routing, health, failover, restart, and the
replica-level chaos conformance suite.

The fleet's contract extends the single-server one across replica death:
every caller future resolves exactly once with a finite, non-garbage
result, no matter which replica attempts raced, died, hung, or slowed —
and the fleet survives to serve the whole stream.  Which single-server
invariants lift to the fleet (and which do not) is documented in
``docs/ARCHITECTURE.md``; the tests here lock the lifted ones down.

Satellite coverage rides along: decorrelated retry jitter (seedable,
divergent across stacks), ``close(timeout=...)`` returning unresolved
futures instead of hanging, and the cross-server shared ``ProgramCache``
single-compile guarantee.
"""

import os
import signal
import threading
import time

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.engine import (
    EeiFleet,
    EeiServer,
    FleetClosed,
    ProgramCache,
    SolverPlan,
    verify_topk_host,
)
from repro.engine.fleet import HEALTHY, SLOW
from repro.runtime import ChaosConfig, ChaosMonkey, route_key
from repro.runtime.fault_tolerance import RestartPolicy, decorrelated_jitter

PLAN = SolverPlan(method="eei_tridiag", backend="jnp")

#: One cache across the whole module (mirrors test_server): every
#: in-process fleet shares it, so compiled programs amortize across tests
#: and restarted replicas come back warm.
SHARED_CACHE = ProgramCache()


def _sym(rng, n: int) -> np.ndarray:
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


def _fleet(n_replicas: int = 3, **kwargs) -> EeiFleet:
    # Fixed plan (not per-bucket auto-planning) so warm-up traffic and the
    # module-shared cache hit the same program keys across all tests.
    kwargs.setdefault("server_kwargs", dict(plan=PLAN))
    kwargs.setdefault("cache", SHARED_CACHE)
    kwargs.setdefault("probe_interval_s", 0.01)
    return EeiFleet(n_replicas, **kwargs)


def _warm(n: int = 8, k: int = 2, largest: bool = True) -> None:
    """Compile the ``(b=1, n, k, largest)`` bucket into SHARED_CACHE so
    tests with tight deadlines never mistake a cold XLA compile for a
    hang."""
    rng = np.random.default_rng(99)
    with EeiServer(PLAN, max_batch=1, cache=SHARED_CACHE) as s:
        fut = s.submit(_sym(rng, n), k, largest=largest)
        s.flush()
        fut.result(timeout=300)


def _assert_fleet_safe(reqs, stats) -> None:
    """``reqs`` is ``[(a, k, future), ...]``; the fleet-level safety
    contract: every caller future resolved exactly once with a finite,
    non-garbage result, and the counters account for the stream."""
    degraded = 0
    for a, k, fut in reqs:
        assert fut.done(), "a submitted future never resolved"
        res = fut.result(timeout=0)
        lam, vec = np.asarray(res.eigenvalues), np.asarray(res.vectors)
        assert lam.shape == (k,) and vec.shape == (k, a.shape[0])
        assert np.all(np.isfinite(lam)) and np.all(np.isfinite(vec))
        # Same garbage separator as the single-server chaos suite: healthy
        # float32 residuals are ~3e-4 of ||A||_F, garbage >= ~0.1.
        flags = verify_topk_host(a, lam, vec)
        assert float(flags.residual) <= 2e-2, (
            f"garbage reached a caller: residual={float(flags.residual)}")
        if getattr(res, "degraded", False):
            degraded += 1
    assert stats["requests_failed"] == 0
    assert stats["requests_completed"] == len(reqs)
    assert stats["requests_unresolved"] == 0


def _wait_for(predicate, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.01)
    pytest.fail(f"timed out after {timeout_s}s waiting for {what}")


# ---------------------------------------------------------------------------
# Building blocks: rendezvous routing, jitter, restart policy, watchdog
# ---------------------------------------------------------------------------


def test_subprocess_replica_refuses_a_tpu_parent(monkeypatch):
    """A parent on the TPU backend holds the chip: starting a worker
    process that needs it must raise, not run a CPU worker in its place."""
    import jax

    from repro.engine.fleet import SubprocessReplica

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        SubprocessReplica(0)


def test_route_key_deterministic_and_minimal_remap():
    """Rendezvous hashing: deterministic for a (key, candidates, salt)
    triple; removing a non-owner never remaps a key; restoring the dead
    candidate restores exactly the original assignment (self-healing)."""
    candidates = [0, 1, 2, 3]
    keys = [(n, largest) for n in range(4, 40) for largest in (True, False)]
    owner = {k: route_key(k, candidates, salt=7) for k in keys}
    assert owner == {k: route_key(k, candidates, salt=7) for k in keys}
    # different salts shuffle ownership (not all keys land identically)
    assert any(owner[k] != route_key(k, candidates, salt=8) for k in keys)
    dead = 2
    survivors = [c for c in candidates if c != dead]
    for k in keys:
        new = route_key(k, survivors, salt=7)
        if owner[k] != dead:
            assert new == owner[k], "removal of a non-owner remapped a key"
    assert {k: route_key(k, candidates, salt=7) for k in keys} == owner


def test_decorrelated_jitter_bounds_and_seed():
    rng = np.random.default_rng(3)
    base, cap, prev = 0.01, 0.5, 0.01
    seen = []
    for _ in range(50):
        prev = decorrelated_jitter(rng, base, prev, cap)
        assert base <= prev <= cap
        seen.append(prev)
    # seedable: the same seed replays the same schedule
    rng2 = np.random.default_rng(3)
    prev2 = 0.01
    replay = []
    for _ in range(50):
        prev2 = decorrelated_jitter(rng2, base, prev2, cap)
        replay.append(prev2)
    assert seen == replay
    assert len(set(seen)) > 10  # jitter, not a fixed ladder


def test_restart_policy_bounded_and_jittered():
    pol = RestartPolicy(max_restarts=3, base_delay_s=0.01, cap_s=1.0, seed=5)
    delays = []
    while not pol.give_up:
        delays.append(pol.next_delay())
    assert len(delays) == 3
    assert all(0.01 <= d <= 1.0 for d in delays)
    pol.reset()
    assert not pol.give_up
    # same seed is NOT re-seeded by reset: schedules keep diverging, which
    # is the point of decorrelated jitter (no thundering herd on flapping)
    assert pol.next_delay() > 0.0


# ---------------------------------------------------------------------------
# Fleet: routing + plain serving
# ---------------------------------------------------------------------------


def test_fleet_basic_serve_and_exactly_once():
    """N=3 in-process, mixed shapes, no chaos: every result matches the
    LAPACK oracle, every future resolves exactly once, nothing degraded."""
    rng = np.random.default_rng(0)
    reqs = []
    with _fleet(3) as fleet:
        for i in range(18):
            n = int(rng.integers(4, 13))
            k = 1 + int(rng.integers(0, n))
            a = _sym(rng, n)
            reqs.append((a, k, fleet.submit(a, k, largest=True)))
        assert fleet.flush(timeout=300)
        stats = fleet.stats()
    _assert_fleet_safe(reqs, stats)
    assert stats["requests_submitted"] == len(reqs)
    for a, k, fut in reqs:
        lam = np.sort(np.asarray(fut.result().eigenvalues))
        ref = np.linalg.eigvalsh(a.astype(np.float64))[-k:]
        np.testing.assert_allclose(lam, ref, rtol=5e-3, atol=5e-3)


def test_fleet_routes_one_key_to_one_replica():
    """All requests sharing a coalesce key land on the rendezvous owner:
    per-replica server stats show exactly one replica served them."""
    rng = np.random.default_rng(1)
    with _fleet(3, salt=4) as fleet:
        futs = [fleet.submit(_sym(rng, 8), 2) for _ in range(10)]
        for f in futs:
            f.result(timeout=300)
        per = fleet.stats()["per_replica"]
        served = [rid for rid, s in per.items()
                  if s.get("requests_submitted", 0) > 0]
    assert served == [route_key((8, True), [0, 1, 2], salt=4)]


def test_fleet_submit_validation_and_closed():
    with _fleet(1) as fleet:
        with pytest.raises(ValueError):
            fleet.submit(np.zeros((3, 4), dtype=np.float32), 1)
        with pytest.raises(ValueError):
            fleet.submit(np.eye(4, dtype=np.float32), 5)
    # after close: rejected with FleetClosed, never silently dropped
    fut = fleet.submit(np.eye(4, dtype=np.float32), 1)
    with pytest.raises(FleetClosed):
        fut.result(timeout=10)
    assert fleet.stats()["requests_rejected"] == 1


# ---------------------------------------------------------------------------
# Failover, restart, deadline, hedging
# ---------------------------------------------------------------------------


def test_fleet_failover_on_kill_and_restart():
    """Killing the replica that owns in-flight work must redispatch every
    unresolved request to a survivor (exactly-once, no caller ever sees
    ReplicaDied) and restart the dead replica within the timeout."""
    rng = np.random.default_rng(2)
    _warm()
    fleet = _fleet(3, salt=1,
                   restart_policy_kwargs=dict(base_delay_s=0.01, cap_s=0.1))
    try:
        # warm traffic before the kill, counted like everything else
        a0 = _sym(rng, 8)
        reqs = [(a0, 2, fleet.submit(a0, 2))]
        reqs[0][2].result(timeout=300)
        owner = route_key((8, True), [0, 1, 2], salt=1)
        reqs += [(a := _sym(rng, 8), 2, fleet.submit(a, 2))
                 for _ in range(6)]
        fleet._kill_replica(owner, reason="test kill")
        for _, _, f in reqs:
            f.result(timeout=300)
        stats = fleet.stats()
        _assert_fleet_safe(reqs, stats)
        assert stats["replicas_killed"] >= 1
        # the dead replica must come back and re-own its keys
        _wait_for(lambda: fleet.stats()["replica_states"][owner] == HEALTHY,
                  60, "killed replica to restart")
        assert fleet.stats()["replicas_restarted"] >= 1
        # rendezvous self-heals: traffic for the key flows to it again
        fleet.submit(_sym(rng, 8), 2).result(timeout=300)
        assert fleet._replicas[owner].driver.stats()[
            "requests_submitted"] >= 1
    finally:
        assert fleet.close(timeout=120) == []


def test_fleet_deadline_catches_hung_replica():
    """A hung replica (accepts work, never answers) is only visible to the
    deadline probe: the fleet must declare it dead, redispatch, and the
    caller future must still resolve with a good result."""
    rng = np.random.default_rng(3)
    _warm()  # a cold compile must never look like a hang to the deadline
    fleet = _fleet(2, salt=0, deadline_s=0.6,
                   restart_policy_kwargs=dict(base_delay_s=0.01, cap_s=0.1))
    try:
        for _ in range(2):
            fleet.submit(_sym(rng, 8), 2).result(timeout=300)
        owner = route_key((8, True), [0, 1], salt=0)
        fleet._replicas[owner].driver.hang(30.0)
        a = _sym(rng, 8)
        fut = fleet.submit(a, 2)
        res = fut.result(timeout=60)
        assert float(verify_topk_host(
            a, np.asarray(res.eigenvalues),
            np.asarray(res.vectors)).residual) <= 2e-2
        stats = fleet.stats()
        assert stats["deadline_deaths"] >= 1
        assert stats["replicas_killed"] >= 1
    finally:
        fleet.close(timeout=120)


def test_fleet_hedges_slow_replica_first_result_wins():
    """A SLOW-classified replica gets hedged: requests stuck past
    ``hedge_age_s`` are re-attempted on a healthy replica and the first
    result wins (the loser's internal future is cancelled, and a late
    duplicate success is counted, not double-resolved)."""
    rng = np.random.default_rng(4)
    _warm()
    fleet = _fleet(2, salt=0, hedge_age_s=0.05, slow_cooldown_s=30.0)
    try:
        for _ in range(2):
            fleet.submit(_sym(rng, 8), 2).result(timeout=300)
        owner = route_key((8, True), [0, 1], salt=0)
        replica = fleet._replicas[owner]
        # white-box: pin the classification (the organic watchdog path is
        # covered by the soak); delay every forward so hedges must win
        replica.driver.slow(1.0, duration_s=30.0)
        with fleet._cv:
            replica.state = SLOW
            replica.last_slow_flag = time.monotonic()
        a = _sym(rng, 8)
        fut = fleet.submit(a, 2)
        t0 = time.monotonic()
        res = fut.result(timeout=60)
        assert float(verify_topk_host(
            a, np.asarray(res.eigenvalues),
            np.asarray(res.vectors)).residual) <= 2e-2
        # the hedge (healthy replica, warm cache) beats the 1s-delayed
        # original by a wide margin
        assert time.monotonic() - t0 < 0.9
        assert fleet.stats()["hedges"] >= 1
    finally:
        fleet.close(timeout=120)


def test_fleet_parks_when_no_replica_routable_then_recovers():
    """With every replica dead, new work parks (never fails) and flows the
    moment a restart lands."""
    rng = np.random.default_rng(5)
    fleet = _fleet(2, restart_policy_kwargs=dict(base_delay_s=0.05,
                                                 cap_s=0.2))
    try:
        fleet.submit(_sym(rng, 8), 2).result(timeout=300)
        for rid in (0, 1):
            fleet._kill_replica(rid, reason="test: total outage")
        with fleet._cv:
            dead_now = all(r.state != HEALTHY
                           for r in fleet._replicas.values())
        assert dead_now
        a = _sym(rng, 8)
        fut = fleet.submit(a, 2)  # admitted during the outage
        res = fut.result(timeout=120)  # resolves after restart
        assert float(verify_topk_host(
            a, np.asarray(res.eigenvalues),
            np.asarray(res.vectors)).residual) <= 2e-2
        assert fleet.stats()["replicas_restarted"] >= 1
    finally:
        assert fleet.close(timeout=120) == []


# ---------------------------------------------------------------------------
# Chaos conformance (the fleet-level analogue of the server chaos fuzz)
# ---------------------------------------------------------------------------

_FREQ = st.tuples(st.integers(4, 12), st.integers(0, 1), st.booleans(),
                  st.integers(0, 2))


@settings(max_examples=3, deadline=None)
@given(ops=st.lists(_FREQ, min_size=4, max_size=14),
       rate=st.sampled_from([0.05, 0.1]),
       seed=st.integers(0, 999), chaos_seed=st.integers(0, 999))
def test_fleet_chaos_stream_conformance_fuzz(ops, rate, seed, chaos_seed):
    """Random streams under 5-10% replica-level chaos (kills, hangs,
    slowdowns): every caller future resolves exactly once with a finite,
    non-garbage result; infra failures never surface to callers; the
    fleet survives the whole stream."""
    chaos = ChaosMonkey(ChaosConfig(
        seed=chaos_seed, rate=0.0, replica_kill_rate=rate,
        replica_hang_rate=rate / 2, replica_slow_rate=rate,
        replica_slow_s=0.01, replica_hang_s=0.3))
    fleet = _fleet(3, chaos=chaos, deadline_s=30.0,
                   restart_policy_kwargs=dict(
                       max_restarts=10_000, base_delay_s=0.01, cap_s=0.1))
    rng = np.random.default_rng(seed)
    reqs = []
    try:
        for n, k_raw, largest, action in ops:
            a, k = _sym(rng, n), 1 + k_raw % n
            reqs.append((a, k, fleet.submit(a, k, largest=largest)))
            if action == 1:
                time.sleep(0.002)
        for _, _, f in reqs:
            f.result(timeout=300)
    finally:
        stranded = fleet.close(timeout=300)
    assert stranded == []
    stats = fleet.stats()
    _assert_fleet_safe(reqs, stats)
    assert stats["chaos_injected"] == chaos.counts()


# ---------------------------------------------------------------------------
# Satellites: retry jitter, close-timeout semantics, shared program cache
# ---------------------------------------------------------------------------


def _all_launches_fail_server(jitter_seed, chaos_seed=11):
    """A server whose every dispatch launch fails (transient): each stack
    burns the full retry ladder (recording its jittered delays) and then
    resolves through the fallback chain — callers still get answers."""
    return EeiServer(
        PLAN, max_batch=1, retry_backoff_s=0.001, retry_backoff_cap_s=0.01,
        retry_jitter_seed=jitter_seed, cache=ProgramCache(),
        chaos=ChaosMonkey(ChaosConfig(seed=chaos_seed, rate=0.0,
                                      launch_rate=1.0)))


def _burn_retries(server, n_stacks=3):
    rng = np.random.default_rng(9)
    reqs = [(a := _sym(rng, 8), server.submit(a, 2)) for _ in range(n_stacks)]
    server.flush()
    for a, f in reqs:
        res = f.result(timeout=300)
        assert res.degraded  # resolved via fallback, not the failing launch
    return list(server.retry_delays_s)


def test_retry_jitter_seedable_and_schedules_diverge():
    """Decorrelated retry jitter: two servers with different jitter seeds
    (same fault schedule) sleep different backoff ladders — retries from
    concurrently-failing stacks spread instead of marching in lockstep —
    while the same seed replays the exact schedule."""
    d1 = _burn_retries(_all_launches_fail_server(jitter_seed=1))
    d2 = _burn_retries(_all_launches_fail_server(jitter_seed=2))
    d1_again = _burn_retries(_all_launches_fail_server(jitter_seed=1))
    # max_retries=2 default: two backoff sleeps per failing stack
    assert len(d1) == len(d2) == len(d1_again) == 6
    assert all(0.001 <= d <= 0.01 for d in d1 + d2)
    assert d1 == d1_again, "same seed must replay the same schedule"
    assert d1 != d2, "different seeds must decorrelate the schedules"
    assert len(set(d1)) > 1, "delays within one schedule must vary"


def test_close_timeout_returns_unresolved_futures():
    """``close(drain=True, timeout=...)`` on a server wedged by chaos
    slow-retires must return the still-unresolved futures instead of
    hanging or raising — the caller decides what to do with the tail."""
    chaos = ChaosMonkey(ChaosConfig(seed=3, rate=0.0, slow_retire_rate=1.0,
                                    slow_s=1.0))
    server = EeiServer(PLAN, max_batch=1, linger_ms=1.0, cache=SHARED_CACHE,
                       chaos=chaos)
    rng = np.random.default_rng(6)
    futs = [server.submit(_sym(rng, 8), 2) for _ in range(4)]
    t0 = time.monotonic()
    stranded = server.close(drain=True, timeout=0.2)
    assert time.monotonic() - t0 < 5.0, "close() must respect its timeout"
    assert stranded, "slow-retired tail should still be unresolved"
    assert set(stranded) <= set(futs)
    assert not server.alive()
    # the retire thread is still draining (daemon): the stranded futures
    # eventually resolve — close() never double-resolved or leaked them
    for f in futs:
        f.result(timeout=300)
    assert server.stats()["requests_unresolved"] == 0


def test_clean_close_returns_empty_list():
    rng = np.random.default_rng(7)
    server = EeiServer(PLAN, max_batch=2, linger_ms=1.0, cache=SHARED_CACHE)
    futs = [server.submit(_sym(rng, 8), 2) for _ in range(3)]
    assert server.close(drain=True, timeout=300) == []
    assert all(f.done() for f in futs)


def test_cross_server_shared_cache_compiles_once_per_bucket():
    """Two servers sharing one injected ProgramCache: concurrent misses on
    the same bucket from *different* servers still compile exactly once
    (the fleet's warm-restart property depends on this)."""
    cache = ProgramCache()
    servers = [EeiServer(PLAN, max_batch=1, cache=cache) for _ in range(2)]
    rng = np.random.default_rng(8)
    mats = [_sym(rng, 8) for _ in range(6)]
    barrier = threading.Barrier(2)
    results = [None, None]

    def drive(i):
        barrier.wait()  # race the first-miss compile across servers
        futs = [servers[i].submit(a, 2) for a in mats]
        servers[i].flush()
        results[i] = [f.result(timeout=300) for f in futs]

    threads = [threading.Thread(target=drive, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    assert cache.compiles == 1 and len(cache) == 1
    assert cache.hits + cache.misses == 12  # every dispatch accounted
    lam0 = [np.asarray(r.eigenvalues) for r in results[0]]
    lam1 = [np.asarray(r.eigenvalues) for r in results[1]]
    for x, y in zip(lam0, lam1):
        np.testing.assert_array_equal(x, y)  # same program, same bits
    for s in servers:
        s.close()


# ---------------------------------------------------------------------------
# Stress lane (-m slow): soak + subprocess replica kill lane
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_chaos_soak_kill_restart_resume():
    """60-request soak at 8% kills / 4% hangs / 8% slowdowns: exactly-once
    and nothing-degraded-unflagged hold end-to-end, kills actually fired,
    and killed replicas restarted and resumed serving."""
    chaos = ChaosMonkey(ChaosConfig(
        seed=7, rate=0.0, replica_kill_rate=0.08, replica_hang_rate=0.04,
        replica_slow_rate=0.08, replica_slow_s=0.01, replica_hang_s=0.3))
    fleet = _fleet(3, chaos=chaos, deadline_s=30.0,
                   restart_policy_kwargs=dict(
                       max_restarts=10_000, base_delay_s=0.01, cap_s=0.1))
    rng = np.random.default_rng(12)
    reqs = []
    try:
        for i in range(60):
            n = int(rng.integers(4, 13))
            k = 1 + int(rng.integers(0, n))
            a = _sym(rng, n)
            reqs.append((a, k, fleet.submit(a, k, largest=bool(i % 2))))
            if i % 7 == 0:
                time.sleep(0.005)
        for _, _, f in reqs:
            f.result(timeout=300)
    finally:
        stranded = fleet.close(timeout=300)
    assert stranded == []
    stats = fleet.stats()
    _assert_fleet_safe(reqs, stats)
    assert stats["replicas_killed"] >= 1, "soak never exercised a kill"
    assert stats["replicas_restarted"] >= 1
    assert stats["redispatches"] >= 1
    assert stats["chaos_injected"]["replica_kill"] >= 1


@pytest.mark.slow
def test_fleet_subprocess_replica_sigkill_failover():
    """Real process isolation: N=2 subprocess replicas, SIGKILL one worker
    mid-stream — EOF on its pipe must fail over every outstanding request
    to the survivor, exactly-once, and close() leaves nothing stranded."""
    if os.cpu_count() is None or os.cpu_count() < 1:
        pytest.skip("no CPU count available")
    rng = np.random.default_rng(13)
    fleet = EeiFleet(
        2, replica_mode="subprocess", probe_interval_s=0.02,
        server_kwargs=dict(max_batch=4, linger_ms=2.0),
        restart_policy_kwargs=dict(max_restarts=100, base_delay_s=0.05,
                                   cap_s=0.5))
    reqs = []
    try:
        # warm both workers (each owns its own process-local cache)
        for _ in range(4):
            a = _sym(rng, 8)
            reqs.append((a, 2, fleet.submit(a, 2)))
        for _, _, f in reqs:
            f.result(timeout=300)
        victim = fleet._replicas[0]
        for _ in range(6):
            a = _sym(rng, 8)
            reqs.append((a, 2, fleet.submit(a, 2)))
        os.kill(victim.driver._proc.pid, signal.SIGKILL)
        for _, _, f in reqs:
            f.result(timeout=300)
        stats = fleet.stats()
        assert stats["replicas_killed"] >= 1
    finally:
        stranded = fleet.close(timeout=300)
    assert stranded == []
    _assert_fleet_safe(reqs, fleet.stats())
