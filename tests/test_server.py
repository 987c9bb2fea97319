"""EeiServer: continuous batching, shape buckets, program-cache bounds,
and the concurrent runtime (linger admission, producer races, close).

The serving machinery's contract: coalescing + bucket padding + slicing add
*zero* numerical change (server output is bit-identical to ``SolverEngine``
on the equivalent padded stack, and bit-identical k-slices of it), padded
rows/components never leak into results, and a mixed 100-request stream
executes through at most one compile per distinct shape bucket.

The property-based stream-conformance suite at the bottom locks the
threaded runtime down: random heterogeneous ``(n, k, largest)`` streams
with random pump/linger timing must stay bitwise-equal to the synchronous
``SolverEngine.topk`` oracle on every dispatched stack, every submitted
future must resolve exactly once, and the program-cache counters must
account for every dispatch.
"""

import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.engine import (
    EeiServer,
    ProgramCache,
    QueueFull,
    ServerClosed,
    ShapeBucket,
    SolverEngine,
    SolverPlan,
    verify_topk_host,
)
from repro.engine.server import PackedBucket, _bucket_n, make_eei_stream
from repro.kernels import blocks
from repro.runtime import ChaosConfig, ChaosMonkey

PLAN = SolverPlan(method="eei_tridiag", backend="jnp")

#: One cache across the whole module: fuzzer iterations and the thread
#: tests reuse compiled programs instead of recompiling per example (the
#: cache is documented shareable and thread-safe).
SHARED_CACHE = ProgramCache()


def _sym(rng, n: int) -> np.ndarray:
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


def _serve(server: EeiServer, stream):
    futs = [server.submit(a, k) for a, k in stream]
    server.flush()
    return [f.result() for f in futs]


def _assert_stream_conformant(server: EeiServer) -> None:
    """Every dispatched stack must be bitwise-equal to the synchronous
    ``SolverEngine.topk`` oracle run on the *same* padded stack under the
    *same* plan, sliced per request — coalescing, padding, threading and
    slicing add zero numerical change.  Needs ``record_dispatches=True``."""
    for rec in server.dispatch_log:
        ref = SolverEngine(rec.plan).topk(
            jnp.asarray(rec.stack), rec.bucket.k, rec.bucket.largest)
        lam, vec = np.asarray(ref.eigenvalues), np.asarray(ref.vectors)
        for row, req in enumerate(rec.requests):
            res = req.future.result(timeout=60)
            if req.largest:
                lam_e, vec_e = lam[row, -req.k:], vec[row, -req.k:, : req.n]
            else:
                lam_e, vec_e = lam[row, : req.k], vec[row, : req.k, : req.n]
            np.testing.assert_array_equal(res.eigenvalues, lam_e)
            np.testing.assert_array_equal(res.vectors, vec_e)


def _assert_accounting(server: EeiServer, futures, cache_before) -> None:
    """Future accounting (every submit resolves exactly once) and program
    cache accounting (hit + miss == dispatch count)."""
    assert all(f.done() for f in futures)
    stats = server.stats()
    assert stats["requests_submitted"] == len(futures)
    assert (stats["requests_completed"] + stats["requests_failed"]
            == len(futures))
    assert len({id(f) for f in futures}) == len(futures)  # no duplicates
    hits0, misses0 = cache_before
    assert (server.cache.hits - hits0) + (server.cache.misses - misses0) \
        == stats["stacks_dispatched"]


# ---------------------------------------------------------------------------
# Numerical contract
# ---------------------------------------------------------------------------


def test_full_stack_bit_identical_to_engine_program():
    """One full stack of mixed-k requests == engine.topk on the same stack.

    The server's value-add (queueing, bucketing, the program cache, async
    dispatch, per-request slicing) must be numerically invisible: for
    aligned n the padded stack *is* the engine's stack, and heterogeneous k
    rides the group-max program with per-request slices that are bitwise
    equal to what smaller-k programs produce (k-selected stages are
    per-pair independent).
    """
    rng = np.random.default_rng(0)
    mats = [_sym(rng, 16) for _ in range(8)]
    ks = [4, 2, 1, 3, 4, 4, 2, 3]
    server = EeiServer(PLAN, max_batch=8)
    results = _serve(server, list(zip(mats, ks)))
    assert server.stats()["stacks_dispatched"] == 1

    ref = SolverEngine(PLAN).topk(jnp.asarray(np.stack(mats)), 4)
    lam_ref = np.asarray(ref.eigenvalues)
    vec_ref = np.asarray(ref.vectors)
    for i, ((lam, vec), k) in enumerate(zip(results, ks)):
        assert lam.shape == (k,) and vec.shape == (k, 16)
        np.testing.assert_array_equal(lam, lam_ref[i, -k:])
        np.testing.assert_array_equal(vec, vec_ref[i, -k:])


def test_mixed_stream_matches_per_request_topk():
    """Heterogeneous (n, k) stream vs one engine.topk call per request.

    Per-request programs run at b=1 while the server batches, so float32
    XLA fusions may differ in the last bits — agreement is to tight
    tolerance, and eigenvalues/vectors land in the request's own shapes.
    """
    rng = np.random.default_rng(1)
    stream = [(_sym(rng, n), k)
              for n, k in [(16, 4), (24, 2), (16, 1), (32, 4), (24, 3),
                           (16, 2), (32, 1), (16, 4), (24, 4), (32, 2)]]
    server = EeiServer(PLAN, max_batch=4)
    results = _serve(server, stream)
    engine = SolverEngine(PLAN)
    for (a, k), (lam, vec) in zip(stream, results):
        ref = engine.topk(jnp.asarray(a), k)
        np.testing.assert_allclose(lam, np.asarray(ref.eigenvalues),
                                   rtol=1e-5, atol=1e-5)
        err = np.minimum(np.abs(vec - np.asarray(ref.vectors)),
                         np.abs(vec + np.asarray(ref.vectors))).max()
        assert err < 5e-3, err


@pytest.mark.parametrize("largest", [True, False])
def test_guard_padded_n_never_leaks(largest):
    """Unaligned n pads to the bucket via guard-diagonal embedding; results
    must carry only the request's own eigenpairs (vs an eigh oracle)."""
    rng = np.random.default_rng(2)
    stream = [(_sym(rng, n), 3) for n in (9, 13, 17, 21, 30, 9, 13, 11)]
    server = EeiServer(PLAN, max_batch=4)
    futs = [server.submit(a, k, largest=largest) for a, k in stream]
    server.flush()
    for (a, k), fut in zip(stream, futs):
        lam, vec = fut.result()
        n = a.shape[0]
        assert lam.shape == (k,) and vec.shape == (k, n)
        w, v = np.linalg.eigh(a.astype(np.float64))
        w_sel = w[-k:] if largest else w[:k]
        v_sel = (v[:, -k:] if largest else v[:, :k]).T
        np.testing.assert_allclose(lam, w_sel, rtol=1e-4, atol=1e-4)
        # guard eigenvalues sit outside the spectrum — none may appear
        assert np.all(lam >= w[0] - 1e-3) and np.all(lam <= w[-1] + 1e-3)
        err = np.abs(np.abs(vec) - np.abs(v_sel)).max()
        assert err < 5e-3, err


def test_batch_padding_rows_never_leak():
    """A partial stack (3 requests into a pow2-4 bucket) returns exactly 3
    results; the padded row is sliced off before futures resolve."""
    rng = np.random.default_rng(3)
    stream = [(_sym(rng, 16), 2) for _ in range(3)]
    server = EeiServer(PLAN, max_batch=8)
    results = _serve(server, stream)
    assert len(results) == 3
    assert server.stats()["requests_completed"] == 3
    bucket = server.cache.buckets()[0]
    assert bucket.b == 4  # 3 requests padded to the pow2 bucket
    engine = SolverEngine(PLAN)
    for (a, k), (lam, vec) in zip(stream, results):
        ref = engine.topk(jnp.asarray(a), k)
        np.testing.assert_allclose(lam, np.asarray(ref.eigenvalues),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Program-cache bounds (the compile-amortization contract)
# ---------------------------------------------------------------------------


def test_program_cache_bounded_by_buckets_on_100_request_stream():
    stream = make_eei_stream(100, 16, 4, seed=7, mixed=True)
    server = EeiServer(PLAN, max_batch=16)
    results = _serve(server, stream)
    assert len(results) == 100
    stats = server.stats()
    assert stats["requests_completed"] == 100
    # one compile per distinct bucket, nothing per-request / per-shape
    assert server.cache.compiles == stats["distinct_buckets"]
    assert server.cache.compiles == len(set(server.cache.buckets()))
    assert server.cache.compiles <= 8  # 100 requests, single-digit programs
    assert server.cache.hits == stats["stacks_dispatched"] - \
        server.cache.compiles
    # replaying the same stream is all hits, zero compiles
    before = server.cache.compiles
    _serve(server, stream)
    assert server.cache.compiles == before


def test_warm_server_replay_is_steady_state():
    stream = make_eei_stream(40, 16, 4, seed=8, mixed=True)
    server = EeiServer(PLAN, max_batch=8)
    _serve(server, stream)
    server.reset_stats()
    results = _serve(server, stream)
    assert len(results) == 40
    stats = server.stats()
    assert stats["program_compiles"] == 0  # warm: buckets bound compilation
    assert stats["program_hits"] == stats["stacks_dispatched"]
    assert stats["p99_latency_ms"] >= stats["p50_latency_ms"] >= 0.0


def test_shape_bucket_rounding():
    b = ShapeBucket.for_requests(5, 17, 3, True)
    assert b == ShapeBucket(b=8, n=24, k=4, largest=True)
    # k bucket never exceeds the padded n
    b = ShapeBucket.for_requests(1, 17, 17, False)
    assert b.n == 24 and b.k == 24 and b.b == 1
    assert ShapeBucket.for_requests(16, 16, 4, True) == \
        ShapeBucket(16, 16, 4, True)


def test_program_cache_counters():
    cache = ProgramCache()
    bucket = ShapeBucket(2, 16, 2, True)
    p1 = cache.get(bucket, PLAN, jnp.float32)
    p2 = cache.get(bucket, PLAN, jnp.float32)
    assert p1 is p2
    assert (cache.hits, cache.misses, cache.compiles, len(cache)) == \
        (1, 1, 1, 1)
    cache.get(ShapeBucket(2, 16, 2, False), PLAN, jnp.float32)
    assert cache.compiles == 2 and len(cache) == 2


def test_pad_waste_accounting_exact():
    """``pad_waste_frac`` counts exactly the grid cells the bucket padded
    on top of the requests' own ``n*n`` work, cumulatively and per bucket:
    an aligned full group wastes 0, an unaligned partial group wastes the
    batch-fill rows plus the n-guard ring."""
    rng = np.random.default_rng(7)
    server = EeiServer(PLAN, max_batch=4)
    futs = [server.submit(_sym(rng, 16), 2) for _ in range(4)]  # exact fit
    futs += [server.submit(_sym(rng, 17), 2) for _ in range(3)]  # pads both
    server.flush()
    [f.result() for f in futs]
    stats = server.stats()
    # bucket 1: b=4, n=16 — zero padding.  bucket 2: 3 requests of n=17
    # round to b=4, n=24 — one full batch-fill matrix + guard ring.
    real = 4 * 16 * 16 + 3 * 17 * 17
    total = 4 * 16 * 16 + 4 * 24 * 24
    assert stats["grid_cells_real"] == real
    assert stats["grid_cells_total"] == total
    assert stats["pad_waste_frac"] == pytest.approx(1.0 - real / total)
    per = stats["pad_waste_by_bucket"]
    assert per["b4n16k2L"] == 0.0
    assert per["b4n24k2L"] == pytest.approx(
        1.0 - (3 * 17 * 17) / (4 * 24 * 24), abs=1e-6)
    server.reset_stats()
    stats = server.stats()
    assert stats["grid_cells_total"] == 0
    assert stats["pad_waste_frac"] == 0.0
    assert stats["pad_waste_by_bucket"] == {}


def test_bucket_rounds_up_to_mesh_batch_axis(monkeypatch):
    """A sharded plan needs stacks divisible by the mesh batch axis; a
    partial group's pow2 bucket must round up to it (the engine pads its
    chunks the same way), not crash inside shard_map."""
    monkeypatch.setattr(SolverPlan, "batch_axis_size",
                        property(lambda self: 8))
    rng = np.random.default_rng(11)
    stream = [(_sym(rng, 16), 2) for _ in range(3)]
    server = EeiServer(PLAN, max_batch=16)
    results = _serve(server, stream)
    assert server.cache.buckets()[0].b == 8  # pow2(3)=4, padded to axis 8
    engine = SolverEngine(PLAN)
    for (a, k), (lam, vec) in zip(stream, results):
        np.testing.assert_allclose(
            lam, np.asarray(engine.topk(jnp.asarray(a), k).eigenvalues),
            rtol=1e-5, atol=1e-5)


def test_non_pow2_max_batch_floors_to_bound():
    """Stack buckets are pow2 — max_batch=48 must serve stacks of at most
    32, never round a full group up past the operator's bound."""
    server = EeiServer(PLAN, max_batch=48)
    assert server.max_batch == 32
    assert EeiServer(PLAN, max_batch=16).max_batch == 16
    assert EeiServer(PLAN, max_batch=1).max_batch == 1


def test_submit_validation():
    server = EeiServer(PLAN)
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        server.submit(rng.standard_normal((4, 5)), 1)
    with pytest.raises(ValueError):
        server.submit(_sym(rng, 4), 0)
    with pytest.raises(ValueError):
        server.submit(_sym(rng, 4), 5)
    with pytest.raises(ValueError):
        EeiServer(PLAN, max_batch=0)
    with pytest.raises(ValueError):
        EeiServer(PLAN, max_inflight=0)


def test_partial_group_does_not_block_other_full_stacks():
    """Head-of-line regression: a partial group in one coalesce key must
    not delay a full stack forming in another key."""
    rng = np.random.default_rng(10)
    server = EeiServer(PLAN, max_batch=4)
    f_head = server.submit(_sym(rng, 16), 2)  # partial n=16 group sits first
    futs = [server.submit(_sym(rng, 32), 2) for _ in range(4)]
    # the full n=32 stack dispatched despite the queued partial n=16 group
    assert server.stats()["stacks_dispatched"] == 1
    assert not f_head.done()
    server.flush()
    assert f_head.done() and all(f.done() for f in futs)
    assert server.stats()["stacks_dispatched"] == 2


def test_failed_dispatch_escalates_to_degraded_results(monkeypatch):
    """A persistent compile/launch failure must not strand callers or fail
    the stack: the server bisection-splits the group and every isolated
    request resolves through the fallback chain as a DegradedResult."""
    rng = np.random.default_rng(12)
    server = EeiServer(PLAN, max_batch=4)

    def boom(*a, **k):
        raise RuntimeError("synthetic compile failure")

    monkeypatch.setattr(server.cache, "get", boom)
    futs = [server.submit(_sym(rng, 16), 2) for _ in range(4)]
    assert all(f.done() for f in futs)  # resolved, not stranded
    for f in futs:
        res = f.result()
        assert res.degraded
        assert res.eigenvalues.shape == (2,)
        assert np.all(np.isfinite(res.vectors))
    stats = server.stats()
    assert stats["requests_failed"] == 0
    assert stats["requests_degraded"] == 4
    assert stats["stack_splits"] >= 1  # 4 -> 2+2 -> 1+1+1+1
    assert sum(stats["fallbacks_by_plan"].values()) == 4
    # the server keeps serving (non-degraded) after the failures
    monkeypatch.undo()
    ok = server.submit(_sym(rng, 16), 2)
    server.flush()
    assert ok.result().eigenvalues.shape == (2,)
    assert not ok.result().degraded


def test_failed_dispatch_fail_fast_without_fallback(monkeypatch):
    """With fallback=False the pre-robustness contract holds: the group's
    futures resolve with the error (never stranded), and the server keeps
    serving afterwards."""
    rng = np.random.default_rng(12)
    server = EeiServer(PLAN, max_batch=4, fallback=False)

    def boom(*a, **k):
        raise RuntimeError("synthetic compile failure")

    monkeypatch.setattr(server.cache, "get", boom)
    futs = [server.submit(_sym(rng, 16), 2) for _ in range(4)]
    assert all(f.done() for f in futs)  # resolved, not stranded
    with pytest.raises(RuntimeError, match="synthetic"):
        futs[0].result()
    assert server.stats()["requests_failed"] == 4
    monkeypatch.undo()
    ok = server.submit(_sym(rng, 16), 2)
    server.flush()
    assert ok.result().eigenvalues.shape == (2,)


def test_double_buffer_keeps_stacks_inflight():
    """With max_inflight=2, dispatching 3 full stacks retires only the
    oldest eagerly; the rest resolve on flush()."""
    rng = np.random.default_rng(9)
    server = EeiServer(PLAN, max_batch=2, max_inflight=2)
    futs = [server.submit(_sym(rng, 16), 2) for _ in range(6)]
    # 3 full stacks dispatched by pump(); at most one retired so far
    assert server.stats()["stacks_dispatched"] == 3
    assert sum(f.done() for f in futs) <= 2
    server.flush()
    assert all(f.done() for f in futs)
    assert server.stats()["requests_completed"] == 6


# ---------------------------------------------------------------------------
# Threaded runtime: linger admission, close semantics, backpressure
# ---------------------------------------------------------------------------


def test_linger_dispatches_partial_stack_without_flush():
    """A sparse stream (3 requests into a max_batch=8 server) must complete
    via the linger thread alone — no pump(), no flush() — within the
    timeout, bitwise-equal to the sync oracle."""
    rng = np.random.default_rng(20)
    with EeiServer(PLAN, max_batch=8, linger_ms=20, cache=SHARED_CACHE,
                   record_dispatches=True) as server:
        before = (server.cache.hits, server.cache.misses)
        futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
        results = [f.result(timeout=120) for f in futs]  # no flush!
        assert all(r.eigenvalues.shape == (2,) for r in results)
        assert server.stats()["stacks_dispatched"] >= 1
        _assert_accounting(server, futs, before)
        _assert_stream_conformant(server)


def test_linger_full_stack_dispatches_before_timeout():
    """Full stacks must not wait out the linger: with a huge linger, a
    full max_batch group still dispatches immediately."""
    rng = np.random.default_rng(21)
    with EeiServer(PLAN, max_batch=4, linger_ms=60_000,
                   cache=SHARED_CACHE) as server:
        futs = [server.submit(_sym(rng, 12), 2) for _ in range(4)]
        for f in futs:
            f.result(timeout=120)  # would time out if linger gated it
        assert server.stats()["stacks_dispatched"] == 1


def test_submit_after_close_resolves_with_error():
    """Late submits must get a resolved-with-error future, not a stranded
    one (and not an exception at the call site)."""
    rng = np.random.default_rng(22)
    for kwargs in ({}, {"linger_ms": 5.0, "cache": SHARED_CACHE}):
        server = EeiServer(PLAN, max_batch=4, **kwargs)
        server.close()
        fut = server.submit(_sym(rng, 8), 1)
        assert fut.done()
        with pytest.raises(ServerClosed):
            fut.result()
        assert server.stats()["requests_rejected"] == 1
        server.close()  # idempotent


def test_close_drains_queued_and_inflight():
    """close() before the linger expires must still drain the queued
    partial group: every future resolves with a real result."""
    rng = np.random.default_rng(23)
    server = EeiServer(PLAN, max_batch=8, linger_ms=60_000,
                       cache=SHARED_CACHE)
    futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
    server.close(timeout=120)
    for f in futs:
        assert f.done()
        assert f.result().eigenvalues.shape == (2,)
    assert server.stats()["requests_completed"] == 3


def test_close_without_drain_fails_queued_futures():
    """close(drain=False) must resolve still-queued requests with
    ServerClosed — resolved, never stranded."""
    rng = np.random.default_rng(24)
    server = EeiServer(PLAN, max_batch=8, linger_ms=60_000,
                       cache=SHARED_CACHE)
    futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
    server.close(drain=False, timeout=120)
    for f in futs:
        assert f.done()
        with pytest.raises(ServerClosed):
            f.result()
    assert server.stats()["requests_failed"] == 3


def test_flush_is_idempotent_and_reentrant():
    """Double flush() (sequential and from two racing threads) must be a
    safe no-op once drained — the double-flush idempotency guard."""
    rng = np.random.default_rng(25)
    server = EeiServer(PLAN, max_batch=4)
    server.flush()  # flush on an empty server
    futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
    server.flush()
    dispatched = server.stats()["stacks_dispatched"]
    server.flush()  # second flush: nothing new
    assert server.stats()["stacks_dispatched"] == dispatched
    assert all(f.done() for f in futs)
    # threaded mode: two concurrent flush barriers
    with EeiServer(PLAN, max_batch=8, linger_ms=10_000,
                   cache=SHARED_CACHE) as tserver:
        tfuts = [tserver.submit(_sym(rng, 12), 2) for _ in range(3)]
        threads = [threading.Thread(target=tserver.flush) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "flush barrier deadlocked"
        assert all(f.done() for f in tfuts)


def test_backpressure_except_policy_raises_queue_full():
    rng = np.random.default_rng(26)
    server = EeiServer(PLAN, max_batch=8, max_pending=2,
                       pending_policy="except")
    server.submit(_sym(rng, 8), 1)
    server.submit(_sym(rng, 8), 1)
    with pytest.raises(QueueFull):
        server.submit(_sym(rng, 8), 1)
    server.flush()  # drains; submits admissible again
    f = server.submit(_sym(rng, 8), 1)
    server.flush()
    assert f.result().eigenvalues.shape == (1,)


def test_backpressure_block_policy_drains_via_linger_thread():
    """With pending_policy='block', producers stall at max_pending and the
    linger thread makes space — bounded by a watchdog so a regression shows
    as a failure, not a hang."""
    rng = np.random.default_rng(27)
    futs = []
    with EeiServer(PLAN, max_batch=2, linger_ms=1, max_pending=2,
                   pending_policy="block", cache=SHARED_CACHE) as server:
        def produce():
            for _ in range(8):
                futs.append(server.submit(_sym(rng, 12), 2))

        worker = threading.Thread(target=produce)
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive(), "blocking submit deadlocked"
        for f in futs:
            f.result(timeout=120)
    assert server.stats()["requests_completed"] == 8


def test_backpressure_block_policy_sync_mode_drains_inline():
    """Caller-driven mode has no admission thread to free space: 'block'
    must drain inline instead of self-deadlocking a single thread."""
    rng = np.random.default_rng(28)
    server = EeiServer(PLAN, max_batch=8, max_pending=2,
                       pending_policy="block")
    futs = [server.submit(_sym(rng, 8), 1) for _ in range(5)]
    server.flush()
    assert all(f.result().eigenvalues.shape == (1,) for f in futs)


def test_validation_of_runtime_parameters():
    with pytest.raises(ValueError):
        EeiServer(PLAN, linger_ms=-1)
    with pytest.raises(ValueError):
        EeiServer(PLAN, max_pending=-1)
    with pytest.raises(ValueError):
        EeiServer(PLAN, pending_policy="drop")


# ---------------------------------------------------------------------------
# Concurrency: producer threads racing the linger thread, cache locking
# ---------------------------------------------------------------------------


def test_producer_threads_race_linger_admission():
    """N producer threads racing submit() against the linger thread: no
    deadlock (every join is timeout-bounded), no lost or duplicated
    futures, and ProgramCache hits + misses == dispatch count."""
    rng = np.random.default_rng(30)
    n_threads, per_thread = 4, 8
    mats = [[(_sym(rng, int(n)), int(k))
             for n, k in zip(rng.choice([6, 8, 12], per_thread),
                             rng.integers(1, 3, per_thread))]
            for _ in range(n_threads)]
    futs_per_thread = [[] for _ in range(n_threads)]
    with EeiServer(PLAN, max_batch=4, linger_ms=1, cache=SHARED_CACHE,
                   record_dispatches=True) as server:
        before = (server.cache.hits, server.cache.misses)

        def produce(i):
            for a, k in mats[i]:
                futs_per_thread[i].append(server.submit(a, k))

        threads = [threading.Thread(target=produce, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "producer thread deadlocked"
        futs = [f for fs in futs_per_thread for f in fs]
        for f in futs:
            f.result(timeout=120)
        _assert_accounting(server, futs, before)
        assert server.stats()["requests_failed"] == 0
        _assert_stream_conformant(server)


def test_program_cache_concurrent_gets_compile_once():
    """Racing get()s for one bucket must compile exactly once and return
    the same executable — the cache lock covers the compile."""
    cache = ProgramCache()
    bucket = ShapeBucket(2, 16, 2, True)
    results = [None] * 4
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        results[i] = cache.get(bucket, PLAN, jnp.float32)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert all(r is results[0] and r is not None for r in results)
    assert cache.compiles == 1 and len(cache) == 1
    assert cache.hits + cache.misses == 4


# ---------------------------------------------------------------------------
# Property-based stream conformance (the fuzzer the runtime is locked by)
# ---------------------------------------------------------------------------

# One request: (n, k_raw, largest, action-after-submit). k = 1 + k_raw % n
# keeps k valid for any n. Actions: 0/3 nothing, 1 pump(), 2 sleep (lets
# the linger thread fire mid-stream / exercises odd pump timing).
_REQ = st.tuples(st.integers(4, 12), st.integers(0, 1), st.booleans(),
                 st.integers(0, 3))


def _run_stream(server, ops, seed):
    rng = np.random.default_rng(seed)
    futs = []
    for n, k_raw, largest, action in ops:
        futs.append(server.submit(_sym(rng, n), 1 + k_raw % n,
                                  largest=largest))
        if action == 1:
            server.pump()
        elif action == 2:
            time.sleep(0.002)
    return futs


@settings(max_examples=8, deadline=None)
@given(ops=st.lists(_REQ, min_size=1, max_size=20),
       max_batch=st.sampled_from([1, 2, 4]), seed=st.integers(0, 999))
def test_stream_conformance_fuzz_caller_driven(ops, max_batch, seed):
    """Random heterogeneous (n, k, largest) streams with random pump
    timing, caller-driven mode: bitwise identity against the synchronous
    SolverEngine.topk oracle on every dispatched stack, and every submit
    resolves exactly once."""
    server = EeiServer(PLAN, max_batch=max_batch, cache=SHARED_CACHE,
                       record_dispatches=True)
    before = (server.cache.hits, server.cache.misses)
    futs = _run_stream(server, ops, seed)
    server.flush()
    _assert_accounting(server, futs, before)
    assert server.stats()["requests_failed"] == 0
    assert sum(len(r.requests) for r in server.dispatch_log) == len(ops)
    _assert_stream_conformant(server)


@settings(max_examples=6, deadline=None)
@given(ops=st.lists(_REQ, min_size=1, max_size=16),
       max_batch=st.sampled_from([2, 4]),
       linger_ms=st.sampled_from([0.0, 1.0, 5.0]),
       seed=st.integers(0, 999))
def test_stream_conformance_fuzz_linger_thread(ops, max_batch, linger_ms,
                                               seed):
    """The same conformance contract under the threaded runtime: random
    linger timeouts and random sleeps decide how stacks form, but every
    grouping must stay bitwise-equal to the sync oracle, with no flush()
    ever called — futures resolve via the linger thread, and close()
    drains the tail."""
    server = EeiServer(PLAN, max_batch=max_batch, linger_ms=linger_ms,
                       cache=SHARED_CACHE, record_dispatches=True)
    before = (server.cache.hits, server.cache.misses)
    try:
        futs = _run_stream(server, ops, seed)
        for f in futs:
            f.result(timeout=120)  # linger thread must complete the stream
    finally:
        server.close(timeout=120)
    _assert_accounting(server, futs, before)
    assert server.stats()["requests_failed"] == 0
    assert sum(len(r.requests) for r in server.dispatch_log) == len(ops)
    _assert_stream_conformant(server)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 12), pad=st.integers(1, 8), seed=st.integers(0, 999),
       largest=st.booleans(), scale=st.sampled_from([1e-2, 1.0, 1e2]))
def test_property_guard_embedding_never_enters_window(n, pad, seed, largest,
                                                      scale):
    """Guard-diagonal embedding invariant at both spectrum extremes: the
    guard value sits strictly outside the spectrum on the far side, so the
    padded matrix's top-n (or bottom-n) eigenvalues are exactly A's and
    guard eigenpairs can never enter any requested k-window."""
    rng = np.random.default_rng(seed)
    a = (scale * _sym(rng, n)).astype(np.float32)
    server = EeiServer(PLAN)
    guard = server._guard_value(a, largest)
    w = np.linalg.eigvalsh(a.astype(np.float64))
    if largest:
        assert guard < w[0]  # strictly below: never in a top-k window
    else:
        assert guard > w[-1]  # strictly above: never in a bottom-k window
    padded = np.zeros((n + pad, n + pad), dtype=np.float64)
    padded[:n, :n] = a
    idx = np.arange(n, n + pad)
    padded[idx, idx] = guard
    wp = np.linalg.eigvalsh(padded)
    window = wp[pad:] if largest else wp[:n]
    guards = wp[:pad] if largest else wp[n:]
    np.testing.assert_allclose(window, w, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(guards, guard, rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# Chaos conformance: the stream contract must survive injected faults.
#
# Under chaos the *bitwise* oracle and the cache-accounting invariants do
# not apply (NaN-poisoned rows resolve through the fallback chain, and
# ``on_launch`` fires after ``cache.get`` so hits+misses can exceed
# dispatches) — the contract that must hold instead is the safety one:
# every future resolves exactly once, nothing non-finite or garbage ever
# reaches a caller, degraded results are marked, and the server never
# wedges (every wait below is timeout-bounded).
# ---------------------------------------------------------------------------


def _assert_chaos_safe(reqs, stats):
    """``reqs`` is ``[(a, k, future), ...]``; asserts the chaos-safety
    contract over resolved results + the server's counter accounting."""
    degraded = 0
    for a, k, fut in reqs:
        assert fut.done(), "a submitted future never resolved"
        res = fut.result(timeout=0)
        lam, vec = np.asarray(res.eigenvalues), np.asarray(res.vectors)
        assert lam.shape == (k,) and vec.shape == (k, a.shape[0])
        assert np.all(np.isfinite(lam)) and np.all(np.isfinite(vec))
        # Independent garbage check: healthy float32 residuals are ~3e-4
        # of ||A||_F and clamped-denominator garbage is >= ~0.1; 2e-2
        # cleanly separates them even after guard-padding skews the
        # device-side scale.
        flags = verify_topk_host(a, lam, vec)
        assert float(flags.residual) <= 2e-2, (
            f"garbage reached a caller: residual={float(flags.residual)}")
        if res.degraded:
            degraded += 1
            assert res.fallback, "degraded result missing its chain link"
    assert stats["requests_failed"] == 0
    assert stats["requests_completed"] == len(reqs)
    assert stats["requests_degraded"] == degraded


@settings(max_examples=5, deadline=None)
@given(ops=st.lists(_REQ, min_size=4, max_size=16),
       max_batch=st.sampled_from([2, 4]),
       rate=st.sampled_from([0.05, 0.1]),
       seed=st.integers(0, 999), chaos_seed=st.integers(0, 999))
def test_chaos_stream_conformance_fuzz(ops, max_batch, rate, seed,
                                       chaos_seed):
    """Random heterogeneous streams under 5-10% injected faults (compile /
    launch failures, NaN-poisoned results, slow retires, thread crashes):
    the safety contract holds — every future resolves exactly once with a
    finite, non-garbage result, and the server survives to serve the whole
    stream."""
    chaos = ChaosMonkey(ChaosConfig(seed=chaos_seed, rate=rate,
                                    slow_s=0.001))
    server = EeiServer(PLAN, max_batch=max_batch, linger_ms=1.0,
                       cache=SHARED_CACHE, chaos=chaos)
    rng = np.random.default_rng(seed)
    reqs = []
    try:
        for n, k_raw, largest, action in ops:
            a, k = _sym(rng, n), 1 + k_raw % n
            reqs.append((a, k, server.submit(a, k, largest=largest)))
            if action == 1:
                server.pump()
            elif action == 2:
                time.sleep(0.002)
        for _, _, f in reqs:
            f.result(timeout=120)
    finally:
        server.close(timeout=120)
    stats = server.stats()
    _assert_chaos_safe(reqs, stats)
    assert stats["chaos_injected"] == chaos.counts()


# ---------------------------------------------------------------------------
# Sharded serving (forced 2-device host mesh in a subprocess: the device
# count must be set before jax initializes, which this process already did)
# ---------------------------------------------------------------------------

_SHARDED_SERVE_SCRIPT = """
import jax, numpy as np, jax.numpy as jnp
assert jax.device_count() == 2, jax.device_count()
from repro.engine import EeiServer, SolverEngine, SolverPlan, plan_for

mesh = jax.make_mesh((2, 1), ("data", "model"))
plan = SolverPlan(method="eei_tridiag", backend="sharded", mesh=mesh)
rng = np.random.default_rng(0)

def sym(n):
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2

# per-bucket auto-planning picks the sharded backend for big-enough stacks
auto = plan_for((4, 48, 48), k=2, mesh=mesh)
assert auto.backend == "sharded", auto

with EeiServer(plan, max_batch=4, linger_ms=5,
               record_dispatches=True) as server:
    futs = [server.submit(sym(n), 2) for n in (12, 12, 16, 12, 9)]
    for f in futs:
        f.result(timeout=240)  # no flush: linger thread drives dispatch
    assert server.stats()["requests_completed"] == 5
    # pow2 buckets round up to the mesh batch axis; bitwise vs the sync
    # sharded oracle on the same padded stack
    for rec in server.dispatch_log:
        assert rec.bucket.b % 2 == 0, rec.bucket
        ref = SolverEngine(rec.plan).topk(
            jnp.asarray(rec.stack), rec.bucket.k, rec.bucket.largest)
        lam = np.asarray(ref.eigenvalues)
        vec = np.asarray(ref.vectors)
        for row, req in enumerate(rec.requests):
            res = req.future.result()
            np.testing.assert_array_equal(res.eigenvalues, lam[row, -req.k:])
            np.testing.assert_array_equal(res.vectors,
                                          vec[row, -req.k:, : req.n])
print("sharded serve OK")
"""


def test_sharded_serve_on_forced_two_device_host_mesh():
    """The sharded backend through the full server path (linger thread,
    bucket rounding to the mesh batch axis) on a 2-device host mesh."""
    import repro.engine

    # repro is a namespace package (__file__ is None) — derive src/ from a
    # concrete module inside it.
    src_dir = str(Path(repro.engine.__file__).parents[2])
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SERVE_SCRIPT], env=env,
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "sharded serve OK" in proc.stdout


# ---------------------------------------------------------------------------
# Stress lane (-m slow): heavier thread stress + sparse-stream serve smoke
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_thread_stress_producers_vs_linger():
    """8 producer threads x 25 mixed requests racing the linger thread with
    backpressure on: timeout-bounded (deadlock fails, never hangs), full
    future/cache accounting, bitwise conformance on every stack."""
    rng = np.random.default_rng(40)
    n_threads, per_thread = 8, 25
    streams = [[(_sym(rng, int(n)), int(k), bool(largest))
                for n, k, largest in zip(
                    rng.choice([6, 8, 12, 16], per_thread),
                    rng.integers(1, 4, per_thread),
                    rng.integers(0, 2, per_thread))]
               for _ in range(n_threads)]
    futs_per_thread = [[] for _ in range(n_threads)]
    with EeiServer(PLAN, max_batch=8, linger_ms=1, max_pending=64,
                   pending_policy="block", cache=SHARED_CACHE,
                   record_dispatches=True) as server:
        before = (server.cache.hits, server.cache.misses)

        def produce(i):
            local_rng = np.random.default_rng(100 + i)
            for a, k, largest in streams[i]:
                if local_rng.random() < 0.2:
                    time.sleep(local_rng.random() * 0.002)
                futs_per_thread[i].append(
                    server.submit(a, min(k, a.shape[0]), largest=largest))

        threads = [threading.Thread(target=produce, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive(), "producer thread deadlocked"
        futs = [f for fs in futs_per_thread for f in fs]
        for f in futs:
            f.result(timeout=300)
        _assert_accounting(server, futs, before)
        assert server.stats()["requests_failed"] == 0
        _assert_stream_conformant(server)


@pytest.mark.slow
def test_sparse_stream_serve_smoke():
    """Sparse-stream serve smoke: a mixed stream with inter-arrival gaps
    completes through the linger thread alone (no flush anywhere), within
    the timeout, bitwise-equal to the sync oracle, with compiles bounded
    by distinct buckets."""
    stream = make_eei_stream(48, 16, 4, seed=41, mixed=True)
    rng = np.random.default_rng(42)
    with EeiServer(None, max_batch=8, linger_ms=3, cache=SHARED_CACHE,
                   record_dispatches=True) as server:
        before = (server.cache.hits, server.cache.misses)
        futs = []
        for a, k in stream:
            time.sleep(rng.exponential(0.001))
            futs.append(server.submit(a, k))
        for f in futs:
            f.result(timeout=300)
        stats = server.stats()
        assert stats["requests_completed"] == len(stream)
        _assert_accounting(server, futs, before)
        _assert_stream_conformant(server)


@pytest.mark.slow
def test_chaos_stress_threaded_producers():
    """Chaos soak: 4 producer threads x 40 mixed heterogeneous requests
    racing the linger thread with backpressure on and ~8% injected faults
    across every injection point.  Timeout-bounded end to end; asserts the
    full chaos-safety contract plus that the deterministic monkey actually
    fired (a silent no-injection run would vacuously pass)."""
    chaos = ChaosMonkey(ChaosConfig(seed=7, rate=0.08, slow_s=0.002))
    n_threads = 4
    streams = [make_eei_stream(40, 16, 4, seed=50 + i, mixed=True)
               for i in range(n_threads)]
    reqs_per_thread = [[] for _ in range(n_threads)]
    with EeiServer(PLAN, max_batch=8, linger_ms=1, max_pending=64,
                   pending_policy="block", chaos=chaos) as server:

        def produce(i):
            local_rng = np.random.default_rng(200 + i)
            for a, k in streams[i]:
                if local_rng.random() < 0.2:
                    time.sleep(local_rng.random() * 0.002)
                reqs_per_thread[i].append((a, k, server.submit(a, k)))

        threads = [threading.Thread(target=produce, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive(), "producer thread deadlocked"
        reqs = [r for rs in reqs_per_thread for r in rs]
        for _, _, f in reqs:
            f.result(timeout=300)
        stats = server.stats()
    _assert_chaos_safe(reqs, stats)
    assert sum(chaos.counts().values()) > 0, "chaos never fired"
    assert stats["chaos_injected"] == chaos.counts()


# ---------------------------------------------------------------------------
# Review regressions: cancellation, close(drain=False) retirement, fair
# key selection, cache compile-failure propagation
# ---------------------------------------------------------------------------


def test_sync_close_without_drain_still_retires_inflight():
    """Caller-driven close(drain=False): stacks already on device must
    retire (their futures resolve with results), queued ones fail."""
    rng = np.random.default_rng(50)
    server = EeiServer(PLAN, max_batch=2)
    inflight = [server.submit(_sym(rng, 12), 2) for _ in range(2)]  # full
    assert server.stats()["stacks_dispatched"] == 1
    queued = server.submit(_sym(rng, 12), 2)  # partial group stays queued
    server.close(drain=False)
    for f in inflight:
        assert f.result(timeout=60).eigenvalues.shape == (2,)
    with pytest.raises(ServerClosed):
        queued.result(timeout=60)


def test_cancelled_future_does_not_poison_the_stack():
    """A caller cancelling its future must not crash the retire path or
    lose the other requests riding the same stack.  Since PR 5 a cancel on
    a still-*pending* request also pulls it from its coalesce group, so it
    never pads a stack."""
    rng = np.random.default_rng(51)
    with EeiServer(PLAN, max_batch=8, linger_ms=60_000,
                   cache=SHARED_CACHE) as server:
        futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
        assert futs[1].cancel()  # still queued: cancellable + dequeued
        server.flush()
        for f in (futs[0], futs[2]):
            assert f.result(timeout=120).eigenvalues.shape == (2,)
        assert futs[1].cancelled()
    stats = server.stats()
    assert stats["requests_cancelled"] == 1
    assert stats["requests_completed"] == 2  # the cancelled one never rode


def test_cancel_pending_request_never_pads_a_stack():
    """PR-4 follow-up (cancellation): a cancel() on an undispatched request
    removes it from its group — the dispatched bucket shrinks to the live
    requests instead of carrying a dead row."""
    rng = np.random.default_rng(53)
    server = EeiServer(PLAN, max_batch=8)
    futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
    assert futs[1].cancel()
    server.flush()
    stats = server.stats()
    assert stats["requests_cancelled"] == 1
    assert stats["requests_completed"] == 2
    # pow2 bucket of the 2 surviving requests — not of the original 3.
    assert server.cache.buckets()[-1].b == 2
    for f in (futs[0], futs[2]):
        assert f.result(timeout=60).eigenvalues.shape == (2,)
    # A cancel landing *after* dispatch rides the stack: device work is
    # spent either way, and retirement tolerates the resolved future.
    futs2 = [server.submit(_sym(rng, 16), 1) for _ in range(8)]
    assert server.stats()["stacks_dispatched"] == 2  # full stack went out
    cancelled_late = futs2[0].cancel()
    server.flush()
    for f in futs2[1:]:
        assert f.result(timeout=60).eigenvalues.shape == (1,)
    stats = server.stats()
    assert stats["requests_cancelled"] == 1  # late cancel is not a dequeue
    if cancelled_late:
        assert futs2[0].cancelled()


def test_cancelled_backpressure_slot_is_released():
    """Cancelling a pending request frees its max_pending slot — a blocked
    producer must make progress without any dispatch happening."""
    rng = np.random.default_rng(54)
    server = EeiServer(PLAN, max_batch=8, max_pending=2,
                       pending_policy="except")
    f0 = server.submit(_sym(rng, 12), 1)
    server.submit(_sym(rng, 12), 1)
    with pytest.raises(QueueFull):
        server.submit(_sym(rng, 12), 1)
    assert f0.cancel()
    f3 = server.submit(_sym(rng, 12), 1)  # slot released by the cancel
    server.flush()
    assert f3.result(timeout=60).eigenvalues.shape == (1,)


def test_ready_key_selection_is_fifo_across_keys():
    """An expired partial group must outrank a younger full group: the
    oldest head request wins, so a hot key cannot starve a lingered one."""
    import collections
    from concurrent.futures import Future as _Future

    from repro.engine.server import _Request

    rng = np.random.default_rng(52)
    server = EeiServer(PLAN, max_batch=2)  # sync mode: threads stay out
    server.linger_ms = 10.0  # only _ready_key_locked reads it here
    now = time.monotonic()
    r_old = _Request(a=_sym(rng, 16), n=16, k=1, largest=True,
                     future=_Future(), t_submit=now - 1.0)
    r_new = [_Request(a=_sym(rng, 24), n=24, k=1, largest=True,
                      future=_Future(), t_submit=now) for _ in range(2)]
    with server._cv:
        server._queues[(24, True)] = collections.deque(r_new)  # full, young
        server._queues[(16, True)] = collections.deque([r_old])  # expired
        key, deadline = server._ready_key_locked(now)
    assert key == (16, True), "expired older head must win over full young"
    assert deadline is None


def test_program_cache_failed_compile_raises_everywhere_and_retries():
    """A failing compile must propagate to concurrent same-bucket waiters
    and be evicted so the next get() retries."""
    cache = ProgramCache()
    bucket = ShapeBucket(2, 16, 2, True)
    calls = {"n": 0}
    import repro.engine.engine as engine_mod
    real = engine_mod.topk_program

    def flaky(plan, k, largest, verify=False):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("synthetic compile failure")
        return real(plan, k, largest, verify)

    engine_mod.topk_program, orig = flaky, engine_mod.topk_program
    try:
        with pytest.raises(RuntimeError, match="synthetic"):
            cache.get(bucket, PLAN, jnp.float32)
        assert len(cache) == 0  # evicted: retry is possible
        prog = cache.get(bucket, PLAN, jnp.float32)  # retries and succeeds
        assert prog is not None and len(cache) == 1
    finally:
        engine_mod.topk_program = orig


# ---------------------------------------------------------------------------
# Packed ragged dispatch (PR 9): small-n requests coalesce into block-
# diagonal segment-packed rows.  The conformance contract is per-request
# (not per-stack-bitwise): every packed result must match the bucketed
# SolverEngine.topk oracle on the request's own unpadded matrix to float32
# tolerance, every future resolves exactly once, and the cancel/chaos
# safety lanes hold unchanged with packing on.
# ---------------------------------------------------------------------------

# Packable request: n small enough to pack (pack_row_n=64 default), k <= 4.
_PACK_REQ = st.tuples(st.integers(1, 32), st.integers(0, 3), st.booleans(),
                      st.integers(0, 3))


def _assert_packed_oracle_match(reqs):
    """``reqs`` is ``[(a, k, largest, future), ...]``: each packed result
    must agree with the bucketed oracle on the unpadded matrix — same
    eigenvalues to float32 tolerance, unit-norm vectors with small
    residuals (vector *entries* are not compared bitwise: a packed row
    solves a different — block-diagonal — matrix, so signs and degenerate
    rotations may differ while the eigenpairs are equally correct)."""
    for a, k, largest, fut in reqs:
        res = fut.result(timeout=120)
        lam = np.asarray(res.eigenvalues)
        vec = np.asarray(res.vectors)
        n = a.shape[0]
        assert lam.shape == (k,) and vec.shape == (k, n)
        # eigh-method oracle: the tridiag reference chain cannot solve
        # n=1 (minor bands need n >= 2), but packed rows accept it.
        ref = SolverEngine(SolverPlan(method="eigh", backend="jnp")).topk(
            jnp.asarray(a), k, largest)
        ref_lam = np.asarray(ref.eigenvalues)
        scale = max(1.0, float(np.max(np.abs(ref_lam))))
        np.testing.assert_allclose(lam, ref_lam, atol=5e-4 * scale, rtol=0)
        fro = max(1.0, float(np.linalg.norm(a)))
        res_norm = np.linalg.norm(a @ vec.T - vec.T * lam, axis=0)
        assert np.max(res_norm) <= 5e-3 * fro
        norms = np.linalg.norm(vec, axis=1)
        np.testing.assert_allclose(norms, 1.0, atol=2e-3)


@settings(max_examples=6, deadline=None)
@given(ops=st.lists(_PACK_REQ, min_size=1, max_size=20),
       max_batch=st.sampled_from([1, 2, 4]), seed=st.integers(0, 999))
def test_packed_stream_conformance_fuzz_caller_driven(ops, max_batch, seed):
    """Random packable streams under pack='always', caller-driven mode:
    per-request oracle conformance, exactly-once future resolution, and
    every stack actually went down the packed path."""
    server = EeiServer(max_batch=max_batch, pack="always",
                       cache=SHARED_CACHE, record_dispatches=True)
    rng = np.random.default_rng(seed)
    reqs = []
    for n, k_raw, largest, action in ops:
        a, k = _sym(rng, n), 1 + k_raw % n
        reqs.append((a, k, largest, server.submit(a, k, largest=largest)))
        if action == 1:
            server.pump()
        elif action == 2:
            time.sleep(0.002)
    server.flush()
    stats = server.stats()
    assert stats["requests_failed"] == 0
    assert stats["requests_completed"] == len(ops)
    assert stats["packed_stacks_dispatched"] == stats["stacks_dispatched"]
    assert all(isinstance(rec.bucket, PackedBucket)
               for rec in server.dispatch_log)
    for rec in server.dispatch_log:  # layout parallels requests exactly
        assert len(rec.layout) == len(rec.requests)
    _assert_packed_oracle_match(reqs)


@settings(max_examples=5, deadline=None)
@given(ops=st.lists(_PACK_REQ, min_size=1, max_size=16),
       linger_ms=st.sampled_from([0.0, 1.0, 5.0]),
       seed=st.integers(0, 999))
def test_packed_stream_conformance_fuzz_linger_thread(ops, linger_ms, seed):
    """The packed conformance contract under the threaded runtime: linger
    timing decides how rows fill, but every future must resolve with an
    oracle-conformant result and no flush() ever called."""
    server = EeiServer(max_batch=2, pack="always", linger_ms=linger_ms,
                       cache=SHARED_CACHE)
    rng = np.random.default_rng(seed)
    reqs = []
    try:
        for n, k_raw, largest, action in ops:
            a, k = _sym(rng, n), 1 + k_raw % n
            reqs.append((a, k, largest,
                         server.submit(a, k, largest=largest)))
            if action == 2:
                time.sleep(0.002)
        for _, _, _, f in reqs:
            f.result(timeout=120)
    finally:
        server.close(timeout=120)
    stats = server.stats()
    assert stats["requests_failed"] == 0
    assert stats["packed_stacks_dispatched"] == stats["stacks_dispatched"]
    _assert_packed_oracle_match(reqs)


@settings(max_examples=4, deadline=None)
@given(ops=st.lists(_PACK_REQ, min_size=4, max_size=12),
       rate=st.sampled_from([0.05, 0.1]),
       seed=st.integers(0, 999), chaos_seed=st.integers(0, 999))
def test_packed_chaos_stream_fuzz(ops, rate, seed, chaos_seed):
    """The chaos safety contract with packing on: injected compile/launch
    failures, NaN results, slow retires and thread crashes — every future
    still resolves exactly once with a finite, verified result."""
    chaos = ChaosMonkey(ChaosConfig(seed=chaos_seed, rate=rate,
                                    slow_s=0.001))
    server = EeiServer(max_batch=2, pack="always", linger_ms=1.0,
                       cache=SHARED_CACHE, chaos=chaos)
    rng = np.random.default_rng(seed)
    reqs = []
    try:
        for n, k_raw, largest, action in ops:
            a, k = _sym(rng, n), 1 + k_raw % n
            reqs.append((a, k, server.submit(a, k, largest=largest)))
            if action == 2:
                time.sleep(0.002)
        for _, _, f in reqs:
            f.result(timeout=120)
    finally:
        server.close(timeout=120)
    _assert_chaos_safe(reqs, server.stats())


def test_packed_cancel_lanes():
    """Cancellation with packing on: a pending cancel dequeues the request
    (it never rides a packed row); a post-dispatch cancel rides the stack
    and retirement tolerates the resolved future."""
    rng = np.random.default_rng(60)
    with EeiServer(max_batch=8, pack="always", pack_row_n=16,
                   linger_ms=60_000, cache=SHARED_CACHE) as server:
        futs = [server.submit(_sym(rng, 12), 2) for _ in range(3)]
        assert futs[1].cancel()
        server.flush()
        for f in (futs[0], futs[2]):
            assert f.result(timeout=120).eigenvalues.shape == (2,)
        assert futs[1].cancelled()
        stats = server.stats()
        assert stats["requests_cancelled"] == 1
        assert stats["requests_completed"] == 2
        assert stats["packed_stacks_dispatched"] == 1
        # Late cancel: the packed group is already on device.  16 n=8
        # requests fill the pack-group cap (max_batch=8 rows x 2 slots
        # at pack_row_n=16), so the admission thread dispatches without
        # waiting out the linger; poll for it (the dispatch is async).
        futs2 = [server.submit(_sym(rng, 8), 1) for _ in range(16)]
        deadline = time.monotonic() + 60
        while (server.stats()["stacks_dispatched"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert server.stats()["stacks_dispatched"] == 2  # full pack group
        cancelled_late = futs2[0].cancel()
        server.flush()
        for f in futs2[1:]:
            assert f.result(timeout=120).eigenvalues.shape == (1,)
        if cancelled_late:
            assert futs2[0].cancelled()


def test_packed_stream_compiles_fewer_programs_than_bucketed():
    """The structural packing win: a mixed small-n stream executes through
    fewer distinct compiled programs packed than bucketed (one packed row
    shape covers every small n; the bucketed path compiles per distinct
    footprint)."""
    sizes = [8, 12, 16, 24, 32, 40]
    rng = np.random.default_rng(61)
    stream = [(_sym(rng, n), 2) for n in sizes for _ in range(4)]
    counts = {}
    for mode in ("never", "always"):
        server = EeiServer(max_batch=4, pack=mode, pack_row_n=64,
                           cache=ProgramCache())
        for a, k in stream:
            server.submit(a, k)
        server.flush()
        server.close()
        stats = server.stats()
        assert stats["requests_completed"] == len(stream)
        counts[mode] = (stats["distinct_buckets"],
                        stats["stacks_dispatched"])
    assert counts["always"][0] < counts["never"][0], counts
    assert counts["always"][1] <= counts["never"][1], counts


# ---------------------------------------------------------------------------
# Pad-waste accounting (PR 9 bugfix): cells are counted once per
# successfully *retired* stack, so retries / splits / redispatch cannot
# inflate the counters relative to a clean run of the same stream.
# ---------------------------------------------------------------------------


def test_pad_waste_counted_at_retire_not_dispatch():
    """Regression for the over-reporting bug: a dispatched-but-unretired
    stack contributes nothing; the cells land exactly when the stack
    retires."""
    rng = np.random.default_rng(62)
    server = EeiServer(PLAN, max_batch=2, max_inflight=2)
    futs = [server.submit(_sym(rng, 12), 2) for _ in range(2)]  # full stack
    assert server.stats()["stacks_dispatched"] == 1
    assert server.stats()["grid_cells_total"] == 0  # on device, not retired
    server.flush()
    stats = server.stats()
    assert stats["grid_cells_total"] == 2 * 16 * 16
    assert stats["grid_cells_real"] == 2 * 12 * 12
    for f in futs:
        assert f.result(timeout=60).eigenvalues.shape == (2,)
    server.close()


@pytest.mark.parametrize("pack", ["never", "always"])
def test_pad_waste_matches_clean_run_under_chaos_launch_failures(pack):
    """The satellite bugfix end-to-end: a stream served under injected
    *transient* launch failures (retried in place, never split) must report
    exactly the clean run's cell counters — before the fix every retried
    stack's cells were counted once per launch attempt path taken."""
    rng = np.random.default_rng(63)
    stream = [(_sym(rng, int(rng.integers(4, 25))), 2) for _ in range(12)]

    def run(chaos):
        server = EeiServer(max_batch=2, pack=pack, cache=SHARED_CACHE,
                           chaos=chaos, max_retries=64,
                           retry_backoff_s=1e-4, retry_backoff_cap_s=1e-3)
        futs = [server.submit(a, k) for a, k in stream]
        server.flush()
        for f in futs:
            f.result(timeout=120)
        server.close()
        return server.stats()

    clean = run(None)
    # High per-point rate: the packed mode serves the whole stream in only
    # a couple of launches, so a modest rate can (deterministically, by
    # seed) miss every one and leave the chaos-fired assertion vacuous.
    chaos = ChaosMonkey(ChaosConfig(seed=7, rate=0.0, launch_rate=0.75))
    chaotic = run(chaos)
    assert chaotic["chaos_injected"].get("launch", 0) > 0  # chaos did fire
    assert chaotic["retries"] > 0
    for key in ("grid_cells_total", "grid_cells_real", "pad_waste_frac",
                "pad_waste_by_bucket", "requests_completed"):
        assert chaotic[key] == clean[key], (key, chaotic[key], clean[key])
    # Launches are *supposed* to differ: stacks_dispatched counts launches.
    assert chaotic["stacks_dispatched"] == clean["stacks_dispatched"]


# ---------------------------------------------------------------------------
# Bucket-edge properties (PR 9 hardening): n=1, k=n, n below the align
# granule — the padded shape always covers the real one, the pow2 k window
# never truncates a request, and guards stay outside the requested window.
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 40), k_raw=st.integers(0, 63),
       count=st.integers(1, 9), largest=st.booleans())
def test_property_bucket_edges_cover_request(n, k_raw, count, largest):
    k = 1 + k_raw % n  # k in [1, n] — k=n hit whenever k_raw % n == n-1
    bn = _bucket_n(n, 8)
    assert bn >= n and bn % 8 == 0 and bn - n < 8
    bucket = ShapeBucket.for_requests(count, n, k, largest)
    assert bucket.n == bn
    assert bucket.b >= count
    assert k <= bucket.k <= bucket.n  # the pow2 window never truncates
    assert blocks.pow2_bucket(bucket.b) == bucket.b
    assert blocks.clamp_block(128, n) == bn  # block clamp = same granule


@settings(max_examples=40, deadline=None)
@given(lengths=st.lists(st.integers(1, 48), min_size=1, max_size=24),
       max_slots=st.integers(1, 8))
def test_property_pack_segments_layout(lengths, max_slots):
    """pack_segments structural invariants: every input appears exactly
    once, offsets are align-granular, footprints never overlap or overflow
    the row, and no row exceeds max_slots."""
    row_width = 64
    rows = blocks.pack_segments(lengths, row_width, max_slots, align=8)
    seen = []
    for row in rows:
        assert 1 <= len(row) <= max_slots
        end = 0
        for idx, off, length in row:
            seen.append(idx)
            assert length == lengths[idx]
            assert off % 8 == 0 and off >= end  # aligned, non-overlapping
            end = off + (-(-length // 8) * 8)
            assert end <= row_width
    assert sorted(seen) == list(range(len(lengths)))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 12), pad=st.integers(1, 8), seed=st.integers(0, 999),
       largest=st.booleans(), scale=st.sampled_from([1e-2, 1.0, 1e2]))
def test_property_guard_value_at_edges(n, pad, seed, largest, scale):
    """_guard_value stays strictly outside the spectrum down to n=1 (the
    degenerate Gershgorin radius-0 case the bucket-edge sweep covers)."""
    rng = np.random.default_rng(seed)
    a = (scale * _sym(rng, n)).astype(np.float32)
    server = EeiServer(PLAN)
    guard = server._guard_value(a, largest)
    w = np.linalg.eigvalsh(a.astype(np.float64))
    if largest:
        assert guard < w[0]
    else:
        assert guard > w[-1]


# ---------------------------------------------------------------------------
# serve.py CLI degenerate streams (PR 9 bugfix): a --requests 0 run must
# drain cleanly through every mode — the stats rollups guard their empty
# denominators and the final `futures[-1].result()` no longer IndexErrors.
# ---------------------------------------------------------------------------


@pytest.fixture()
def cli_cache_dir():
    """``serve.py``'s main turns the persistent compile cache on; keep that
    from outliving the CLI test in this worker process."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("extra", [[], ["--sync"], ["--linger-ms", "1"],
                                   ["--pack", "always"]],
                         ids=["server", "sync", "linger", "packed"])
def test_serve_cli_zero_request_stream(extra, cli_cache_dir):
    from repro.launch import serve as serve_cli

    assert serve_cli.main(["--eei", "--requests", "0", "--n", "12",
                           "--k", "2", *extra]) is None


def test_serve_cli_exits_nonzero_on_degraded_requests(monkeypatch,
                                                      cli_cache_dir):
    """Without --chaos, a request that resolves through the host fallback
    chain means the device path did not serve it: the CLI exits non-zero
    instead of logging counters and exiting 0."""
    from repro.engine import engine as engine_mod
    from repro.launch import serve as serve_cli

    def broken(*args, **kwargs):
        raise RuntimeError("device program unavailable")

    monkeypatch.setattr(engine_mod, "topk_program", broken)
    with pytest.raises(SystemExit) as exc:
        serve_cli.main(["--eei", "--requests", "2", "--n", "12", "--k", "2"])
    assert "degraded" in str(exc.value.code)


def test_serve_cli_packed_stream_smoke(cli_cache_dir):
    """--eei --pack always on a small mixed stream: the CLI serves it end
    to end and returns the final request's result."""
    from repro.launch import serve as serve_cli

    out = serve_cli.main(["--eei", "--requests", "6", "--n", "16", "--k",
                          "2", "--mixed", "--pack", "always"])
    assert out is not None and np.all(np.isfinite(np.asarray(out.eigenvalues)))
