"""Serving launcher: batched LM decode, or batched EEI top-k queries.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
        --batch 4 --prompt-len 32 --gen 16

Demonstrates the production decode path: prefill fills sharded KV caches,
then ``serve_step`` (one token, cache update in place via donated buffers)
iterates.  Request batching is static (continuous batching is an orthogonal
scheduler concern; the cache layout supports it — position is per-batch
scalar here for the dry-run shapes).

The EEI mode serves the paper's workload — streams of top-k eigenpair
queries over many small symmetric matrices — through the continuous-batching
``repro.engine.EeiServer`` (queue -> coalesce -> shape buckets -> program
cache -> async double-buffered dispatch):

    PYTHONPATH=src python -m repro.launch.serve --eei --batch 8 --n 64 \
        --k 4 --requests 64 [--mixed] [--sync] [--linger-ms 2] \
        [--gap-ms 1] [--sharded] [--spectrum auto|full|windowed] \
        [--chaos SEED] [--chaos-rate 0.05] \
        [--replicas 3 [--replica-mode subprocess] [--chaos-replicas]]

``--mixed`` samples ``n`` and ``k`` per request (the heterogeneous stream
the server exists for); ``--sync`` runs the PR-2-style synchronous
per-request loop instead (the baseline the server is benchmarked against).
``--linger-ms`` turns on the threaded serving runtime: a background
admission thread dispatches partial stacks once their oldest request has
lingered that long, so the stream completes with *no* ``flush()`` — pair it
with ``--gap-ms`` (mean inter-arrival sleep) to emulate the sparse stream
the linger thread exists for.  ``--sharded`` serves through the multi-device
mesh from ``--mesh`` (the server rounds pow2 stack buckets up to the mesh
batch axis); force host devices off-TPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
``--chaos SEED`` runs a soak: deterministic fault injection (compile and
launch failures, NaN-poisoned results, slow retires, thread crashes) at
``--chaos-rate`` per injection point — the stream must still complete, with
the robustness counters (verify failures, retries, stack splits, degraded
resolutions, per-plan fallbacks, injections) logged at the end.
``--replicas N`` serves through an ``EeiFleet`` of N replica servers
(rendezvous-hashed routing, health probes, failover redispatch, restart);
``--replica-mode subprocess`` isolates each replica in its own process,
and ``--chaos-replicas`` arms the replica-level kill/hang/slow points so
replicas die mid-stream while every request must still resolve.
The request stream is generated *before* the timed region either way.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_config, reduced_config
from repro.launch import mesh as mesh_lib
from repro.launch.train import parse_mesh
from repro.runtime.compile_cache import enable_compile_cache
from repro.models.lm import LanguageModel
from repro.train import build_programs
from repro.train.steps import cast_tree

log = logging.getLogger("repro.serve")


def serve_eei(args):
    """Serve a pre-generated stream of top-k spectral queries.

    Default: continuous batching through ``EeiServer``.  ``--sync``: the
    synchronous per-request loop (one engine.topk + block_until_ready per
    matrix) — the PR-2 baseline the server's ≥2x requests/s claim is
    measured against.
    """
    from repro.engine import EeiServer, SolverEngine, autotune, plan_for, \
        resolved_crossovers
    from repro.engine.server import SPANS, make_eei_stream

    if args.calibration:
        autotune.set_table(autotune.load_table(args.calibration))
    table = autotune.get_table()

    mesh = parse_mesh(args.mesh)
    if args.sharded and mesh.shape["data"] < 2:
        raise SystemExit(
            "--sharded needs a multi-device data axis; pass --mesh DxM and "
            "(off-TPU) XLA_FLAGS=--xla_force_host_platform_device_count=N")
    serve_mesh = mesh if mesh.devices.size > 1 else None
    plan = plan_for((args.batch, args.n, args.n), k=args.k, mesh=serve_mesh,
                    backend="sharded" if args.sharded else None,
                    spectrum=None if args.spectrum == "auto" else
                    args.spectrum)
    # Crossovers are backend-specific since schema v2 — log the pair the
    # resolved plan's backend actually dispatches on.
    eigh_x, dense_x = resolved_crossovers(plan.backend)
    log.info("plan calibration: %s (backend=%s eigh_crossover_n=%d "
             "dense_crossover_n=%d)",
             table.source if table else "static fallback constants",
             plan.backend, eigh_x, dense_x)
    mode = "sync-loop" if args.sync else (
        f"continuous-batching linger={args.linger_ms}ms"
        if args.linger_ms is not None else "continuous-batching")
    if args.mixed and not args.sync:
        # The server re-plans per shape bucket; the fixed plan above is
        # only the log's reference point for the nominal (batch, n, k).
        log.info("eei serve: per-bucket planning, max_batch=%d nominal "
                 "n=%d k=%d mode=%s mixed-shapes", args.batch, args.n,
                 args.k, mode)
    else:
        log.info("eei serve plan: method=%s backend=%s spectrum=%s "
                 "max_batch=%d n=%d k=%d mode=%s", plan.method, plan.backend,
                 plan.spectrum, args.batch, args.n, args.k, mode)

    # The stream is generated before t0 — only serving is timed.
    stream = make_eei_stream(args.requests, args.n, args.k,
                             seed=args.seed, mixed=args.mixed)

    gap_s = (args.gap_ms or 0.0) / 1e3
    rng = np.random.default_rng(args.seed)
    if args.sync:
        engine = SolverEngine(plan)
        # Warmup compiles outside the timed region, like the server path.
        for n_i in sorted({a.shape[0] for a, _ in stream}):
            for k_i in sorted({k for a, k in stream if a.shape[0] == n_i}):
                jax.block_until_ready(
                    engine.topk(jnp.zeros((n_i, n_i), jnp.float32), k_i))
        t0 = time.monotonic()
        out = None
        for a, k_i in stream:
            if gap_s:
                # The sync baseline pays the same arrival gaps as the
                # server path, so --sync vs --linger-ms comparisons at
                # equal flags stay apples-to-apples.
                time.sleep(rng.exponential(gap_s))
            out = engine.topk(jnp.asarray(a), k_i)
            jax.block_until_ready(out)
        dt = time.monotonic() - t0
        log.info("sync loop served %d requests in %.3fs (%.1f solves/s, "
                 "%.1f requests/s)", len(stream), dt,
                 len(stream) / max(dt, 1e-9), len(stream) / max(dt, 1e-9))
        return out

    if args.replicas > 1:
        return _serve_eei_fleet(args, stream, gap_s, rng)

    chaos = None
    if args.chaos is not None:
        from repro.runtime import ChaosConfig, ChaosMonkey

        chaos = ChaosMonkey(ChaosConfig(seed=args.chaos,
                                        rate=args.chaos_rate))
        log.info("chaos soak: seed=%d rate=%.3f (deterministic injection "
                 "at compile/launch/result/retire/thread points)",
                 args.chaos, args.chaos_rate)
    # --mixed uses per-bucket planning (plan=None + the serve mesh); a
    # fixed nominal shape pins the one plan computed above.
    server = EeiServer(plan if args.mixed is False else None,
                       max_batch=args.batch, max_inflight=args.inflight,
                       linger_ms=args.linger_ms,
                       mesh=serve_mesh if args.mixed else None,
                       pack=args.pack, chaos=chaos)
    t0 = time.monotonic()
    futures = []
    for a, k_i in stream:
        if gap_s:
            time.sleep(rng.exponential(gap_s))  # sparse Poisson-ish arrivals
        futures.append(server.submit(a, k_i))
    if args.linger_ms is not None:
        # The whole point of the linger thread: the stream drains with no
        # explicit flush — just wait on the completion futures.
        for f in futures:
            f.result(timeout=600)
    else:
        server.flush()
    dt = time.monotonic() - t0
    server.close()
    stats = server.stats()
    log.info("served %d requests in %.3fs (%.1f solves/s, %.1f requests/s)",
             len(stream), dt, len(stream) / max(dt, 1e-9),
             len(stream) / max(dt, 1e-9))
    log.info("latency p50=%.1fms p99=%.1fms | %d stacks, %d program "
             "compiles over %d distinct buckets, %d cache hits",
             stats["p50_latency_ms"], stats["p99_latency_ms"],
             stats["stacks_dispatched"], stats["program_compiles"],
             stats["distinct_buckets"], stats["program_hits"])
    # The server's span counters: host phases and device wait per stack
    # (the compile is part of launch), queue wait and Lanczos steps per
    # request.
    stacks = max(stats["stacks_dispatched"], 1)
    served = max(stats["requests_completed"], 1)
    phases = ", ".join(
        f"{name}={stats[key] / stacks / 1e6:.3f}" for name, key in
        [(name, f"{name}_ns") for name in SPANS if name != "session_update"]
        + [("compile", "program_compile_ns")])
    log.info("per stack (ms): %s | per request: queue wait %.3f ms, "
             "%.1f Lanczos steps", phases,
             stats["queue_wait_ns"] / served / 1e6,
             stats["lanczos_steps"] / served)
    per_bucket = ", ".join(
        f"{name}={frac:.3f}"
        for name, frac in sorted(stats["pad_waste_by_bucket"].items()))
    log.info("pad waste %.3f (%d of %d grid cells padding) | per bucket: %s",
             stats["pad_waste_frac"],
             stats["grid_cells_total"] - stats["grid_cells_real"],
             stats["grid_cells_total"], per_bucket or "none")
    if stats["packed_stacks_dispatched"]:
        log.info("packed dispatch (--pack=%s): %d of %d stacks packed, "
                 "%d requests packed | pad waste packed=%.3f bucketed=%.3f",
                 args.pack, stats["packed_stacks_dispatched"],
                 stats["stacks_dispatched"],
                 stats["packed_requests_completed"],
                 stats["pad_waste_packed_frac"],
                 stats["pad_waste_bucketed_frac"])
    by_plan = ", ".join(f"{name}={count}" for name, count in
                        sorted(stats["fallbacks_by_plan"].items()))
    log.info("robustness: %d verify failures, %d retries, %d stack splits, "
             "%d degraded | fallbacks: %s",
             stats["verify_failed"], stats["retries"], stats["stack_splits"],
             stats["requests_degraded"], by_plan or "none")
    if chaos is not None:
        injected = ", ".join(f"{point}={count}" for point, count in
                             sorted(stats["chaos_injected"].items()))
        log.info("chaos injected: %s | requests_failed=%d",
                 injected or "none", stats["requests_failed"])
    _require_clean(futures, chaos_armed=chaos is not None)
    # A zero-request stream (--requests 0: config smoke, drained replay)
    # has no futures — the rollups above already guard division by zero /
    # empty percentiles; returning None instead of futures[-1] keeps the
    # degenerate run from dying with IndexError after serving nothing.
    return futures[-1].result() if futures else None


def _serve_eei_fleet(args, stream, gap_s, rng):
    """Serve the stream through an ``EeiFleet`` of ``--replicas`` servers.

    ``--chaos-replicas`` arms the replica-level injection points
    (kill / hang / slow, each at ``--chaos-rate``, seeded by ``--chaos``)
    — replicas die *while serving* and the stream must still complete,
    with the failover counters logged at the end.
    """
    from repro.engine import EeiFleet

    chaos = None
    if args.chaos_replicas:
        from repro.runtime import ChaosConfig, ChaosMonkey

        seed = args.chaos if args.chaos is not None else 0
        chaos = ChaosMonkey(ChaosConfig(
            seed=seed, rate=0.0, replica_kill_rate=args.chaos_rate,
            replica_hang_rate=args.chaos_rate / 2,
            replica_slow_rate=args.chaos_rate))
        log.info("replica chaos soak: seed=%d kill/slow rate=%.3f "
                 "hang rate=%.3f", seed, args.chaos_rate,
                 args.chaos_rate / 2)
    fleet = EeiFleet(
        args.replicas,
        replica_mode=args.replica_mode,
        server_kwargs=dict(
            max_batch=args.batch, max_inflight=args.inflight,
            linger_ms=args.linger_ms if args.linger_ms is not None else 2.0,
            pack=args.pack),
        chaos=chaos,
        restart_policy_kwargs=dict(max_restarts=1000),
    )
    log.info("eei fleet: %d %s replicas, max_batch=%d", args.replicas,
             args.replica_mode, args.batch)
    t0 = time.monotonic()
    futures = []
    for a, k_i in stream:
        if gap_s:
            time.sleep(rng.exponential(gap_s))
        futures.append(fleet.submit(a, k_i))
    for f in futures:
        f.result(timeout=600)
    dt = time.monotonic() - t0
    stranded = fleet.close(timeout=120)
    stats = fleet.stats()
    log.info("fleet served %d requests in %.3fs (%.1f requests/s) | "
             "%d unresolved at close", len(stream), dt,
             len(stream) / max(dt, 1e-9), len(stranded))
    log.info("fleet latency p50=%.1fms p99=%.1fms | states=%s",
             stats["p50_latency_ms"], stats["p99_latency_ms"],
             stats["replica_states"])
    log.info("failover: %d redispatches, %d hedges (%d wasted), "
             "%d kills, %d restarts, %d deadline deaths",
             stats["redispatches"], stats["hedges"], stats["hedge_wasted"],
             stats["replicas_killed"], stats["replicas_restarted"],
             stats["deadline_deaths"])
    if chaos is not None:
        injected = ", ".join(f"{point}={count}" for point, count in
                             sorted(stats["chaos_injected"].items())
                             if count)
        log.info("chaos injected: %s | requests_failed=%d",
                 injected or "none", stats["requests_failed"])
    _require_clean(futures, chaos_armed=chaos is not None)
    return futures[-1].result() if futures else None


def _require_clean(futures, chaos_armed: bool) -> None:
    """Exit non-zero when a request resolved degraded or failed and no
    chaos was armed to explain it: a degraded result took the host
    fallback chain, so the run did not show the device path serving."""
    failed = sum(f.exception() is not None for f in futures)
    degraded = sum(bool(getattr(f.result(), "degraded", False))
                   for f in futures if f.exception() is None)
    if (failed or degraded) and not chaos_armed:
        raise SystemExit(
            f"eei serve: {degraded} of {len(futures)} requests resolved "
            f"degraded and {failed} failed without --chaos")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS))
    ap.add_argument("--eei", action="store_true",
                    help="serve batched EEI top-k queries instead of an LM")
    ap.add_argument("--n", type=int, default=64, help="EEI matrix size")
    ap.add_argument("--k", type=int, default=4, help="EEI top-k per query")
    ap.add_argument("--requests", type=int, default=64,
                    help="EEI requests (single-matrix queries) to serve")
    ap.add_argument("--mixed", action="store_true",
                    help="EEI: sample n and k per request (heterogeneous "
                    "stream through the shape-bucketed server)")
    ap.add_argument("--sync", action="store_true",
                    help="EEI: synchronous per-request loop instead of the "
                    "continuous-batching server (baseline)")
    ap.add_argument("--pack", choices=["auto", "never", "always"],
                    default="never",
                    help="EEI: segment-packed dispatch — coalesce small-n "
                    "requests into block-diagonal packed rows ('auto': "
                    "pack below the calibrated crossover; 'always': pack "
                    "anything that fits a row; default 'never' keeps the "
                    "pure shape-bucketed path)")
    ap.add_argument("--spectrum", choices=["auto", "full", "windowed"],
                    default="auto",
                    help="EEI: pin the stage composition — 'windowed' "
                    "computes only the k requested extremal rows (the "
                    "k-windowed Sturm + minor-determinant path), 'full' "
                    "the whole table; 'auto' lets the calibrated planner "
                    "pick per bucket (windowed_k_frac crossover)")
    ap.add_argument("--inflight", type=int, default=2,
                    help="EEI server: max in-flight stacks (double "
                    "buffering = 2)")
    ap.add_argument("--linger-ms", type=float, default=None,
                    help="EEI: run the threaded serving runtime — a "
                    "background admission thread dispatches partial stacks "
                    "after this linger timeout (no explicit flush)")
    ap.add_argument("--gap-ms", type=float, default=0.0,
                    help="EEI: mean inter-arrival sleep between submits "
                    "(emulates the sparse stream the linger thread serves)")
    ap.add_argument("--sharded", action="store_true",
                    help="EEI: serve through the sharded backend on the "
                    "--mesh data axis (stack buckets round up to it)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="EEI: soak mode — deterministically inject faults "
                    "(compile/launch failures, NaN-poisoned results, slow "
                    "retires, thread crashes) from this seed and log the "
                    "robustness counters; the stream must still complete")
    ap.add_argument("--chaos-rate", type=float, default=0.05,
                    help="EEI: per-injection-point chaos probability "
                    "(default 0.05; only with --chaos)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="EEI: serve through an EeiFleet of this many "
                    "replica servers (health-probed routing, failover "
                    "redispatch, restart); 1 = single server")
    ap.add_argument("--replica-mode", choices=["inprocess", "subprocess"],
                    default="inprocess",
                    help="EEI fleet: replica driver — in-process servers "
                    "sharing one program cache, or one worker process per "
                    "replica (true process isolation and parallelism)")
    ap.add_argument("--chaos-replicas", action="store_true",
                    help="EEI fleet: arm replica-level chaos (kill/hang/"
                    "slow at --chaos-rate, seeded by --chaos) — replicas "
                    "die mid-stream and the fleet must still answer "
                    "every request")
    ap.add_argument("--calibration", default=None,
                    help="path to an autotune calibration table (JSON); "
                    "default: env/cache/repo-default resolution chain")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    if args.eei:
        return serve_eei(args)
    if args.arch is None:
        ap.error("--arch is required unless --eei is given")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = parse_mesh(args.mesh)
    model = LanguageModel(cfg)
    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    smax = args.prompt_len + args.gen

    rng = jax.random.PRNGKey(args.seed)
    with mesh:
        programs = build_programs(model, mesh, compute_dtype=compute_dtype)
        params = jax.jit(
            model.init, out_shardings=programs.state_shardings.params
        )(rng)
        batch = {"tokens": jax.random.randint(
            rng, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
        batch["labels"] = batch["tokens"]
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                rng, (args.batch, cfg.enc_seq, cfg.d_model)) * 0.02
        if cfg.family == "vlm":
            batch["images"] = jax.random.normal(
                rng, (args.batch, cfg.img_seq, cfg.d_model)) * 0.02

        t0 = time.monotonic()
        logits, caches = jax.jit(
            lambda p, b: model.prefill(cast_tree(p, compute_dtype), b, smax),
            out_shardings=(None, programs.cache_shardings),
        )(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        log.info("prefill %.3fs (B=%d, S=%d)", time.monotonic() - t0,
                 args.batch, args.prompt_len)

        out_tokens = [np.asarray(tok)]
        t0 = time.monotonic()
        for i in range(args.gen - 1):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            tok, caches = programs.serve_step(params, caches, tok, pos)
            out_tokens.append(np.asarray(tok))
        dt = time.monotonic() - t0
        gen = np.stack(out_tokens, axis=1)
        log.info("decode %d tokens x %d seqs in %.3fs (%.1f tok/s)",
                 gen.shape[1], gen.shape[0], dt,
                 gen.size / max(dt, 1e-9))
        print(gen)
    return gen


if __name__ == "__main__":
    main()
