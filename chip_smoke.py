#!/usr/bin/env python3
"""Prove that the EEI solve/serve path runs, and answers correctly, on a TPU.

    python chip_smoke.py [--seed 0]      # one chip: phases (a)-(c)
    python chip_smoke.py --four-chips    # four chips: the sharded phase only

One process, float32, every matrix made from ``--seed``.  Each phase goes
through the entry points a user calls, with the planner's own plan choice:

  (a) served   64 single-matrix top-k queries through ``EeiServer``
               (n in {256, 1024}, k in {4, 16}, both extremes,
               ``max_batch=16``), plus one n=256 bucket pinned to
               ``spectrum="full"`` so the minor-spectra Sturm and the
               prod_diff kernels run too;
  (b) large    one n=8192 matrix with a known spectrum (a spiked bulk,
               rotated by seeded Householder reflectors) through
               ``SolverEngine.topk``, k=16;
  (c) session  ``EeiServer.open_session`` at n=1024, k=8, then 16 rank-1
               updates.

``--four-chips`` runs only the multi-chip path: the sharded backend serving
phase (a)'s n=1024, k=16 requests as one b=16 stack on a 4x1 ``data`` mesh,
compared with the one-chip result on device 0 and with the oracle.

Every result is checked against a float64 host oracle at the phase's own
size: the eigenvalue error and the residual of ``verify_topk_host``, both
in units of ``||A||_F`` and both within ``verify.DEFAULT_TOL``.  A phase
fails on any degraded result, fallback, verify failure, retry, stack split
or session host reseed, and on a plan that is not the pallas backend.

The script exits non-zero, printing no result, when JAX finds no TPU or the
``repro`` package is not beside it.  Each phase prints one JSON line (plan,
wall and compile seconds, oracle errors, counters, cache directory); the
last line is ``{"ok": true, "device": {...}}`` as JAX reports the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

#: Server counters that must stay zero in a clean run.
_SERVER_FAULTS = ("verify_failed", "retries", "stack_splits",
                  "requests_degraded", "requests_failed", "session_degraded")


def _require_repro() -> None:
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


class CompileMeter:
    """Seconds of XLA compilation (or of fetching an executable from the
    persistent cache instead) while the meter is active."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _active: "list[CompileMeter]" = []
    _registered = False

    def __init__(self):
        self.seconds = 0.0

    @classmethod
    def _listen(cls, event: str, duration: float, **_) -> None:
        if event == cls._EVENT:
            for meter in cls._active:
                meter.seconds += duration

    def __enter__(self) -> "CompileMeter":
        import jax

        if not CompileMeter._registered:
            jax.monitoring.register_event_duration_secs_listener(
                CompileMeter._listen)
            CompileMeter._registered = True
        CompileMeter._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        CompileMeter._active.remove(self)


def goe(rng: np.random.Generator, n: int) -> np.ndarray:
    """A float32 GOE matrix, as ``make_eei_stream`` draws them."""
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


def oracle_errors(a: np.ndarray, lam, vec, k: int, largest: bool,
                  lam_ref=None) -> dict:
    """Float64 host check of one top-k answer, in units of ``||A||_F``."""
    from repro.engine.verify import verify_topk_host

    a64 = np.asarray(a, np.float64)
    lam = np.asarray(lam, np.float64)
    vec = np.asarray(vec, np.float64)
    if lam_ref is None:
        lam_all = np.linalg.eigvalsh(a64)
        lam_ref = lam_all[-k:] if largest else lam_all[:k]
    scale = max(float(np.linalg.norm(a64)), 1e-30)
    flags = verify_topk_host(a64, lam, vec)
    return {"eig_err": float(np.max(np.abs(lam - lam_ref))) / scale,
            "residual": float(flags.residual), "verify_ok": bool(flags.ok)}


def _worst(errs: list) -> dict:
    return {"eig_err": max(e["eig_err"] for e in errs),
            "residual": max(e["residual"] for e in errs),
            "verify_ok": all(e["verify_ok"] for e in errs),
            "checked": len(errs)}


def _plan_label(plan) -> dict:
    return {"method": plan.method, "backend": plan.backend,
            "spectrum": plan.spectrum}


def _server_faults(stats: dict) -> dict:
    faults = {key: stats[key] for key in _SERVER_FAULTS}
    faults["fallbacks"] = sum(stats["fallbacks_by_plan"].values())
    return faults


def _collect(futures: list) -> list:
    return [fut.result(timeout=1200) for fut in futures]


def phase_served(seed: int, ns=(256, 1024), ks=(4, 16), per_cell: int = 7,
                 full_n: int = 256, full_k: int = 16, full_count: int = 8,
                 max_batch: int = 16) -> dict:
    """(a) A stream of single-matrix queries through ``EeiServer``."""
    from repro.engine import EeiServer, plan_for

    rng = np.random.default_rng(seed)
    windowed = [(goe(rng, n), k, largest) for n in ns for k in ks
                for largest in (True, False) for _ in range(per_cell)]
    full = [(goe(rng, full_n), full_k, True) for _ in range(full_count)]
    full_plan = dataclasses.replace(
        plan_for((full_count, full_n, full_n), k=full_k), spectrum="full")
    t0 = time.perf_counter()
    with CompileMeter() as meter:
        servers = [EeiServer(max_batch=max_batch, record_dispatches=True),
                   EeiServer(full_plan, max_batch=max_batch,
                             record_dispatches=True)]
        futures = [[srv.submit(a, k, lg) for a, k, lg in reqs]
                   for srv, reqs in zip(servers, (windowed, full))]
        for srv in servers:
            srv.flush()
        results = [_collect(f) for f in futures]
    wall = time.perf_counter() - t0
    for srv in servers:
        srv.close()
    errs = [oracle_errors(a, r.eigenvalues, r.vectors, k, lg)
            for reqs, res in zip((windowed, full), results)
            for (a, k, lg), r in zip(reqs, res)]
    plans, counters = [], {}
    for srv in servers:
        for rec in srv.dispatch_log:
            label = dict(_plan_label(rec.plan), bucket=(
                f"b{rec.bucket.b}n{rec.bucket.n}k{rec.bucket.k}"
                + ("L" if rec.bucket.largest else "S")))
            if label not in plans:
                plans.append(label)
        for key, val in _server_faults(srv.stats()).items():
            counters[key] = counters.get(key, 0) + val
    counters["degraded_results"] = sum(
        bool(r.degraded) for res in results for r in res)
    counters["requests"] = len(windowed) + len(full)
    return {"phase": "served", "plans": plans, "wall_s": wall,
            "compile_s": meter.seconds, "oracle": _worst(errs),
            "counters": counters}


def spiked_matrix(seed: int, n: int, k: int, reflectors: int = 4):
    """``A = Q diag(lam) Q^T`` (float64) with a known spectrum: a uniform
    bulk in [0, 1] and ``k`` spikes in [2, 2 + 0.2 (k - 1)], rotated by
    ``Q``, a product of seeded Householder reflectors.  Returns ``(A, top
    k eigenvalues ascending, their eigenvectors as rows)``."""
    rng = np.random.default_rng(seed)
    lam = np.sort(np.concatenate([rng.uniform(0.0, 1.0, n - k),
                                  2.0 + 0.2 * np.arange(k)]))
    vs = rng.standard_normal((reflectors, n))
    vs /= np.linalg.norm(vs, axis=1, keepdims=True)
    a = np.diag(lam)
    for v in vs:  # A <- H A H, H = I - 2 v v^T
        w = a @ v
        a -= 2.0 * (np.outer(v, w) + np.outer(w, v))
        a += 4.0 * float(v @ w) * np.outer(v, v)
    vec = np.eye(n)[n - k:]  # rows e_i of the top-k, then rotated by Q
    for v in vs:
        vec -= 2.0 * np.outer(vec @ v, v)
    return a, lam[n - k:], vec


def phase_large(seed: int, n: int = 8192, k: int = 16) -> dict:
    """(b) One large matrix through ``SolverEngine.topk``."""
    import jax

    from repro.engine import SolverEngine

    a64, lam_ref, _ = spiked_matrix(seed, n, k)
    a = jax.device_put(a64.astype(np.float32))
    engine = SolverEngine.for_problem((n, n), k=k)
    t0 = time.perf_counter()
    with CompileMeter() as meter:
        res = jax.block_until_ready(engine.topk(a, k))
    wall = time.perf_counter() - t0
    errs = oracle_errors(a64, res.eigenvalues, res.vectors, k, True,
                         lam_ref=lam_ref)
    return {"phase": "large", "plans": [_plan_label(engine.plan)],
            "wall_s": wall, "compile_s": meter.seconds,
            "oracle": _worst([errs]), "counters": {"n": n, "k": k}}


def phase_session(seed: int, n: int = 1024, k: int = 8,
                  updates: int = 16) -> dict:
    """(c) A stateful session through ``EeiServer``: open, then rank-1
    updates, every answer checked against the updated matrix."""
    from repro.engine import EeiServer, plan_for

    rng = np.random.default_rng(seed)
    a = goe(rng, n).astype(np.float64)
    us = (rng.standard_normal((updates, n)) * np.sqrt(2.0 / n)).astype(
        np.float32)
    signs = [-1 if i % 4 == 3 else 1 for i in range(updates)]
    srv = EeiServer()
    t0 = time.perf_counter()
    errs = []
    with CompileMeter() as meter:
        sid = srv.open_session(a, k)
        results = []
        for u, sign in zip(us, signs):
            results.append(srv.submit_update(sid, u, sign).result(
                timeout=1200))
            a = a + sign * np.outer(u.astype(np.float64), u)
            errs.append(oracle_errors(a, results[-1].eigenvalues,
                                      results[-1].vectors, k, True))
    wall = time.perf_counter() - t0
    session = srv.session_stats(sid)
    counters = _server_faults(srv.stats())
    counters.update(
        degraded_results=sum(bool(r.degraded) for r in results),
        host_reseeds=session["host_reseeds"],
        verify_resolves=session["resolves_by_cause"].get("verify", 0),
        fast_updates=session["fast_updates"],
        full_resolves=session["full_resolves"])
    srv.close()
    return {"phase": "session", "plans": [_plan_label(plan_for(
        (1, n, n), k=k))], "wall_s": wall, "compile_s": meter.seconds,
        "oracle": _worst(errs), "counters": counters}


def phase_four_chips(seed: int, n: int = 1024, k: int = 16,
                     b: int = 16, chips: int = 4) -> dict:
    """The sharded serving path on a ``chips x 1`` data mesh, compared with
    the one-chip server on device 0 and with the oracle."""
    import jax
    import jax.numpy as jnp

    from repro.engine import EeiServer

    rng = np.random.default_rng(seed)
    mats = [goe(rng, n) for _ in range(b)]
    mesh = jax.make_mesh((chips, 1), ("data", "model"))
    t0 = time.perf_counter()
    with CompileMeter() as meter:
        sharded = EeiServer(mesh=mesh, max_batch=b, record_dispatches=True)
        single = EeiServer(max_batch=b)
        futs = [[srv.submit(a, k) for a in mats] for srv in (sharded, single)]
        for srv in (sharded, single):
            srv.flush()
        res_sharded, res_single = (_collect(f) for f in futs)
        rec = sharded.dispatch_log[0]
        program = sharded.cache.get(rec.bucket, rec.plan, sharded.dtype,
                                    verify=True)
        out, _ = program(jnp.asarray(rec.stack))
        devices = len(out.vectors.sharding.device_set)
    wall = time.perf_counter() - t0
    errs = [oracle_errors(a, r.eigenvalues, r.vectors, k, True)
            for res in (res_sharded, res_single) for a, r in zip(mats, res)]
    # One chip vs four: eigenvalues in ||A||_F units, vectors up to sign.
    gap = max(float(np.max(np.abs(np.asarray(s.eigenvalues, np.float64)
                                  - np.asarray(o.eigenvalues, np.float64))))
              / float(np.linalg.norm(a))
              for a, s, o in zip(mats, res_sharded, res_single))
    overlap = min(float(np.min(np.abs(np.sum(
        np.asarray(s.vectors, np.float64) * np.asarray(o.vectors, np.float64),
        axis=-1)))) for s, o in zip(res_sharded, res_single))
    counters = {}
    for srv in (sharded, single):
        for key, val in _server_faults(srv.stats()).items():
            counters[key] = counters.get(key, 0) + val
        srv.close()
    counters.update(
        degraded_results=sum(bool(r.degraded)
                             for r in res_sharded + res_single),
        output_devices=devices)
    return {"phase": "four_chips", "plans": [_plan_label(rec.plan)],
            "wall_s": wall, "compile_s": meter.seconds,
            "oracle": _worst(errs), "counters": counters,
            "vs_one_chip": {"eig_gap": gap, "min_vector_overlap": overlap}}


def problems(record: dict, backend: str = "pallas") -> list:
    """Why a phase record fails the smoke contract (empty: it passes)."""
    from repro.engine.verify import DEFAULT_TOL

    out = []
    for plan in record["plans"]:
        if plan["backend"] != backend:
            out.append(f"plan ran on backend={plan['backend']}, "
                       f"not {backend}")
    for key, val in record["counters"].items():
        if key in _SERVER_FAULTS + ("fallbacks", "degraded_results",
                                    "host_reseeds", "verify_resolves") \
                and val:
            out.append(f"{key}={val}")
    oracle = record["oracle"]
    if not oracle["verify_ok"]:
        out.append("oracle residual check failed")
    for key in ("eig_err", "residual"):
        if not oracle[key] <= DEFAULT_TOL:
            out.append(f"oracle {key}={oracle[key]:.3g} > {DEFAULT_TOL}")
    cmp = record.get("vs_one_chip")
    if cmp is not None:
        if record["counters"]["output_devices"] != 4:
            out.append(f"output spans {record['counters']['output_devices']}"
                       " devices, not 4")
        if not cmp["eig_gap"] <= DEFAULT_TOL:
            out.append(f"four-chip vs one-chip eigenvalue gap "
                       f"{cmp['eig_gap']:.3g}")
        if not cmp["min_vector_overlap"] >= 1.0 - DEFAULT_TOL:
            out.append(f"four-chip vs one-chip vector overlap "
                       f"{cmp['min_vector_overlap']:.6f}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4x1 data mesh")
    args = ap.parse_args(argv)
    _require_repro()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found "
              f"{devices[0].platform!r} devices only", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    if args.four_chips:
        phases = [(phase_four_chips, "sharded")]
    else:
        phases = [(phase_served, "pallas"), (phase_large, "pallas"),
                  (phase_session, "pallas")]
    ok = True
    for phase, backend in phases:
        try:
            record = phase(args.seed)
            record["problems"] = problems(record, backend)
        except Exception as exc:  # a phase that raises fails the smoke
            traceback.print_exc()
            record = {"phase": phase.__name__, "problems": [repr(exc)]}
        record["cache_dir"] = cache_dir
        ok &= not record["problems"]
        print(json.dumps(record), flush=True)
    if not ok:
        print("chip_smoke: a phase failed (see its problems)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
