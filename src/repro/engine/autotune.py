"""Measured planner calibration: block shapes + method crossovers per host.

The planner's method heuristics (``plan.EIGH_CROSSOVER_N`` /
``DENSE_CROSSOVER_N``) and the Pallas kernels' tile shapes are
hardware-dependent — the paper's own Table 1 shows the eigh/EEI crossover
moving with the BLAS backing.  This module closes the loop from measurement
to dispatch:

* :func:`calibrate` sweeps kernel block shapes, method crossovers and the
  windowed-composition ``k / n`` crossover with the same timing harness as
  ``benchmarks/throughput.py`` and returns a :class:`CalibrationTable`;
* tables persist as JSON — per-host under ``~/.cache/repro/`` (or
  ``$REPRO_CALIBRATION``), with a repo-checked default
  (``calibration_default.json``) so fresh checkouts plan from measured
  numbers, not guesses;
* :func:`get_table` is the process-global resolution the planner
  (``plan.resolved_crossovers``) and the pallas backend (kernel blocks)
  consult; the static constants in ``plan.py`` remain only as the
  uncalibrated fallback when no table can be found.

Resolution order: :func:`set_table` override > ``$REPRO_CALIBRATION`` path >
``~/.cache/repro/calibration.json`` > the repo default.  Regenerate with::

    PYTHONPATH=src python -m repro.engine.autotune [--smoke] [--out PATH]
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import platform
import time
from pathlib import Path
from typing import Optional, Sequence

import jax

from repro.engine.plan import WINDOWED_K_FRAC

log = logging.getLogger("repro.autotune")

CALIBRATION_ENV = "REPRO_CALIBRATION"
CACHE_PATH = Path.home() / ".cache" / "repro" / "calibration.json"
REPO_DEFAULT_PATH = Path(__file__).with_name("calibration_default.json")

#: v1 (PR 2): jnp-only crossovers, 3-tuple prod_diff blocks (bb fixed at 1).
#: v2 (PR 3) adds the batch tile ``prod_diff_block_b`` and pallas-backend
#: crossover measurements.  v3 (PR 5) adds ``windowed_k_frac`` — the
#: measured ``k / n`` fraction at/below which the planner routes top-k
#: queries through the windowed stage composition.  v4 (PR 6) adds
#: ``krylov_n_min`` — the measured ``n`` at/above which the Lanczos partial
#: reduce beats the dense Householder reduce for narrow top-k windows.
#: v5 (PR 9) adds the packed-dispatch pair: ``pack_n_max`` — the largest
#: bucketed ``n`` whose requests are worth coalescing into segment-packed
#: rows — and ``packed_eigh_n_max`` — the packed *row width* at/below which
#: the packed chain pins the LAPACK eigh composition (above it the
#: segmented-Sturm tridiagonal chain wins).
#: Older tables still load (warn once per process + defaults for the
#: missing fields): a v2 table plans windows from the static
#: ``plan.WINDOWED_K_FRAC`` fallback exactly like an uncalibrated host, a
#: v3 table routes Krylov from the static ``plan.KRYLOV_N_MIN``, a v4
#: table packs from the static ``plan.PACK_N_MAX`` / ``PACKED_EIGH_N_MAX``.
_SCHEMA_VERSION = 5


@dataclasses.dataclass(frozen=True)
class CalibrationTable:
    """One host class's measured pipeline constants (see module docstring)."""

    eigh_crossover_n: int  # n below which LAPACK eigh wins outright (jnp)
    dense_crossover_n: int  # n up to which dense minors beat tridiag (jnp)
    prod_diff_blocks: tuple  # (block_i, block_j, block_k)
    sturm_blocks: tuple  # (block_b, block_m)
    prod_diff_block_b: int = 1  # bb — matrices per batch-grid step
    pallas_eigh_crossover_n: Optional[int] = None  # None -> use jnp value
    pallas_dense_crossover_n: Optional[int] = None  # None -> use jnp value
    windowed_k_frac: float = WINDOWED_K_FRAC  # k/n below which windowed wins
    krylov_n_min: Optional[int] = None  # n at which krylov reduce wins;
    # None -> the static plan.KRYLOV_N_MIN fallback (pre-v4 tables)
    pack_n_max: Optional[int] = None  # largest bucketed n worth packing;
    # None -> the static plan.PACK_N_MAX fallback (pre-v5 tables)
    packed_eigh_n_max: Optional[int] = None  # packed row width at/below
    # which the packed chain pins eigh; None -> plan.PACKED_EIGH_N_MAX
    host: str = ""  # host class the numbers were measured on
    backend: str = ""  # jax backend (cpu | tpu | gpu) at measurement
    measured_at: str = ""  # ISO timestamp, empty for hand-written tables
    source: str = "memory"  # where the table was loaded from

    def crossovers_for(self, backend: Optional[str] = None) -> tuple:
        """``(eigh_crossover_n, dense_crossover_n)`` for a plan backend.

        The pallas kernels amortize differently than fused jnp (the paper's
        Table 1 shows the crossover moving with the BLAS backing, and it
        moves again with kernelized EEI), so v2 tables carry a second
        measured pair; any other backend — and v1 tables, whose pallas
        fields are None — falls back to the jnp pair.
        """
        if backend == "pallas" and self.pallas_eigh_crossover_n is not None:
            return (self.pallas_eigh_crossover_n,
                    self.pallas_dense_crossover_n
                    if self.pallas_dense_crossover_n is not None
                    else self.dense_crossover_n)
        return self.eigh_crossover_n, self.dense_crossover_n

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("source")
        d["schema_version"] = _SCHEMA_VERSION
        d["prod_diff_blocks"] = list(self.prod_diff_blocks)
        d["sturm_blocks"] = list(self.sturm_blocks)
        return d

    @classmethod
    def from_dict(cls, d: dict, source: str = "memory") -> "CalibrationTable":
        version = int(d.get("schema_version", _SCHEMA_VERSION))
        if version > _SCHEMA_VERSION:
            raise ValueError(
                f"calibration table schema_version {version} is newer than "
                f"this code understands ({_SCHEMA_VERSION})")
        if version < _SCHEMA_VERSION:
            _warn_once(
                (source, version),
                "calibration table %s has schema_version %d (current %d); "
                "loading with defaults for the missing fields (v1: bb=1 + "
                "pallas crossovers from the jnp sweep; v2: windowed_k_frac "
                "from the static fallback) — re-run "
                "`python -m repro.engine.autotune` to refresh it",
                source, version, _SCHEMA_VERSION)

        def _opt_int(key):
            return int(d[key]) if d.get(key) is not None else None

        return cls(
            eigh_crossover_n=int(d["eigh_crossover_n"]),
            dense_crossover_n=int(d["dense_crossover_n"]),
            prod_diff_blocks=tuple(int(x) for x in d["prod_diff_blocks"]),
            sturm_blocks=tuple(int(x) for x in d["sturm_blocks"]),
            prod_diff_block_b=int(d.get("prod_diff_block_b", 1)),
            pallas_eigh_crossover_n=_opt_int("pallas_eigh_crossover_n"),
            pallas_dense_crossover_n=_opt_int("pallas_dense_crossover_n"),
            windowed_k_frac=float(
                d.get("windowed_k_frac", WINDOWED_K_FRAC)),
            krylov_n_min=_opt_int("krylov_n_min"),
            pack_n_max=_opt_int("pack_n_max"),
            packed_eigh_n_max=_opt_int("packed_eigh_n_max"),
            host=str(d.get("host", "")),
            backend=str(d.get("backend", "")),
            measured_at=str(d.get("measured_at", "")),
            source=source,
        )

    def save(self, path: Path) -> Path:
        path = Path(path).expanduser()
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_dict(), indent=2) + "\n")
        return path


#: (source, version) pairs already warned about — old-schema tables get
#: re-loaded freely (serve --calibration, tests, every fresh ``load_table``
#: call), and repeating the same warning every time buries real signal.
#: Deduped per process; keyed on the source too, so two *different* stale
#: files each still get their one warning.
_WARNED: set = set()


def _warn_once(key, msg: str, *args) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    log.warning(msg, *args)


def host_key() -> str:
    """Host class the calibration is keyed on (platform + device kind)."""
    dev = jax.devices()[0]
    return f"{platform.machine()}-{jax.default_backend()}-{dev.device_kind}"


def load_table(path: Optional[os.PathLike] = None) -> Optional[CalibrationTable]:
    """Load a table from ``path`` or the resolution chain (None if absent).

    An explicit ``path`` (or ``$REPRO_CALIBRATION``) is trusted verbatim and
    must exist.  Chain candidates (user cache, repo default) are measured
    artifacts that may have been produced on a different host class — they
    are skipped unless their recorded ``backend`` matches this process's jax
    backend, so a CPU-measured repo default never governs planning on TPU.
    """
    candidates = []  # (path, source, explicit)
    if path is not None:
        candidates.append((Path(path), f"file:{path}", True))
    else:
        env = os.environ.get(CALIBRATION_ENV)
        if env:
            candidates.append((Path(env), f"env:{env}", True))
        candidates.append((CACHE_PATH, f"cache:{CACHE_PATH}", False))
        candidates.append((REPO_DEFAULT_PATH, "repo-default", False))
    for cand, source, explicit in candidates:
        cand = cand.expanduser()
        if not cand.is_file():
            if explicit:
                raise FileNotFoundError(
                    f"calibration table not found: {cand}")
            continue
        try:
            table = CalibrationTable.from_dict(
                json.loads(cand.read_text()), source=source)
        except (json.JSONDecodeError, KeyError, ValueError) as exc:
            raise ValueError(f"malformed calibration table {cand}: {exc}")
        if (not explicit and table.backend
                and table.backend != jax.default_backend()):
            # Measured on a different host class — skipping is correct
            # (a CPU-measured table must not govern TPU planning), but a
            # silent skip reads as "calibrated" when the planner is in
            # fact running on static fallbacks.  Say so, once per source.
            _warn_once(
                (source, "backend-mismatch", table.backend),
                "calibration table %s was measured on backend %r but this "
                "process runs %r; skipping it (planning falls back to the "
                "next candidate or the static constants) — re-run "
                "`python -m repro.engine.autotune` on this host to "
                "calibrate it",
                source, table.backend, jax.default_backend())
            continue
        return table
    return None


# Process-global resolution, cached after the first lookup.  ``set_table``
# overrides (tests, serve --calibration); ``set_table(None)`` re-resolves.
# Note: the engine caches jitted programs per plan, and the pallas backend
# bakes tile shapes in at program-build time — a table change affects plans
# compiled *afterwards*, not programs already jitted in this process.
_ACTIVE: Optional[CalibrationTable] = None
_RESOLVED = False


def set_table(table: Optional[CalibrationTable]) -> None:
    global _ACTIVE, _RESOLVED
    _ACTIVE = table
    _RESOLVED = table is not None


def get_table() -> Optional[CalibrationTable]:
    """The active calibration table, or None (static-constant fallback)."""
    global _ACTIVE, _RESOLVED
    if not _RESOLVED:
        _ACTIVE = load_table()
        _RESOLVED = True
    return _ACTIVE


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _time(fn, *args, repeat: int = 3, warmup: int = 1) -> float:
    """Mean wall seconds per call (post-warmup, blocking on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeat


def _sym_stack(b: int, n: int, seed: int = 0) -> jax.Array:
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    a = rng.standard_normal((b, n, n)).astype(np.float32)
    return jnp.asarray((a + np.swapaxes(a, 1, 2)) / 2)


def _sweep_prod_diff_blocks(
    b: int, n: int, candidates: Sequence[tuple]
) -> tuple:
    """Best ``(bb, bi, bj, bk)`` over the candidate grid (bb swept too —
    b-tiling the batch axis is what recovers occupancy at small ``n``)."""
    from repro.kernels.prod_diff import ops as pd_ops

    a = _sym_stack(b, n)
    import jax.numpy as jnp

    lam = jax.vmap(jnp.linalg.eigvalsh)(a)
    mu = jnp.sort(_sym_stack(b, n, seed=1)[:, :, : n - 1], axis=-1)
    best, best_t = None, float("inf")
    for blk in candidates:
        bb, bi, bj, bk = blk

        def run(lam=lam, mu=mu, bb=bb, bi=bi, bj=bj, bk=bk):
            return pd_ops.eei_magnitudes_batched(
                lam, mu, block_b=bb, block_i=bi, block_j=bj, block_k=bk)

        t = _time(run)
        if t < best_t:
            best, best_t = blk, t
    return best


def _sweep_sturm_blocks(b: int, n: int, candidates: Sequence[tuple]) -> tuple:
    from repro.kernels.sturm import ops as sturm_ops
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.standard_normal((b, n)).astype(np.float32))
    e = jnp.asarray(rng.standard_normal((b, n - 1)).astype(np.float32))
    best, best_t = None, float("inf")
    for blk in candidates:
        bb, bm = blk

        def run(d=d, e=e, bb=bb, bm=bm):
            return sturm_ops.sturm_eigenvalues(d, e, block_b=bb, block_m=bm)

        t = _time(run)
        if t < best_t:
            best, best_t = blk, t
    return best


def _measure_crossovers(
    sizes: Sequence[int], k: int, batch: int, backend: str = "jnp"
):
    """Smallest n where each EEI method beats its cheaper alternative.

    ``backend`` picks whose stage implementations get timed: the planner's
    TPU default is pallas, where the kernelized EEI amortizes differently
    than fused jnp — sweeping only jnp would mis-place the TPU crossovers.
    (The ``eigh`` leg always runs LAPACK regardless of backend.)
    """
    from repro.engine.engine import SolverEngine
    from repro.engine.plan import SolverPlan

    eigh_x = None
    dense_x = None
    # A win at the very first swept size must record a crossover *below* it
    # (plan_for routes n <= crossover to the cheaper method).
    prev_n = max(sizes[0] - 1, 0)
    for n in sizes:
        a = _sym_stack(batch, n)
        times = {}
        for method in ("eigh", "eei_dense", "eei_tridiag"):
            eng = SolverEngine(SolverPlan(method=method, backend=backend))
            times[method] = _time(lambda eng=eng, a=a: eng.topk(a, k))
        best_eei = min(times["eei_dense"], times["eei_tridiag"])
        if eigh_x is None and best_eei < times["eigh"]:
            eigh_x = prev_n  # last size where eigh still won
        if dense_x is None and times["eei_tridiag"] < times["eei_dense"]:
            dense_x = prev_n
        prev_n = n
    # Never observed a win inside the sweep -> the crossover sits above it.
    return eigh_x if eigh_x is not None else sizes[-1], (
        dense_x if dense_x is not None else sizes[-1])


def _measure_windowed_crossover(
    n: int, batch: int, ks: Sequence[int], backend: str = "jnp"
) -> float:
    """Largest measured ``k / n`` where the windowed composition still
    beats the full-spectrum composition on a batched topk.

    Sweeps power-of-two ``k`` (the serving buckets' k axis) on the
    tridiagonal method — the composition whose windowed variant replaces
    the whole minor-spectra stage.  Returns 0.0 if windowed never wins
    (the planner then never routes through it).
    """
    from repro.engine.engine import SolverEngine
    from repro.engine.plan import SolverPlan

    a = _sym_stack(batch, n)
    frac = 0.0
    for k in ks:
        if k > n:
            break
        full = SolverEngine(SolverPlan(
            method="eei_tridiag", backend=backend, spectrum="full"))
        win = SolverEngine(SolverPlan(
            method="eei_tridiag", backend=backend, spectrum="windowed"))
        t_full = _time(lambda eng=full: eng.topk(a, k))
        t_win = _time(lambda eng=win: eng.topk(a, k))
        if t_win < t_full:
            frac = k / n
        else:
            break  # windowed work grows with k; first loss ends the sweep
    return frac


#: ``krylov_n_min`` recorded when the Krylov reduce never won the sweep —
#: far above any real n, so the planner never routes through it (mirrors
#: the sizes[-1] convention of the method-crossover sweep, which cannot be
#: reused verbatim here because the krylov sweep stops at CI-sized n while
#: the true crossover may sit well past it).
KRYLOV_NEVER = 1 << 30


def _measure_krylov_crossover(
    sizes: Sequence[int], k: int, batch: int, backend: str = "jnp"
) -> int:
    """Smallest swept ``n`` where the Krylov reduce beats dense Householder
    on a windowed batched topk, or :data:`KRYLOV_NEVER` if it never does.

    The Lanczos band is O(n^2 m) against the dense reduce's O(n^3), so the
    win is monotone in n for fixed k — the first winning size is the
    crossover.  The sweep keeps ``k`` fixed (the planner additionally
    requires ``k <= n/16``, which bounds the band width relative to n).
    """
    from repro.engine.engine import SolverEngine
    from repro.engine.plan import SolverPlan

    for n in sizes:
        if not 0 < k < n:
            continue
        a = _sym_stack(batch, n)
        dense = SolverEngine(SolverPlan(
            method="eei_tridiag", backend=backend, spectrum="windowed"))
        krylov = SolverEngine(SolverPlan(method="eei_krylov",
                                         backend=backend))
        t_dense = _time(lambda eng=dense, a=a: eng.topk(a, k))
        t_krylov = _time(lambda eng=krylov, a=a: eng.topk(a, k))
        if t_krylov < t_dense:
            return n
    return KRYLOV_NEVER


def _packed_uniform_layout(batch: int, row_n: int, seg_n: int):
    """A uniform packed stack: ``row_n // seg_n`` segments per row."""
    import jax.numpy as jnp
    import numpy as np

    slots = row_n // seg_n
    a = np.asarray(_sym_stack(batch * slots, seg_n))
    rows = np.zeros((batch, row_n, row_n), np.float32)
    off = np.zeros((batch, slots), np.int32)
    length = np.full((batch, slots), seg_n, np.int32)
    for b in range(batch):
        for s in range(slots):
            o = s * seg_n
            rows[b, o:o + seg_n, o:o + seg_n] = a[b * slots + s]
            off[b, s] = o
    return jnp.asarray(a), jnp.asarray(rows), jnp.asarray(off), \
        jnp.asarray(length)


def _measure_pack_crossovers(
    row_ns: Sequence[int], seg_ns: Sequence[int], batch: int, k: int,
    backend: str = "jnp",
) -> tuple:
    """``(pack_n_max, packed_eigh_n_max)`` measured on uniform packed rows.

    ``pack_n_max``: largest segment ``n`` where one packed launch of
    ``(batch, row)`` beats the *fragmented* bucketed service of the same
    requests — ``slots`` separate ``(batch, n)`` launches, one per
    distinct segment size.  That is the stream condition the packer
    replaces: a mixed small-n stream spreads across one coalesce queue
    per distinct ``n``, so the bucketed path pays a launch (and a
    compiled program) per ``n`` while the packed path coalesces them all
    into one row queue.  0 when packing never wins even against the
    fragmented baseline, which keeps the planner's ``"auto"`` gate shut.

    ``packed_eigh_n_max``: largest swept row width where the packed eigh
    chain still beats the packed segmented-tridiagonal chain (mirrors the
    bucketed eigh crossover, which moves again for packed rows because
    eigh pays the full O(row^3) while the segmented chain's Sturm lanes
    pay per-segment brackets).
    """
    from repro.engine.engine import packed_topk_program, topk_program
    from repro.engine.plan import SolverPlan

    eigh_plan = SolverPlan(method="eigh", backend=backend)
    row0 = row_ns[0]
    pack_n_max = 0
    for seg_n in seg_ns:
        if seg_n * 2 > row0:
            break
        a, rows, off, length = _packed_uniform_layout(batch, row0, seg_n)
        slots = row0 // seg_n
        chunks = [a[s * batch:(s + 1) * batch] for s in range(slots)]
        bucketed = topk_program(eigh_plan, k, True)
        packed = packed_topk_program(eigh_plan, k, True)
        t_b = _time(lambda: [bucketed(c) for c in chunks])
        t_p = _time(lambda: packed(rows, off, length))
        if t_p < t_b:
            pack_n_max = seg_n
    seg_n = max(seg_ns[0], 8)
    packed_eigh_n_max = row_ns[-1]
    prev = max(row_ns[0] // 2, seg_n * 2)
    tri_plan = SolverPlan(
        method="eei_tridiag", backend=backend, spectrum="windowed")
    for row_n in row_ns:
        _, rows, off, length = _packed_uniform_layout(batch, row_n, seg_n)
        t_eigh = _time(lambda: packed_topk_program(
            eigh_plan, k, True)(rows, off, length))
        t_tri = _time(lambda: packed_topk_program(
            tri_plan, k, True)(rows, off, length))
        if t_tri < t_eigh:
            packed_eigh_n_max = prev  # last width where eigh still won
            break
        prev = row_n
    return pack_n_max, packed_eigh_n_max


def calibrate(
    *,
    smoke: bool = False,
    batch: int = 16,
    k: int = 4,
) -> CalibrationTable:
    """Measure crossovers + kernel blocks on this host; return the table.

    ``smoke`` shrinks the sweep to a CI-sized sanity pass (seconds, not
    minutes); full runs sweep enough sizes to bracket both crossovers.
    """
    if smoke:
        sizes = [8, 16, 32]
        pd_candidates = [(1, 32, 32, 32), (4, 32, 32, 32), (1, 64, 64, 64)]
        st_candidates = [(8, 64), (8, 128)]
        bench_b, bench_n = 8, 32
        win_n, win_ks = 32, (1, 4, 16, 32)
        krylov_sizes, krylov_k, krylov_b = [64, 128], 4, 2
        pack_rows, pack_segs, pack_b = [32, 64], (8, 16), 2
    else:
        sizes = [8, 16, 24, 32, 48, 64, 96, 128]
        win_n, win_ks = 64, (1, 2, 4, 8, 16, 32, 64)
        pd_candidates = [
            # bb = 1 tiles (the PR-2 grid) ...
            (1, 32, 32, 32), (1, 64, 64, 64), (1, 128, 128, 128),
            (1, 128, 128, 64), (1, 64, 128, 128),
            # ... and b-tiled blocks for the small-n occupancy regime.
            (4, 32, 32, 32), (8, 32, 32, 32), (4, 64, 64, 64),
            (8, 16, 64, 64), (16, 8, 32, 32),
        ]
        st_candidates = [(4, 128), (8, 64), (8, 128), (16, 128), (8, 256)]
        bench_b, bench_n = 64, 64
        krylov_sizes, krylov_k, krylov_b = [256, 512, 1024], 8, 2
        pack_rows, pack_segs, pack_b = [64, 128, 256], (8, 16, 32), 4
    eigh_x, dense_x = _measure_crossovers(sizes, k=k, batch=batch,
                                          backend="jnp")
    # The planner's accelerator default is the pallas backend — time its
    # crossovers too instead of assuming they match fused jnp.
    pallas_eigh_x, pallas_dense_x = _measure_crossovers(
        sizes, k=k, batch=batch, backend="pallas")
    pd_blocks = _sweep_prod_diff_blocks(bench_b, bench_n, pd_candidates)
    st_blocks = _sweep_sturm_blocks(bench_b * bench_n, bench_n, st_candidates)
    windowed_frac = _measure_windowed_crossover(win_n, batch, win_ks)
    krylov_n_min = _measure_krylov_crossover(
        krylov_sizes, k=krylov_k, batch=krylov_b)
    pack_n_max, packed_eigh_n_max = _measure_pack_crossovers(
        pack_rows, pack_segs, batch=pack_b, k=k)
    return CalibrationTable(
        eigh_crossover_n=int(eigh_x),
        dense_crossover_n=int(dense_x),
        prod_diff_blocks=tuple(pd_blocks[1:]),
        sturm_blocks=tuple(st_blocks),
        prod_diff_block_b=int(pd_blocks[0]),
        pallas_eigh_crossover_n=int(pallas_eigh_x),
        pallas_dense_crossover_n=int(pallas_dense_x),
        windowed_k_frac=float(windowed_frac),
        krylov_n_min=int(krylov_n_min),
        pack_n_max=int(pack_n_max),
        packed_eigh_n_max=int(packed_eigh_n_max),
        host=host_key(),
        backend=jax.default_backend(),
        measured_at=time.strftime("%Y-%m-%dT%H:%M:%S"),
        source="measured",
    )


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (seconds, coarse)")
    ap.add_argument("--out", default=str(CACHE_PATH),
                    help="where to write the table (default: user cache)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--k", type=int, default=4)
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    table = calibrate(smoke=args.smoke, batch=args.batch, k=args.k)
    path = table.save(Path(args.out))
    print(json.dumps(table.to_dict(), indent=2))
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
