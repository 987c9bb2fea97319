"""SolverPlan — one immutable record of every EEI pipeline choice.

Before this subsystem the choice of implementation was scattered across four
dispatch sites (the ``identity.VARIANTS`` string ladder, the
``method``/``use_kernels`` flags of ``SpectralEngine``, the free ``shard_map``
functions in ``core.distributed`` and per-kernel ``interpret`` plumbing).  A
``SolverPlan`` captures all of it in one hashable value:

    method        eigh | eei_dense | eei_tridiag   (what maths runs)
    spectrum      full | windowed   (which composition top-k programs run:
                  the full-spectrum chain, or the k-windowed chain that
                  computes only the selected extremal rows)
    backend       reference | jnp | pallas | sharded   (who runs each stage)
    mesh / axes   device topology for the sharded backend
    precision     None (keep input dtype) | "float32" | "float64"
    bisect_iters  Sturm bisection iterations (0 -> dtype default)
    max_batch     microbatch bound for very long query stacks (0 -> no bound)

Plans are produced by :func:`plan_for` from problem shape + device topology,
or constructed explicitly.  The registry resolves ``(plan.method,
plan.spectrum)`` to a stage composition and ``plan.backend`` to the stage
library that implements it; ``SolverEngine`` executes the plan.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax

Method = Literal[
    "eigh", "eei_dense", "eei_tridiag", "eei_krylov", "eei_krylov_si"]
BackendName = Literal["reference", "jnp", "pallas", "sharded"]
Spectrum = Literal["full", "windowed"]

#: ``n`` below which a full LAPACK ``eigh`` beats any EEI pipeline (the
#: paper's crossover regime; Table 1 shows speedup < 1 for small n).
#: Uncalibrated fallback — :func:`resolved_crossovers` prefers the measured
#: calibration table (``repro.engine.autotune``) when one is available.
EIGH_CROSSOVER_N = 24

#: ``n`` up to which dense minor spectra (n LAPACK calls of size n-1) are
#: cheaper than tridiagonalize + Sturm on this class of hardware.
#: Uncalibrated fallback — see :func:`resolved_crossovers`.
DENSE_CROSSOVER_N = 64

#: ``k / n`` at/below which a top-k query plans the *windowed* composition
#: (windowed Sturm + windowed components) instead of the full-spectrum
#: chain.  Uncalibrated fallback — schema-v3 calibration tables carry the
#: measured crossover (:func:`resolved_windowed_k_frac`); the windowed
#: chain does strictly less work, so the measured value normally sits at
#: the top of the sweep.
WINDOWED_K_FRAC = 0.5

#: ``n`` at/above which a top-k query plans the Krylov (Lanczos partial
#: tridiagonalization) reduce stage instead of the dense Householder reduce.
#: Uncalibrated fallback — schema-v4 calibration tables carry the measured
#: crossover (:func:`resolved_krylov_n_min`).  The Krylov band costs
#: O(n^2 m) for m ~ 16k versus O(n^3) dense, so the crossover sits where
#: m << n, i.e. large n with a narrow window.
KRYLOV_N_MIN = 1024

#: ``k / n`` at/below which the Krylov band (m ~ 16k) is meaningfully
#: narrower than the matrix and the partial reduce can win.  Above this the
#: band approaches n and dense Householder is strictly better.
KRYLOV_K_FRAC = 1.0 / 16.0

#: Largest *bucketed* ``n`` whose requests the serving packer coalesces
#: into segment-packed rows under ``pack="auto"``.  Uncalibrated fallback —
#: schema-v5 calibration tables carry the measured value
#: (:func:`resolved_pack_n_max`).  Packing wins where per-launch overhead
#: and pad waste dominate the solve, i.e. well below the eigh crossover.
PACK_N_MAX = 32

#: Packed *row width* at/below which the packed composition pins the LAPACK
#: eigh chain; wider rows take the segmented-Sturm tridiagonal chain.
#: Mirrors the bucketed eigh crossover (the packed row is one matrix as far
#: as LAPACK is concerned), measured separately because the segmented chain
#: pays per-segment bracket work, not per-row.  Uncalibrated fallback — see
#: :func:`resolved_packed_eigh_n_max`.
PACKED_EIGH_N_MAX = 128


def resolved_krylov_n_min() -> int:
    """The measured ``n`` at which the Krylov reduce starts winning here.

    Reads the calibration table (see ``repro.engine.autotune``); the static
    :data:`KRYLOV_N_MIN` fallback applies when no table resolves or the
    table predates schema v4.
    """
    from repro.engine import autotune

    table = autotune.get_table()
    if table is None or table.krylov_n_min is None:
        return KRYLOV_N_MIN
    return table.krylov_n_min


def resolved_windowed_k_frac() -> float:
    """The measured ``k / n`` windowed-composition crossover for this host.

    Reads the calibration table (see ``repro.engine.autotune``); the static
    :data:`WINDOWED_K_FRAC` fallback applies when no table resolves or the
    table predates schema v3 (``windowed_k_frac`` then loads as the same
    fallback value).
    """
    from repro.engine import autotune

    table = autotune.get_table()
    if table is None:
        return WINDOWED_K_FRAC
    return table.windowed_k_frac


def resolved_pack_n_max() -> int:
    """The measured largest bucketed ``n`` worth segment-packing here.

    Reads the calibration table (see ``repro.engine.autotune``); the static
    :data:`PACK_N_MAX` fallback applies when no table resolves or the table
    predates schema v5.
    """
    from repro.engine import autotune

    table = autotune.get_table()
    if table is None or table.pack_n_max is None:
        return PACK_N_MAX
    return table.pack_n_max


def resolved_packed_eigh_n_max() -> int:
    """The measured packed row width at/below which eigh wins the packed
    chain (static :data:`PACKED_EIGH_N_MAX` fallback for pre-v5 tables)."""
    from repro.engine import autotune

    table = autotune.get_table()
    if table is None or table.packed_eigh_n_max is None:
        return PACKED_EIGH_N_MAX
    return table.packed_eigh_n_max


def packed_plan_for(
    row_n: int,
    *,
    backend: Optional[BackendName] = None,
    precision: Optional[str] = None,
) -> SolverPlan:
    """Pick the plan a segment-packed row stack executes.

    The packed row is block-diagonal, so both packed compositions apply to
    it directly; the choice is the packed twin of the bucketed eigh/EEI
    crossover, keyed on the *row width* (what LAPACK sees) rather than any
    single request's ``n``: at/below :func:`resolved_packed_eigh_n_max`
    the eigh chain (one LAPACK call + mass-gated per-slot selection) wins;
    above it the segmented-Sturm windowed tridiagonal chain takes over.
    """
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if row_n <= resolved_packed_eigh_n_max():
        return SolverPlan(
            method="eigh", backend=backend, precision=precision)
    return SolverPlan(
        method="eei_tridiag", backend=backend, spectrum="windowed",
        precision=precision)


def resolved_crossovers(backend: Optional[str] = None) -> tuple:
    """``(eigh_crossover_n, dense_crossover_n)`` the planner dispatches on.

    Reads the measured calibration table (env > user cache > repo default;
    see ``repro.engine.autotune``); the static module constants above are
    used only when no table can be found.  ``backend`` selects the
    backend-specific measurement when the table carries one (schema v2
    times the pallas backend separately — the kernelized EEI crosses over
    at a different ``n`` than fused jnp); v1 tables fall back to their
    single jnp-measured pair.
    """
    from repro.engine import autotune

    table = autotune.get_table()
    if table is None:
        return EIGH_CROSSOVER_N, DENSE_CROSSOVER_N
    return table.crossovers_for(backend)


def fallback_chain() -> tuple:
    """``((name, SolverPlan), ...)`` — the per-request escalation chain.

    The serving runtime walks this after a request is isolated (a
    single-request stack that still fails, or a stack row failing
    verification), re-solving the *unpadded* matrix under each plan in turn
    and host-verifying the result before it may resolve the future.
    Ordered cheap-to-certain:

    1. the windowed EEI path (the request's own fast path minus the
       co-batch — isolates co-batch/padding interactions);
    2. the full-spectrum EEI chain (index-targeted Sturm windows are the
       first casualty of clustered spectra; the full bisection is sturdier);
    3. shift-and-invert Krylov — the proven escape hatch for clustered
       *extremal* groups (the regime where the EEI denominators collapse);
    4. the LAPACK eigh oracle.

    A terminal pure-numpy ``eigh`` link (no XLA at all) is appended by the
    server itself, so even a wedged device path cannot strand a caller.
    Built lazily (not a module constant) so it is cheap to import and easy
    to monkeypatch in tests.
    """
    return (
        ("eei_windowed", SolverPlan(
            method="eei_tridiag", backend="jnp", spectrum="windowed")),
        ("eei_full", SolverPlan(method="eei_tridiag", backend="jnp")),
        ("eei_krylov_si", SolverPlan(
            method="eei_krylov_si", backend="jnp", spectrum="windowed")),
        ("eigh", SolverPlan(method="eigh", backend="jnp")),
    )


@dataclasses.dataclass(frozen=True)
class SolverPlan:
    """Immutable, hashable description of one way to run the EEI pipeline."""

    method: Method = "eei_tridiag"
    backend: BackendName = "jnp"
    spectrum: Spectrum = "full"
    mesh: Optional[jax.sharding.Mesh] = None
    batch_axis: str = "data"
    minor_axis: Optional[str] = "model"
    precision: Optional[str] = None  # None -> keep input dtype
    bisect_iters: int = 0  # 0 -> dtype default
    max_batch: int = 0  # 0 -> solve the whole stack in one program
    krylov_m: int = 0  # Krylov band size; 0 -> default_m(n, k) at trace time

    def __post_init__(self):
        if self.method not in (
                "eigh", "eei_dense", "eei_tridiag", "eei_krylov",
                "eei_krylov_si"):
            raise ValueError(f"unknown method {self.method!r}")
        if self.backend not in ("reference", "jnp", "pallas", "sharded"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.spectrum not in ("full", "windowed"):
            raise ValueError(f"unknown spectrum {self.spectrum!r}")
        if self.precision not in (None, "float32", "float64"):
            raise ValueError(f"unknown precision {self.precision!r}")
        if self.backend == "pallas" and self.precision == "float64":
            raise ValueError(
                "precision='float64' cannot run on the pallas backend: its "
                "TPU kernels take float32 only; plan backend='jnp' or "
                "'reference' for float64")
        if self.backend == "sharded":
            if self.mesh is None:
                raise ValueError("backend='sharded' requires a mesh")
            if self.batch_axis not in self.mesh.axis_names:
                raise ValueError(
                    f"batch_axis {self.batch_axis!r} not in mesh axes "
                    f"{self.mesh.axis_names}")

    @property
    def batch_axis_size(self) -> int:
        """Devices along the batch (data) axis; 1 for unsharded backends."""
        if self.backend != "sharded" or self.mesh is None:
            return 1
        return self.mesh.shape[self.batch_axis]


def plan_for(
    shape: tuple,
    *,
    k: Optional[int] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    method: Optional[Method] = None,
    backend: Optional[BackendName] = None,
    spectrum: Optional[Spectrum] = None,
    precision: Optional[str] = None,
    bisect_iters: int = 0,
) -> SolverPlan:
    """Pick a plan from problem shape + device topology.

    ``shape`` is ``(n, n)`` or ``(b, n, n)``; ``k`` is the number of
    eigenpairs the caller will ask for (``None`` = the full table).  Explicit
    ``method``/``backend`` keywords override the heuristics; everything else
    is derived:

    * tiny matrices (or full-spectrum queries on small ones) route to the
      LAPACK oracle — the paper's own conclusion is that EEI wins only for
      *partial* outputs past a crossover size; the crossover sizes come from
      the per-host measured calibration table when one exists
      (:func:`resolved_crossovers`), else the static fallback constants;
    * small matrices keep dense minors (n eigvalsh calls beat the
      tridiagonalization constant); larger ones take the tridiagonal path;
    * a known top-k window (``k`` given, below the measured
      ``windowed_k_frac`` fraction of ``n``) plans the *windowed*
      composition — top-k programs then compute only the selected extremal
      rows instead of the full spectrum table
      (:func:`resolved_windowed_k_frac`; ``spectrum`` overrides);
    * a *narrow* window on a *large* matrix (``k <= n/16`` and ``n`` past
      the measured :func:`resolved_krylov_n_min` crossover) swaps the dense
      Householder reduce for the Krylov (Lanczos) partial band — the whole
      downstream chain is band-size agnostic, so only the reduce changes;
    * a mesh with >1 device along its batch axis picks the sharded backend
      whenever the stack puts at least one matrix on every device —
      divisibility is *not* required, because both ``SolverEngine._run_chunk``
      and the serving runtime pad indivisible stacks up to the batch axis
      and slice back (pow2 serving buckets meet non-pow2 meshes here); a
      real TPU picks Pallas kernels; the fused-jnp backend is the portable
      default.
    """
    if len(shape) not in (2, 3):
        raise ValueError(f"expected (n, n) or (b, n, n), got {shape}")
    n = shape[-1]
    b = shape[0] if len(shape) == 3 else 1

    # Backend first: the method crossovers are backend-specific (the
    # calibration table times the pallas kernels separately from fused jnp).
    if backend is None:
        if (mesh is not None and "data" in mesh.axis_names
                and mesh.shape["data"] > 1 and b >= mesh.shape["data"]):
            backend = "sharded"
        elif jax.default_backend() == "tpu":
            backend = "pallas"
        else:
            backend = "jnp"
    if backend != "sharded":
        mesh = None

    if method is None:
        eigh_x, dense_x = resolved_crossovers(backend)
        if n <= eigh_x or (k is not None and k >= n):
            method = "eigh"
        elif n <= dense_x:
            method = "eei_dense"
        else:
            method = "eei_tridiag"
        # Narrow top-k window on a large matrix: replace the dense O(n^3)
        # Householder reduce with the Lanczos partial band (O(n^2 m),
        # m ~ 16k).  Only for genuinely narrow windows — the band must be
        # much smaller than n — and past the measured size crossover.
        # Shift-and-invert ("eei_krylov_si") is never planned implicitly;
        # it is an explicit choice for clustered-spectrum matrices.
        if (method == "eei_tridiag" and k is not None and 0 < k < n
                and k <= KRYLOV_K_FRAC * n and n >= resolved_krylov_n_min()):
            method = "eei_krylov"

    if spectrum is None:
        spectrum = "full"
        if (method != "eigh" and k is not None and 0 < k < n
                and k <= resolved_windowed_k_frac() * n):
            spectrum = "windowed"

    minor_axis = None
    if mesh is not None and "model" in mesh.axis_names:
        minor_axis = "model"

    return SolverPlan(
        method=method,
        backend=backend,
        spectrum=spectrum,
        mesh=mesh,
        batch_axis="data",
        minor_axis=minor_axis,
        precision=precision,
        bisect_iters=bisect_iters,
    )
