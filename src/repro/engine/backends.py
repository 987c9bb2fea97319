"""Default stage libraries (reference, fused-jnp, Pallas) + compositions.

Each factory bundles batched stage implementations (see ``registry``):

    reference   straightforward jnp — unfused logspace sums, pure-JAX Sturm
                bisection.  The numerical oracle the faster backends are
                tested against, and the fallback when nothing else applies.
    jnp         the optimized portable path — the numerator/denominator
                reductions expressed as fused ones-contractions
                (``identity.*_dot``: producer fuses into the MXU dot, no
                (b, n, n, n) temps).
    pallas      the kernelized path — Sturm bisection (full-spectrum and
                index-targeted windows) and the prod-diff log-sum run as
                natively batched Pallas TPU kernels (interpret mode
                off-TPU): one pallas_call per stack with batch on the
                leading grid axis, stacked minor bands flattened onto the
                Sturm row axis, and tile shapes taken from the autotune
                calibration table when present.

The ``sharded`` backend lives in ``repro.core.distributed`` (it owns the
mesh/axis logic) and is registered here lazily to avoid an import cycle.

This module also registers the **default compositions** — the stage chains
the engine's graph executors run:

    eigh                  direct LAPACK (the oracle / small-n crossover)
    eei_dense             dense minors -> full EEI table -> LU signs
    eei_tridiag           Householder -> Sturm -> full EEI -> recurrence signs
    eei_dense_windowed    as eei_dense, but the components stage evaluates
                          only the k selected rows (prod_diff I-axis = k);
                          bitwise-equal to the sliced full table
    eei_tridiag_windowed  Householder -> *windowed* Sturm (k index-targeted
                          brackets) -> minor-determinant components (ratio
                          recurrence, O(n k): no minor-spectra stage at
                          all) -> recurrence signs
    eei_krylov            Lanczos partial band (m ~ 16k << n) -> the same
                          windowed Sturm / minor-det / signs chain on the
                          m-band; components return to the dense basis
                          through the partial Q (topk / eigenvalues only —
                          a partial basis has no full-table solve)
    eei_krylov_si         as eei_krylov on (A - sigma I)^{-1} via one
                          batched LU; a final map stage undoes
                          theta = 1/(lambda - sigma)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import identity, minors
from repro.core.directions import (
    inverse_iteration_signs,
    inverse_iteration_signs_batched,
    tridiagonal_signs,
)
from repro.engine.plan import SolverPlan
from repro.engine.registry import (
    Composition,
    StageLibrary,
    StageSig,
    register_backend,
    register_composition,
)
from repro.engine.verify import verify_topk as _verify_topk
from repro.engine.verify import verify_topk_packed as _verify_topk_packed
from repro.linalg import householder, sturm

# ---------------------------------------------------------------------------
# Stage implementations shared across backends
# ---------------------------------------------------------------------------


def _tridiagonalize(a: jax.Array, with_q: bool = True):
    return householder.tridiagonalize_batched(a, with_q=with_q)


def _dense_eigenvalues(a: jax.Array):
    return jax.vmap(jnp.linalg.eigvalsh)(a)


def _dense_minor_spectra(a: jax.Array):
    return jax.vmap(identity.minor_spectra)(a)


def _tridiag_signs(d, e, lam_sel, mag_sel):
    """Selected signed tridiagonal eigenvectors, ``(b, k, n)``."""
    inner = jax.vmap(tridiagonal_signs, in_axes=(None, None, 0, 0))
    return jax.vmap(inner)(d, e, lam_sel, mag_sel)


def _dense_signs_reference(a, lam_sel, mag_sel):
    """Per-(matrix, pair) inverse-iteration solves — the sign oracle."""
    inner = jax.vmap(inverse_iteration_signs, in_axes=(None, 0, 0))
    return jax.vmap(inner)(a, lam_sel, mag_sel)


def _dense_signs(a, lam_sel, mag_sel):
    """Selected signed dense eigenvectors, one batched LU program."""
    return inverse_iteration_signs_batched(a, lam_sel, mag_sel)


def _minor_det_components(d, e, lam_sel):
    """Windowed |w|^2 rows via the minor-determinant ratio recurrence."""
    return identity.tridiag_windowed_magnitudes_batched(d, e, lam_sel)


def _make_krylov_stages(plan: SolverPlan):
    """The two Krylov reduce stages, closing over the plan's band override.

    Shared verbatim by the reference / jnp / pallas libraries: the Lanczos
    loop is a sequential ``while_loop`` of dense matvecs — XLA already fuses
    it well, and there is no tile-level parallelism for a kernel to exploit
    (the same rationale as the minor-determinant recurrence below).
    """
    from repro.linalg import lanczos

    m = plan.krylov_m

    def krylov_reduce(a, k, largest):
        return lanczos.krylov_reduce_batched(a, int(k), bool(largest), m)

    def krylov_shift_invert_reduce(a, k, largest):
        return lanczos.krylov_shift_invert_reduce_batched(
            a, int(k), bool(largest), m)

    return {
        "krylov_reduce": krylov_reduce,
        "krylov_shift_invert_reduce": krylov_shift_invert_reduce,
    }


def _make_segmented_sturm_stage(iters: int, block_b=None, block_m=None):
    """Per-segment windowed Sturm on a packed band.

    The segmented bracket/target layout is lane bookkeeping around the same
    bisection loop, and the kernels module owns that layout — so every
    backend shares the one implementation (interpret mode keeps it portable
    off-TPU; the import stays lazy per the lazy-kernel convention).
    """

    def tridiag_eigenvalues_segmented(d, e, seg_off, seg_len, k, largest):
        from repro.kernels.sturm import ops as sturm_ops

        kwargs = {}
        if block_b is not None:
            kwargs = {"block_b": block_b, "block_m": block_m}
        return sturm_ops.sturm_eigenvalues_segmented(
            d, e, seg_off, seg_len, k=int(k), largest=bool(largest),
            n_iter=iters, **kwargs)

    return tridiag_eigenvalues_segmented


# ---------------------------------------------------------------------------
# reference / jnp
# ---------------------------------------------------------------------------


def _make_jnp_like(name: str, reduce: str, plan: SolverPlan) -> StageLibrary:
    iters = plan.bisect_iters

    def tridiag_eigenvalues(d, e):
        return sturm.bisect_eigenvalues_batched(d, e, n_iter=iters)

    def tridiag_eigenvalues_windowed(d, e, k, largest):
        return sturm.bisect_eigenvalues_windowed_batched(
            d, e, k, largest=largest, n_iter=iters)

    def tridiag_eigenvalues_bracketed(d, e, lo, hi, k, largest):
        return sturm.bisect_eigenvalues_bracketed_batched(
            d, e, lo, hi, int(k), largest=bool(largest), n_iter=iters)

    def tridiag_minor_spectra(d, e):
        dm, em = minors.all_tridiagonal_minor_bands_batched(d, e)
        return sturm.bisect_eigenvalues_batched(dm, em, n_iter=iters)

    def magnitudes(lam, mu):
        return identity.magnitudes_from_spectra(
            lam, mu, logspace=True, reduce=reduce)

    def magnitudes_windowed(lam, mu, idx):
        return identity.magnitudes_from_spectra(
            lam, mu, logspace=True, reduce=reduce, rows=idx)

    return StageLibrary(name, {
        "tridiagonalize": _tridiagonalize,
        "tridiag_eigenvalues": tridiag_eigenvalues,
        "tridiag_eigenvalues_windowed": tridiag_eigenvalues_windowed,
        "tridiag_eigenvalues_bracketed": tridiag_eigenvalues_bracketed,
        "tridiag_minor_spectra": tridiag_minor_spectra,
        "dense_eigenvalues": _dense_eigenvalues,
        "dense_minor_spectra": _dense_minor_spectra,
        "magnitudes": magnitudes,
        "magnitudes_windowed": magnitudes_windowed,
        "minor_det_components": _minor_det_components,
        "tridiag_signs": _tridiag_signs,
        "dense_signs": (
            _dense_signs_reference if name == "reference" else _dense_signs),
        "tridiag_eigenvalues_segmented": _make_segmented_sturm_stage(iters),
        "verify_topk": _verify_topk,
        "verify_topk_packed": _verify_topk_packed,
        **_make_krylov_stages(plan),
    })


def make_reference_backend(plan: SolverPlan) -> StageLibrary:
    return _make_jnp_like("reference", "sum", plan)


def make_jnp_backend(plan: SolverPlan) -> StageLibrary:
    return _make_jnp_like("jnp", "dot", plan)


# ---------------------------------------------------------------------------
# pallas
# ---------------------------------------------------------------------------


def make_pallas_backend(plan: SolverPlan) -> StageLibrary:
    # Kernel modules are imported lazily (mirrors the seed's lazy-kernel
    # convention: importing the engine must not require a Pallas-capable
    # install until a pallas plan actually runs).
    from repro.engine import autotune
    from repro.kernels.prod_diff import ops as pd_ops
    from repro.kernels.sturm import ops as sturm_ops

    iters = plan.bisect_iters
    # Tile shapes come from the host calibration table when one exists
    # (autotune sweeps them with benchmarks/throughput.py's harness); the
    # kernel-side defaults are the uncalibrated fallback.
    table = autotune.get_table()
    pd_bi, pd_bj, pd_bk = table.prod_diff_blocks if table else (128, 128, 128)
    pd_bb = table.prod_diff_block_b if table else 1
    st_bb, st_bm = table.sturm_blocks if table else (8, 128)

    def tridiag_eigenvalues(d, e):
        return sturm_ops.sturm_eigenvalues(
            d, e, n_iter=iters, block_b=st_bb, block_m=st_bm)

    def tridiag_eigenvalues_windowed(d, e, k, largest):
        return sturm_ops.sturm_eigenvalues(
            d, e, n_iter=iters, block_b=st_bb, block_m=st_bm,
            window=(int(k), bool(largest)))

    def tridiag_eigenvalues_bracketed(d, e, lo, hi, k, largest):
        return sturm_ops.sturm_eigenvalues_bracketed(
            d, e, lo, hi, k=int(k), largest=bool(largest),
            n_iter=iters, block_b=st_bb, block_m=st_bm)

    def tridiag_minor_spectra(d, e):
        dm, em = minors.all_tridiagonal_minor_bands_batched(d, e)
        return sturm_ops.sturm_minor_spectra(
            dm, em, n_iter=iters, block_b=st_bb, block_m=st_bm)

    def magnitudes(lam, mu):
        return pd_ops.eei_magnitudes_batched(
            lam, mu, block_b=pd_bb,
            block_i=pd_bi, block_j=pd_bj, block_k=pd_bk)

    def magnitudes_windowed(lam, mu, idx):
        return pd_ops.eei_magnitudes_windowed(
            lam, mu, idx, block_b=pd_bb,
            block_i=pd_bi, block_j=pd_bj, block_k=pd_bk)

    # The minor-determinant recurrence is sequential over the band — a VPU
    # scan, not a tile job — so the pallas library shares the jnp stage.
    return StageLibrary("pallas", {
        "tridiagonalize": _tridiagonalize,
        "tridiag_eigenvalues": tridiag_eigenvalues,
        "tridiag_eigenvalues_windowed": tridiag_eigenvalues_windowed,
        "tridiag_eigenvalues_bracketed": tridiag_eigenvalues_bracketed,
        "tridiag_minor_spectra": tridiag_minor_spectra,
        "dense_eigenvalues": _dense_eigenvalues,
        "dense_minor_spectra": _dense_minor_spectra,
        "magnitudes": magnitudes,
        "magnitudes_windowed": magnitudes_windowed,
        "minor_det_components": _minor_det_components,
        "tridiag_signs": _tridiag_signs,
        "dense_signs": _dense_signs,
        "tridiag_eigenvalues_segmented": _make_segmented_sturm_stage(
            iters, st_bb, st_bm),
        "verify_topk": _verify_topk,
        "verify_topk_packed": _verify_topk_packed,
        **_make_krylov_stages(plan),
    })


def _sharded_factory(plan: SolverPlan) -> StageLibrary:
    from repro.core.distributed import make_sharded_backend

    return make_sharded_backend(plan)


def register_default_backends() -> None:
    register_backend("reference", make_reference_backend)
    register_backend("jnp", make_jnp_backend)
    register_backend("pallas", make_pallas_backend)
    register_backend("sharded", _sharded_factory)


# ---------------------------------------------------------------------------
# Default compositions (stage chains per program kind)
# ---------------------------------------------------------------------------

# Shared stage signatures.
_REDUCE = StageSig("reduce", "householder", ("a",), ("d", "e", "q"))
_REDUCE_NOQ = StageSig("reduce", "householder", ("a",), ("d", "e"))
_SPEC_DENSE = StageSig("spectrum", "dense_eigenvalues", ("a",), ("lam",))
_SPEC_TRI = StageSig("spectrum", "tridiag_full", ("d", "e"), ("lam",))
_SPEC_TRI_WIN = StageSig(
    "spectrum", "tridiag_windowed", ("d", "e"), ("lam_sel",))
_MINORS_DENSE = StageSig("minor_spectra", "dense_minors", ("a",), ("mu",))
_MINORS_TRI = StageSig("minor_spectra", "tridiag_minors", ("d", "e"), ("mu",))
_COMP_FULL = StageSig("components", "eei_full", ("lam", "mu"), ("mags",))
_COMP_SELECT = StageSig(
    "components", "eei_select", ("lam", "mu", "idx"), ("lam_sel", "mag_sel"))
_COMP_WIN = StageSig(
    "components", "eei_windowed", ("lam", "mu", "idx"),
    ("lam_sel", "mag_sel"))
_COMP_DET = StageSig(
    "components", "minor_det", ("d", "e", "lam_sel"), ("mag_sel",))
_REC_TRI = StageSig(
    "recover", "tridiag_signs", ("d", "e", "q", "lam_sel", "mag_sel"),
    ("vecs",))
_REC_TRI_SOLVE = StageSig(
    "recover", "tridiag_solve", ("d", "e", "q", "lam", "mags"), ("mags",))
_REC_DENSE = StageSig(
    "recover", "dense_signs", ("a", "lam_sel", "mag_sel"), ("vecs",))
# Krylov reduce: a Lanczos band (d (b, m), e (b, m-1)) plus the partial
# orthonormal basis q (b, n, m) and the Lanczos steps taken, steps (b,).
# Every downstream tridiagonal stage is band-size agnostic, so the windowed
# Sturm / minor-determinant / sign chain runs on the m-band unchanged and
# the same back-transform through q lifts band eigenvectors to the dense
# basis (q columns are the basis — exactly Householder's convention with
# m = n).
_REDUCE_KRYLOV = StageSig(
    "reduce", "krylov", ("a",), ("d", "e", "q", "steps"))
_REDUCE_KRYLOV_NOQ = StageSig("reduce", "krylov", ("a",), ("d", "e", "steps"))
# Shift-and-invert: the band lives in theta = 1/(lambda - sigma) space and
# the recover chain ends with a map stage undoing the transform.
_REDUCE_SI = StageSig(
    "reduce", "krylov_shift_invert", ("a",),
    ("d", "e", "q", "sigma", "steps"))
_REDUCE_SI_NOQ = StageSig(
    "reduce", "krylov_shift_invert", ("a",), ("d", "e", "sigma", "steps"))
_SPEC_SI_WIN = StageSig(
    "spectrum", "tridiag_windowed_si", ("d", "e"), ("lam_sel",))
_MAP_SI = StageSig(
    "recover", "shift_invert_map", ("sigma", "lam_sel", "vecs"),
    ("lam_sel", "vecs"))
_MAP_SI_EIG = StageSig(
    "recover", "shift_invert_map", ("sigma", "lam_sel"), ("lam_sel",))
# Packed (segment-stacked) chains: the input row is block-diagonal, so the
# full-chain stages apply to the packed matrix itself — the packed eigh
# chain gates LAPACK's columns per segment by mass, and the packed tridiag
# chain swaps the windowed Sturm for its segmented twin (per-lane
# bracket/target state) while the minor-det and sign-recurrence stages run
# unchanged on the flattened (b, S*k) window (a segment eigenvalue has ~0
# minor-det mass outside its block, and the sign recurrence restarts at
# every e ~= 0 junction by construction).
_SPEC_TRI_SEG = StageSig(
    "spectrum", "tridiag_segmented", ("d", "e", "seg_off", "seg_len"),
    ("lam_sel",))
_REC_PACKED_SELECT = StageSig(
    "recover", "packed_select", ("lam", "v", "seg_off", "seg_len"),
    ("lam_seg", "vecs_seg"))
_REC_PACKED_RESHAPE = StageSig(
    "recover", "packed_reshape", ("lam_sel", "vecs", "seg_off", "seg_len"),
    ("lam_seg", "vecs_seg"))
# Streaming rank-1 update chain (the ``update`` program kind), shared by
# every method: the reduce stage projects the *updated* matrix onto the
# session's retained Ritz basis augmented with the update direction and a
# few Lanczos extension vectors (so the perturbation is inside the span),
# producing a small (b, m') band whose back-transform q lifts band
# eigenvectors straight to the dense basis — after that the chain *is* the
# windowed tridiagonal chain, except the spectrum stage bisects from
# interlacing + secular warm brackets instead of Gershgorin, and a final
# select stage splits the k-window answer from the refreshed (basis, theta)
# session state.
_REDUCE_WARM = StageSig(
    "reduce", "warm_project", ("a", "basis", "u"), ("d", "e", "q", "z2"))
_SPEC_TRI_BRACKETED = StageSig(
    "spectrum", "tridiag_bracketed", ("a", "d", "e", "theta", "rho", "z2"),
    ("lam_sel",))
_REC_UPDATE_SELECT = StageSig(
    "recover", "update_select", ("lam_sel", "vecs", "idx"),
    ("lam_sel", "vecs", "basis", "theta"))
_UPDATE_CHAIN = (
    _REDUCE_WARM, _SPEC_TRI_BRACKETED, _COMP_DET, _REC_TRI,
    _REC_UPDATE_SELECT)


def register_default_compositions() -> None:
    register_composition(Composition(
        name="eigh", method="eigh", windowed=False,
        topk=(
            StageSig("spectrum", "eigh", ("a",), ("lam", "v")),
            StageSig("recover", "eigh_topk", ("lam", "v", "idx"),
                     ("lam_sel", "vecs")),
        ),
        solve=(
            StageSig("spectrum", "eigh", ("a",), ("lam", "v")),
            StageSig("recover", "eigh_solve", ("lam", "v"), ("mags",)),
        ),
        eigenvalues=(_SPEC_DENSE,),
        packed_topk=(
            StageSig("spectrum", "eigh", ("a",), ("lam", "v")),
            _REC_PACKED_SELECT,
        ),
        update=_UPDATE_CHAIN,
    ))
    register_composition(Composition(
        name="eei_dense", method="eei_dense", windowed=False,
        topk=(_SPEC_DENSE, _MINORS_DENSE, _COMP_SELECT, _REC_DENSE),
        solve=(_SPEC_DENSE, _MINORS_DENSE, _COMP_FULL),
        eigenvalues=(_SPEC_DENSE,),
        update=_UPDATE_CHAIN,
    ))
    register_composition(Composition(
        name="eei_dense_windowed", method="eei_dense", windowed=True,
        topk=(_SPEC_DENSE, _MINORS_DENSE, _COMP_WIN, _REC_DENSE),
        update=_UPDATE_CHAIN,
    ))
    register_composition(Composition(
        name="eei_tridiag", method="eei_tridiag", windowed=False,
        topk=(_REDUCE, _SPEC_TRI, _MINORS_TRI, _COMP_SELECT, _REC_TRI),
        solve=(_REDUCE, _SPEC_TRI, _MINORS_TRI, _COMP_FULL, _REC_TRI_SOLVE),
        eigenvalues=(_REDUCE_NOQ, _SPEC_TRI),
        update=_UPDATE_CHAIN,
    ))
    register_composition(Composition(
        name="eei_tridiag_windowed", method="eei_tridiag", windowed=True,
        topk=(_REDUCE, _SPEC_TRI_WIN, _COMP_DET, _REC_TRI),
        eigenvalues=(_REDUCE_NOQ, _SPEC_TRI_WIN),
        packed_topk=(
            _REDUCE, _SPEC_TRI_SEG, _COMP_DET, _REC_TRI,
            _REC_PACKED_RESHAPE),
        update=_UPDATE_CHAIN,
    ))
    # Krylov: the Lanczos partial band replaces Householder; everything
    # after the reduce is the *same* windowed chain (the stages are
    # band-size agnostic and the back-transform through q is shared).
    # There is no full-table solve — a partial basis cannot produce every
    # row by construction, so SolverEngine.solve on a krylov plan raises
    # the registry's "declares no 'solve' chain" error.
    register_composition(Composition(
        name="eei_krylov", method="eei_krylov", windowed=False,
        topk=(_REDUCE_KRYLOV, _SPEC_TRI_WIN, _COMP_DET, _REC_TRI),
        eigenvalues=(_REDUCE_KRYLOV_NOQ, _SPEC_TRI_WIN),
        update=_UPDATE_CHAIN,
    ))
    register_composition(Composition(
        name="eei_krylov_si", method="eei_krylov_si", windowed=False,
        topk=(_REDUCE_SI, _SPEC_SI_WIN, _COMP_DET, _REC_TRI, _MAP_SI),
        eigenvalues=(_REDUCE_SI_NOQ, _SPEC_SI_WIN, _MAP_SI_EIG),
        update=_UPDATE_CHAIN,
    ))


register_default_backends()
register_default_compositions()
