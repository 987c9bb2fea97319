"""Post-solve result verification — the ``verify`` stage role.

The EEI identity degrades exactly where traffic is nastiest: (near-)
degenerate spectra collapse the product-difference denominators.  The
kernels clamp those denominators at ``eps * spectral scale`` so nothing
overflows, but clamping only guarantees *finite* garbage — nothing checked
that the emitted vectors are eigenvectors.  This module is that check: a
cheap batched stage appended after ``recover`` that scores every row of a
topk result and emits per-matrix boolean flags, so the serving layer can
route failing requests down the fallback chain instead of returning
garbage to a caller.

Checks (per matrix in the stack):

* **finite** — every selected eigenvalue and vector entry is finite.
* **residual** — ``max_i ||A v_i - lam_i v_i||_2 <= tol * scale(A)`` where
  ``scale(A) = max(||A||_F, tiny)`` — the Frobenius norm never vanishes on
  the guard-padded server stacks, and measured float32 EEI residuals track
  it with an n-independent constant (~3e-4 of ``||A||_F`` from n=16 to
  n=128), so one tolerance covers every bucket size.
* **unit norm** — ``| ||v_i||_2 - 1 | <= norm_tol`` for every row.
* **bracket order** — selected eigenvalues ascend: ``lam[j+1] >= lam[j] -
  tol * scale``.  A collapsed or crossed Sturm bracket shows up here.

``verify_topk`` is pure jnp on purpose: it runs inside the jitted program
on every backend (under GSPMD the post-recover arrays are already global,
so no shard_map wrapper is needed), and ``verify_topk_host`` is the same
math on numpy for checking host-side fallback solves.

Tolerances default to ``DEFAULT_TOL`` — >= 3x above the worst measured
healthy float32 EEI residual, while a clamped-denominator garbage vector
(residual ``O(|lam|_max)``, i.e. >= ``||A||_F / sqrt(n)``) sits >= 50x
*above* it at serving sizes — and an exactly-degenerate collapse shows up
as NaN, which the finiteness check catches outright.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

#: Default residual tolerance, in units of ``||A||_F``.  Measured healthy
#: float32 EEI residuals sit at ~1e-4..4e-4 of ``||A||_F`` across n=16..128
#: (the bisection tolerance dominates and tracks the Frobenius norm);
#: garbage from a clamped denominator is O(||A||_F / sqrt(n)) or NaN.
DEFAULT_TOL = 2e-3

#: Default unit-norm tolerance.  Recover stages renormalize explicitly, so
#: a healthy row is 1 +/- a few ulp; a NaN-poisoned or zero row is not.
DEFAULT_NORM_TOL = 1e-3


class VerifyFlags(NamedTuple):
    """Per-matrix verification verdict for a batched topk result.

    All fields carry the stack's leading batch axis ``(b,)``.  ``ok`` is
    the conjunction of the individual checks; ``residual`` is the worst
    relative residual (units of ``||A||_F``) for observability / debugging.
    ``steps`` is filled by the serving program when its chain has a Krylov
    reduce: the Lanczos steps each row took (``None`` otherwise).
    """

    ok: jax.Array          # (b,) bool — all checks passed
    finite: jax.Array      # (b,) bool — no NaN/Inf in lam or vecs
    residual_ok: jax.Array # (b,) bool — max_i ||A v - lam v|| <= tol*scale
    norm_ok: jax.Array     # (b,) bool — rows unit-norm within norm_tol
    ordered: jax.Array     # (b,) bool — selected eigenvalues ascend
    residual: jax.Array    # (b,) float — worst relative residual
    steps: Optional[jax.Array] = None  # (b,) float32 — Lanczos steps


def _spectral_scale(a: jnp.ndarray) -> jnp.ndarray:
    """Per-matrix scale ``max(||A||_F, tiny)`` — never vanishes, so
    relative tolerances stay meaningful for near-zero matrices."""
    fro = jnp.sqrt(jnp.sum(a * a, axis=(-2, -1)))
    return jnp.maximum(fro, jnp.asarray(1e-30, a.dtype))


def verify_topk(a: jnp.ndarray, lam_sel: jnp.ndarray, vecs: jnp.ndarray,
                tol: float = DEFAULT_TOL,
                norm_tol: float = DEFAULT_NORM_TOL) -> VerifyFlags:
    """Batched verification of a topk result.

    ``a`` is the input stack ``(b, n, n)``, ``lam_sel`` the selected
    eigenvalues ``(b, k)`` ascending, ``vecs`` the selected eigenvectors
    ``(b, k, n)`` (rows).  Pure jnp — safe inside jit on every backend.
    """
    scale = _spectral_scale(a)  # (b,)

    finite = (jnp.all(jnp.isfinite(lam_sel), axis=-1)
              & jnp.all(jnp.isfinite(vecs), axis=(-2, -1)))

    # Residual ||A v_i - lam_i v_i|| per selected row; rows of `vecs` are
    # eigenvectors, so A acts on the last axis.
    av = jnp.einsum("...ij,...kj->...ki", a, vecs)
    res = av - lam_sel[..., :, None] * vecs
    res_norm = jnp.sqrt(jnp.sum(res * res, axis=-1))  # (b, k)
    worst = jnp.max(res_norm, axis=-1) / scale         # (b,)
    # NaN comparisons are False, so a poisoned row fails residual_ok too —
    # but report the raw worst for observability.
    residual_ok = worst <= tol

    norms = jnp.sqrt(jnp.sum(vecs * vecs, axis=-1))    # (b, k)
    norm_ok = jnp.all(jnp.abs(norms - 1.0) <= norm_tol, axis=-1)

    # Ascending within tol*scale: a collapsed bracket (repeated lam is
    # fine) passes, a crossed one fails.
    dif = lam_sel[..., 1:] - lam_sel[..., :-1]
    ordered = jnp.all(dif >= -tol * scale[..., None], axis=-1)
    if lam_sel.shape[-1] < 2:
        ordered = jnp.ones_like(finite)

    ok = finite & residual_ok & norm_ok & ordered
    return VerifyFlags(ok=ok, finite=finite, residual_ok=residual_ok,
                       norm_ok=norm_ok, ordered=ordered, residual=worst)


def verify_topk_packed(a: jnp.ndarray, seg_off: jnp.ndarray,
                       seg_len: jnp.ndarray, lam_seg: jnp.ndarray,
                       vecs_seg: jnp.ndarray, largest: bool = True,
                       tol: float = DEFAULT_TOL,
                       norm_tol: float = DEFAULT_NORM_TOL) -> VerifyFlags:
    """Per-*slot* verification of a segment-packed topk result, ``(b, S)``.

    Same checks as :func:`verify_topk`, scoped to each segment of each
    packed row so the PR-7 guarantee holds per request, not per stack:

    * residuals are scaled by the *segment's* Frobenius norm (a small
      request must not hide behind a large neighbor's scale);
    * only the ``min(seg_len, k)`` *valid* lanes are checked — the packer
      guarantees every rider's window lies inside them (sentinel lanes sit
      at the front for ``largest``, at the back for smallest);
    * ``norm_ok`` additionally requires in-segment mass ``>= 1 - norm_tol``
      — the check that catches eigh rotating a cross-segment (near-)
      degenerate eigenspace into vectors that straddle two requests;
    * empty slots (``seg_len == 0``) pass vacuously.
    """
    b, s, k = lam_seg.shape
    n = a.shape[-1]
    dtype = a.dtype
    seg_off = seg_off.astype(jnp.int32)
    seg_len = seg_len.astype(jnp.int32)

    col = jnp.arange(n, dtype=jnp.int32)
    in_seg = ((seg_off[:, :, None] <= col[None, None, :])
              & (col[None, None, :]
                 < (seg_off + seg_len)[:, :, None]))  # (b, S, N)
    m = in_seg.astype(dtype)
    empty = seg_len == 0  # (b, S)

    # Valid lanes per slot: the min(len, k) real window positions.
    clen = jnp.minimum(seg_len, k)  # (b, S)
    t = jnp.arange(k, dtype=jnp.int32)[None, None, :]
    if largest:
        valid = t >= (k - clen)[:, :, None]  # (b, S, k)
    else:
        valid = t < clen[:, :, None]

    finite_lane = (jnp.isfinite(lam_seg)
                   & jnp.all(jnp.isfinite(vecs_seg), axis=-1))  # (b, S, k)
    finite = jnp.all(finite_lane | ~valid, axis=-1)

    # Segment-scoped scale: ||A[seg, seg]||_F via the mask quadratic form.
    seg_fro2 = jnp.einsum("bsp,bpq,bsq->bs", m, a * a, m)
    scale = jnp.maximum(jnp.sqrt(seg_fro2),
                        jnp.asarray(1e-30, dtype))  # (b, S)

    # Residual of the *served* slice: retire hands each caller only its
    # segment's columns, so the vector is masked to the segment first.  The
    # tridiag chain's rows carry ~1e-4 stray amplitude outside the segment
    # (minor-det mass is ~0 there, not exactly 0) that the caller never
    # sees; unmasked, the guard diagonals amplify it into a false reject.
    # What the mask drops is bounded by the in-segment mass check below.
    vm = vecs_seg * m[:, :, None, :]  # (b, S, k, N)
    av = jnp.einsum("bij,bskj->bski", a, vm)
    res = av - lam_seg[..., None] * vm
    res_norm = jnp.sqrt(jnp.sum(res * res, axis=-1))  # (b, S, k)
    worst = jnp.max(jnp.where(valid, res_norm, 0.0), axis=-1) / scale
    residual_ok = worst <= tol

    norms2 = jnp.sum(vecs_seg * vecs_seg, axis=-1)  # (b, S, k)
    mass = jnp.einsum("bsp,bskp->bsk", m, vecs_seg * vecs_seg)
    lane_norm_ok = (jnp.abs(jnp.sqrt(norms2) - 1.0) <= norm_tol) \
        & (mass >= 1.0 - norm_tol)
    norm_ok = jnp.all(lane_norm_ok | ~valid, axis=-1)

    # Ascending across adjacent *valid* lanes only (sentinels are contiguous
    # at one end, so valid lanes are contiguous and adjacency is enough).
    dif = lam_seg[..., 1:] - lam_seg[..., :-1]
    pair_valid = valid[..., 1:] & valid[..., :-1]
    ordered = jnp.all(
        (dif >= -tol * scale[..., None]) | ~pair_valid, axis=-1)
    if k < 2:
        ordered = jnp.ones_like(finite)

    ok = (finite & residual_ok & norm_ok & ordered) | empty
    return VerifyFlags(ok=ok, finite=finite | empty,
                       residual_ok=residual_ok | empty,
                       norm_ok=norm_ok | empty, ordered=ordered | empty,
                       residual=jnp.where(empty, 0.0, worst))


def verify_topk_host(a: np.ndarray, lam_sel: np.ndarray, vecs: np.ndarray,
                     tol: float = DEFAULT_TOL,
                     norm_tol: float = DEFAULT_NORM_TOL) -> VerifyFlags:
    """Host (numpy) twin of :func:`verify_topk` for checking fallback
    solves without a device round-trip.  Same checks, same tolerances;
    returns :class:`VerifyFlags` of numpy arrays."""
    a = np.asarray(a)
    lam_sel = np.asarray(lam_sel)
    vecs = np.asarray(vecs)
    squeeze = a.ndim == 2
    if squeeze:
        a, lam_sel, vecs = a[None], lam_sel[None], vecs[None]

    fro = np.sqrt(np.sum(a * a, axis=(-2, -1)))
    scale = np.maximum(fro, 1e-30)

    finite = (np.all(np.isfinite(lam_sel), axis=-1)
              & np.all(np.isfinite(vecs), axis=(-2, -1)))

    av = np.einsum("...ij,...kj->...ki", a, vecs)
    res = av - lam_sel[..., :, None] * vecs
    with np.errstate(invalid="ignore", over="ignore"):
        res_norm = np.sqrt(np.sum(res * res, axis=-1))
        worst = np.max(res_norm, axis=-1) / scale
        residual_ok = worst <= tol

        norms = np.sqrt(np.sum(vecs * vecs, axis=-1))
        norm_ok = np.all(np.abs(norms - 1.0) <= norm_tol, axis=-1)

        dif = lam_sel[..., 1:] - lam_sel[..., :-1]
        ordered = np.all(dif >= -tol * scale[..., None], axis=-1)
    if lam_sel.shape[-1] < 2:
        ordered = np.ones_like(finite)

    ok = finite & residual_ok & norm_ok & ordered
    flags = VerifyFlags(ok=ok, finite=finite, residual_ok=residual_ok,
                        norm_ok=norm_ok, ordered=ordered, residual=worst)
    if squeeze:
        flags = VerifyFlags(*(f if f is None else f[0] for f in flags))
    return flags
