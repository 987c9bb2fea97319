"""SolverEngine — plan-driven, batched graph execution of the EEI pipeline.

``SolverEngine.solve(a)`` / ``.topk(a, k)`` accept a single symmetric matrix
``(n, n)`` or a stack ``(b, n, n)`` and run the plan's *composition* on the
plan's backend end-to-end batched — this is the serving path for streams of
top-k queries over stacks of matrices (the regime the paper's use cases
issue).

The engine is a generic **stage-graph executor**: a program builder resolves
``plan -> composition -> stage chain`` (``registry``), binds each stage
signature to its builder (the ``_STAGE_BUILDERS`` table below, which pulls
implementations from the backend's stage library) and jits a function that
threads a state dict through the chain.  There is no per-method branch in
here — adding a method or a windowed variant is a registry change.

Compositions currently registered (see ``backends.py``):

    eigh                  vmapped LAPACK — the oracle / small-n fallback.
    eei_dense[...]        dense minor spectra -> EEI products (optionally
                          windowed to the k selected rows, bitwise-equal to
                          the sliced full table).
    eei_tridiag           Householder -> Sturm -> full EEI on the
                          tridiagonal form -> recurrence signs ->
                          back-transform with Q.
    eei_tridiag_windowed  Householder -> index-targeted Sturm window (k
                          bisection lanes) -> minor-determinant components
                          (ratio recurrence, no minor-spectra stage) ->
                          recurrence signs.  O(n^2 k + n^3-tridiagonalize)
                          instead of the full path's O(n^3 * iters).
    eei_krylov[_si]       Lanczos partial band (m ~ 16k) -> the same
                          windowed chain on the m-band -> back-transform
                          through the partial Q.  O(n^2 m) reduce — the
                          large-n top-k path.  The _si variant iterates on
                          (A - sigma I)^{-1} and a final map stage undoes
                          theta = 1/(lambda - sigma).  topk / eigenvalues
                          only: a partial basis has no full-table solve.

Jitted programs are cached per ``(plan, kind, k)``; the sharded backend's
stack is padded up to a multiple of the mesh batch axis and sliced back.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.engine import registry
from repro.engine.plan import SolverPlan, plan_for


class SolveResult(NamedTuple):
    """Full-table result: ``eigenvalues (..., n)`` ascending and the
    component-magnitude table ``magnitudes (..., n, n)`` (rows are
    eigenvectors, dense basis)."""

    eigenvalues: jax.Array
    magnitudes: jax.Array


class TopkResult(NamedTuple):
    """``eigenvalues (..., k)`` ascending and signed, unit-norm eigenvectors
    ``vectors (..., k, n)`` (rows are eigenvectors, dense basis)."""

    eigenvalues: jax.Array
    vectors: jax.Array

    # Class-level marker so callers can test `result.degraded` uniformly;
    # the server's ``DegradedResult`` subclass overrides it per instance.
    degraded = False


class PackedTopkResult(NamedTuple):
    """Per-slot windows of a segment-packed stack: ``eigenvalues
    (b, S, k)`` ascending per slot and ``vectors (b, S, k, n)`` over the
    full packed-row width (each segment's columns live at its offset; the
    server slices them out on retire).  Slots with fewer than ``k`` real
    eigenvalues carry finite sentinel values *outside* the slice a
    ``k' <= seg_len`` request reads — at the front for ``largest`` windows,
    at the back for smallest — mirroring the bucketed guard convention."""

    eigenvalues: jax.Array
    vectors: jax.Array


class ProgramSpec(NamedTuple):
    """Static description of one jitted program: kind + window + verify.

    ``verify=True`` appends the backend's ``verify`` stage to the chain:
    the program then returns ``(TopkResult, VerifyFlags)`` instead of the
    bare result (topk / packed_topk programs only)."""

    kind: str  # solve | topk | eigenvalues | packed_topk | update
    k: int = 0  # 0 -> no window (full spectrum)
    largest: bool = True
    verify: bool = False
    # ``update`` programs only: retained Ritz pairs kept per session and the
    # number of augmentation directions (u + Lanczos extension vectors) the
    # warm-project reduce appends to the retained basis.
    m_keep: int = 0
    ext: int = 0


def _renormalize(vecs: jax.Array) -> jax.Array:
    nrm = jnp.linalg.norm(vecs, axis=-1, keepdims=True)
    return vecs / jnp.maximum(nrm, 1e-30)


def _batched_eigh(a: jax.Array):
    return jax.vmap(jnp.linalg.eigh)(a)


def _back_transform(w: jax.Array, q: jax.Array) -> jax.Array:
    """Rows ``w[.., i, :]`` of tridiagonal eigenvectors -> dense ``v = Q w``."""
    return jnp.einsum("...in,...jn->...ij", w, q)


# ---------------------------------------------------------------------------
# Stage builders: (role, name) -> builder(lib, plan, spec) -> fn(state)->dict
# ---------------------------------------------------------------------------


def _b_householder(lib, plan, spec):
    with_q = spec.kind != "eigenvalues"

    def fn(st):
        d, e, q = lib.tridiagonalize(st["a"], with_q)
        return {"d": d, "e": e, "q": q}

    return fn


def _b_eigh(lib, plan, spec):
    def fn(st):
        lam, v = _batched_eigh(st["a"])
        return {"lam": lam, "v": v}

    return fn


def _b_eigh_topk(lib, plan, spec):
    def fn(st):
        lam, v, idx = st["lam"], st["v"], st["idx"]
        return {"lam_sel": lam[..., idx],
                "vecs": jnp.swapaxes(v[..., :, idx], -1, -2)}

    return fn


def _b_eigh_solve(lib, plan, spec):
    def fn(st):
        return {"mags": jnp.swapaxes(st["v"] * st["v"], -1, -2)}

    return fn


def _b_dense_eigenvalues(lib, plan, spec):
    return lambda st: {"lam": lib.dense_eigenvalues(st["a"])}


def _b_tridiag_full(lib, plan, spec):
    return lambda st: {"lam": lib.tridiag_eigenvalues(st["d"], st["e"])}


def _b_tridiag_windowed(lib, plan, spec):
    k, largest = spec.k, spec.largest

    def fn(st):
        # spec.k == 0 (full-eigenvalues program on a windowed chain) means
        # "the whole band" — which on a Krylov reduce is the band size m,
        # not n, so the width comes from the band itself.
        return {"lam_sel": lib.tridiag_eigenvalues_windowed(
            st["d"], st["e"], k or st["d"].shape[-1], largest)}

    return fn


def _b_krylov(lib, plan, spec):
    k, largest = spec.k, spec.largest

    def fn(st):
        d, e, q, steps = lib.krylov_reduce(
            st["a"], k or st["a"].shape[-1], largest)
        return {"d": d, "e": e, "q": q, "steps": steps}

    return fn


def _b_krylov_si(lib, plan, spec):
    k, largest = spec.k, spec.largest

    def fn(st):
        d, e, q, sigma, steps = lib.krylov_shift_invert_reduce(
            st["a"], k or st["a"].shape[-1], largest)
        return {"d": d, "e": e, "q": q, "sigma": sigma, "steps": steps}

    return fn


def _b_tridiag_windowed_si(lib, plan, spec):
    # The shift-and-invert band lives in theta = 1/(lambda - sigma) space,
    # where the requested extreme of lambda is the *opposite* extreme of
    # theta (sigma sits outside the spectrum on the requested side, so the
    # map is order-reversing there) — hence `not largest`.
    k, largest = spec.k, spec.largest

    def fn(st):
        return {"lam_sel": lib.tridiag_eigenvalues_windowed(
            st["d"], st["e"], k or st["d"].shape[-1], not largest)}

    return fn


def _b_shift_invert_map(lib, plan, spec):
    def fn(st):
        # theta ascending maps to lambda *descending* (1/x is decreasing on
        # a sign-definite interval), so flip to restore ascending order.
        lam = st["sigma"][..., None] + 1.0 / st["lam_sel"]
        out = {"lam_sel": lam[..., ::-1]}
        if "vecs" in st:
            out["vecs"] = st["vecs"][..., ::-1, :]
        return out

    return fn


def _b_dense_minors(lib, plan, spec):
    return lambda st: {"mu": lib.dense_minor_spectra(st["a"])}


def _b_tridiag_minors(lib, plan, spec):
    return lambda st: {"mu": lib.tridiag_minor_spectra(st["d"], st["e"])}


def _b_eei_full(lib, plan, spec):
    return lambda st: {"mags": lib.magnitudes(st["lam"], st["mu"])}


def _b_eei_select(lib, plan, spec):
    def fn(st):
        mags = lib.magnitudes(st["lam"], st["mu"])
        idx = st["idx"]
        return {"lam_sel": st["lam"][..., idx],
                "mag_sel": mags[..., idx, :]}

    return fn


def _b_eei_windowed(lib, plan, spec):
    def fn(st):
        idx = st["idx"]
        return {"lam_sel": st["lam"][..., idx],
                "mag_sel": lib.magnitudes_windowed(st["lam"], st["mu"], idx)}

    return fn


def _b_minor_det(lib, plan, spec):
    def fn(st):
        return {"mag_sel": lib.minor_det_components(
            st["d"], st["e"], st["lam_sel"])}

    return fn


def _b_tridiag_signs(lib, plan, spec):
    def fn(st):
        w = lib.tridiag_signs(st["d"], st["e"], st["lam_sel"], st["mag_sel"])
        return {"vecs": _renormalize(_back_transform(w, st["q"]))}

    return fn


def _b_tridiag_solve(lib, plan, spec):
    def fn(st):
        # Sign + back-transform every row so the full table reports in the
        # dense basis like the other compositions.
        w = lib.tridiag_signs(st["d"], st["e"], st["lam"], st["mags"])
        v = _back_transform(_renormalize(w), st["q"])
        mags = v * v
        return {"mags": mags / jnp.sum(mags, axis=-1, keepdims=True)}

    return fn


def _b_dense_signs(lib, plan, spec):
    def fn(st):
        return {"vecs": _renormalize(lib.dense_signs(
            st["a"], st["lam_sel"], st["mag_sel"]))}

    return fn


def _b_verify_topk(lib, plan, spec):
    def fn(st):
        return {"flags": lib.verify_topk(st["a"], st["lam_sel"], st["vecs"])}

    return fn


# -- streaming rank-1 update stages -----------------------------------------


def _b_warm_project(lib, plan, spec):
    """Augmented-subspace reduce for the ``update`` kind.

    Projects the *updated* stack onto ``S = [basis; u; A'-Krylov ext]`` —
    the session's retained Ritz basis, the unit update direction (so the
    rank-1 perturbation acts *inside* the span) and ``spec.ext - 1`` short
    Lanczos extension directions that let escaped spectral weight re-enter
    the window.  The projected ``(b, m', m')`` compression tridiagonalizes
    through the backend's own Householder stage, and the composed
    back-transform ``q_eff = S^T q_small`` lifts band eigenvectors straight
    to the dense basis — downstream stages cannot tell this reduce from the
    full Householder one.  Cost is O(m' n^2) versus the from-scratch
    O(n^3): the entire speedup of the update path lives here.
    """
    n_aug = spec.ext  # augmentation directions (the first one is u)

    def _append_ortho(s_rows, v, seed):
        """One more orthonormal row onto ``s_rows`` (CGS2; deterministic
        fallback direction when ``v`` already lies in the span)."""
        n = s_rows.shape[-1]

        def proj_out(x):
            for _ in range(2):
                c = jnp.einsum("...rn,...n->...r", s_rows, x)
                x = x - jnp.einsum("...r,...rn->...n", c, s_rows)
            return x

        v = proj_out(v)
        nrm = jnp.linalg.norm(v, axis=-1, keepdims=True)
        fb = proj_out(jnp.broadcast_to(
            jnp.cos(jnp.arange(n, dtype=s_rows.dtype) * (seed + 2) + 0.1),
            v.shape))
        fb_nrm = jnp.linalg.norm(fb, axis=-1, keepdims=True)
        v = jnp.where(nrm > 1e-6,
                      v / jnp.maximum(nrm, 1e-30),
                      fb / jnp.maximum(fb_nrm, 1e-30))
        return jnp.concatenate([s_rows, v[..., None, :]], axis=-2)

    def fn(st):
        a, basis, u = st["a"], st["basis"], st["u"]
        m = basis.shape[-2]
        # Re-orthonormalize the retained rows (sign-recurrence vectors are
        # orthogonal only to fp accuracy; QR of a near-orthonormal frame is
        # cheap and keeps the Rayleigh-Ritz compression exact).
        qb, _ = jnp.linalg.qr(jnp.swapaxes(basis, -1, -2))
        s_rows = jnp.swapaxes(qb, -1, -2)  # (b, m, n)
        v = u
        for j in range(n_aug):
            s_rows = _append_ortho(s_rows, v, j)
            v = jnp.einsum("...nm,...m->...n", a, s_rows[..., -1, :])
        # Rayleigh-Ritz compression B = S A' S^T, symmetrized.
        t = jnp.einsum("...rn,...nm->...rm", s_rows, a)
        band = jnp.einsum("...rm,...sm->...rs", t, s_rows)
        band = 0.5 * (band + jnp.swapaxes(band, -1, -2))
        d, e, qs = lib.tridiagonalize(band, True)
        q_eff = jnp.einsum("...rn,...rt->...nt", s_rows, qs)
        # Secular weights: coefficients of u on the *retained* frame.
        z = jnp.einsum("...rn,...n->...r", s_rows[..., :m, :], u)
        return {"d": d, "e": e, "q": q_eff, "z2": z * z}

    return fn


def _b_tridiag_bracketed(lib, plan, spec):
    """Warm-bracket spectrum stage for the ``update`` kind.

    Lane brackets come from rank-1 interlacing + Weyl on the cached Ritz
    values (``repro.linalg.interlace.rank1_update_brackets``), widened by a
    slack covering what the verify tolerance lets the cached spectrum
    drift, then tightened one-sided by the secular-equation refinement
    (exact roots of the retained-frame compression: a lower bound for the
    band's ``largest`` window and an upper bound for ``smallest``, by
    Poincare on the nested frames).  The backend's bracketed bisection
    validates every lane's Sturm counts and falls back to Gershgorin where
    a bracket cannot prove containment — a stale session costs iterations,
    never correctness.
    """
    from repro.engine.verify import DEFAULT_TOL
    from repro.linalg import interlace

    k_lanes, largest = spec.m_keep, spec.largest

    def fn(st):
        theta, rho, z2, a = st["theta"], st["rho"], st["z2"], st["a"]
        scale = jnp.sqrt(jnp.sum(a * a, axis=(-2, -1)))  # (b,) ||A'||_F
        slack = (8.0 * DEFAULT_TOL) * scale
        lo, hi = interlace.rank1_update_brackets(
            theta, rho, drift_bound=slack[..., None])
        slo, shi = interlace.secular_bracket_refine(theta, z2, rho, lo, hi)
        sec_pad = (1e-5 * scale)[..., None]
        if largest:
            lo = jnp.maximum(lo, slo - sec_pad)
        else:
            hi = jnp.minimum(hi, shi + sec_pad)
        return {"lam_sel": lib.tridiag_eigenvalues_bracketed(
            st["d"], st["e"], lo, hi, k_lanes, largest)}

    return fn


def _b_update_select(lib, plan, spec):
    """Split the caller's k-window out of the refreshed m_keep-window; the
    full window becomes the session's next ``(basis, theta)``."""
    k, largest = spec.k, spec.largest

    def fn(st):
        lam, vecs = st["lam_sel"], st["vecs"]  # (b, m_keep[, n]) ascending
        if largest:
            lam_k, vecs_k = lam[..., -k:], vecs[..., -k:, :]
        else:
            lam_k, vecs_k = lam[..., :k], vecs[..., :k, :]
        return {"lam_sel": lam_k, "vecs": vecs_k,
                "basis": vecs, "theta": lam}

    return fn


# -- packed (segment-stacked) stages ----------------------------------------


def _b_packed_select(lib, plan, spec):
    """Per-slot window selection from a full eigh of the packed row.

    A packed row is block-diagonal, so eigh's eigenvectors are each
    supported on exactly one segment (or on guard slack) — in-segment mass
    is the ownership test.  Guard eigenpairs have zero mass in every slot
    and near-degenerate *cross-segment* pairs can mix (mass ~ 0.5 each);
    both fail the > 0.5 gate, leaving finite sentinels the per-segment
    verify stage flags, which escalates the affected requests through the
    server's fallback chain instead of returning mixed vectors.
    """
    k, largest = spec.k, spec.largest

    def fn(st):
        lam, v = st["lam"], st["v"]  # (b, N) asc, (b, N, N) columns = vecs
        seg_off, seg_len = st["seg_off"], st["seg_len"]  # (b, S) int32
        b, n = lam.shape
        col = jnp.arange(n, dtype=jnp.int32)
        in_seg = ((seg_off[:, :, None] <= col[None, None, :])
                  & (col[None, None, :] <
                     (seg_off + seg_len)[:, :, None]))  # (b, S, N_pos)
        mass = jnp.einsum(
            "bsp,bpj->bsj", in_seg.astype(lam.dtype), v * v)  # (b, S, N_vec)
        owned = mass > 0.5
        big = jnp.asarray(jnp.finfo(lam.dtype).max, lam.dtype) / 8
        if largest:
            vals = jnp.where(owned, lam[:, None, :], -big)
            top, idx = jax.lax.top_k(vals, k)  # descending; sentinels last
            lam_seg = top[..., ::-1]  # ascending per slot, sentinels first
            idx = idx[..., ::-1]
        else:
            vals = jnp.where(owned, -lam[:, None, :], -big)
            top, idx = jax.lax.top_k(vals, k)  # -lam desc = lam ascending
            lam_seg = -top  # ascending per slot, sentinels (+big) last
        vt = jnp.swapaxes(v, -1, -2)  # (b, N_vec, N_pos), rows = vecs
        vecs_seg = vt[jnp.arange(b)[:, None, None], idx, :]  # (b, S, k, N)
        return {"lam_seg": lam_seg, "vecs_seg": vecs_seg}

    return fn


def _b_tridiag_segmented(lib, plan, spec):
    """Per-segment windowed Sturm on the packed band (segmented kernel).

    Provides the flattened ``(b, S*k)`` window as ``lam_sel`` so the
    existing minor-determinant components stage and sign-recurrence recover
    stage run on it unchanged — both treat window lanes independently, and
    on a block-diagonal band the minor-determinant row of an eigenvalue in
    segment ``s`` normalizes to that segment's magnitudes with ~0 mass
    elsewhere (the EEI identity applied to the packed matrix itself).
    """
    k, largest = spec.k, spec.largest

    def fn(st):
        lam_seg = lib.tridiag_eigenvalues_segmented(
            st["d"], st["e"], st["seg_off"], st["seg_len"], k, largest)
        b, s, _ = lam_seg.shape
        return {"lam_sel": lam_seg.reshape(b, s * k)}

    return fn


def _b_packed_reshape(lib, plan, spec):
    k = spec.k

    def fn(st):
        lam_sel, vecs = st["lam_sel"], st["vecs"]  # (b, S*k), (b, S*k, N)
        b = lam_sel.shape[0]
        s = st["seg_off"].shape[1]
        return {"lam_seg": lam_sel.reshape(b, s, k),
                "vecs_seg": vecs.reshape(b, s, k, vecs.shape[-1])}

    return fn


def _b_verify_topk_packed(lib, plan, spec):
    def fn(st):
        return {"flags": lib.verify_topk_packed(
            st["a"], st["seg_off"], st["seg_len"], st["lam_seg"],
            st["vecs_seg"], spec.largest)}

    return fn


#: The verify stage appended to a topk chain when ``spec.verify`` is set.
#: Not part of any registered composition — the engine appends it, so every
#: method/backend pair gets verification without N new compositions.
_VERIFY_SIG = registry.StageSig(
    role="verify", name="verify_topk",
    requires=("a", "lam_sel", "vecs"), provides=("flags",))

#: Packed twin: per-slot flags ``(b, S)`` so one bad segment degrades one
#: request, not the whole packed row (the PR-7 guarantee held per request).
_PACKED_VERIFY_SIG = registry.StageSig(
    role="verify", name="verify_topk_packed",
    requires=("a", "seg_off", "seg_len", "lam_seg", "vecs_seg"),
    provides=("flags",))


_STAGE_BUILDERS = {
    ("reduce", "householder"): _b_householder,
    ("reduce", "krylov"): _b_krylov,
    ("reduce", "krylov_shift_invert"): _b_krylov_si,
    ("spectrum", "eigh"): _b_eigh,
    ("spectrum", "dense_eigenvalues"): _b_dense_eigenvalues,
    ("spectrum", "tridiag_full"): _b_tridiag_full,
    ("spectrum", "tridiag_windowed"): _b_tridiag_windowed,
    ("spectrum", "tridiag_windowed_si"): _b_tridiag_windowed_si,
    ("minor_spectra", "dense_minors"): _b_dense_minors,
    ("minor_spectra", "tridiag_minors"): _b_tridiag_minors,
    ("components", "eei_full"): _b_eei_full,
    ("components", "eei_select"): _b_eei_select,
    ("components", "eei_windowed"): _b_eei_windowed,
    ("components", "minor_det"): _b_minor_det,
    ("recover", "eigh_topk"): _b_eigh_topk,
    ("recover", "eigh_solve"): _b_eigh_solve,
    ("recover", "tridiag_signs"): _b_tridiag_signs,
    ("recover", "tridiag_solve"): _b_tridiag_solve,
    ("recover", "dense_signs"): _b_dense_signs,
    ("recover", "shift_invert_map"): _b_shift_invert_map,
    ("spectrum", "tridiag_segmented"): _b_tridiag_segmented,
    ("recover", "packed_select"): _b_packed_select,
    ("recover", "packed_reshape"): _b_packed_reshape,
    ("reduce", "warm_project"): _b_warm_project,
    ("spectrum", "tridiag_bracketed"): _b_tridiag_bracketed,
    ("recover", "update_select"): _b_update_select,
    ("verify", "verify_topk"): _b_verify_topk,
    ("verify", "verify_topk_packed"): _b_verify_topk_packed,
}


def register_stage_builder(role: str, name: str, builder) -> None:
    """Register (or replace) the builder behind a composition stage name."""
    _STAGE_BUILDERS[(role, name)] = builder


# ---------------------------------------------------------------------------
# Generic graph executor
# ---------------------------------------------------------------------------


def _resolve_chain(plan: SolverPlan, spec: ProgramSpec):
    """Pick the composition + chain a program executes.

    * ``topk`` uses the windowed composition when the plan asks for it
      (``plan.spectrum == "windowed"``);
    * ``solve`` always uses the method's full composition — a full table
      needs every spectrum row by definition;
    * ``eigenvalues`` with a window (``spec.k > 0``) prefers the windowed
      composition's eigenvalue chain (index-targeted bisection); methods
      without one run the full chain and the executor slices the window
      (bitwise-identical, since bisection lanes are index-independent).
    """
    if spec.kind in ("topk", "packed_topk", "update"):
        windowed = plan.spectrum == "windowed"
    elif spec.kind == "eigenvalues":
        windowed = spec.k > 0
    else:
        windowed = False
    comp = registry.composition_for(plan.method, windowed)
    chain = comp.chain(spec.kind)
    if chain is None:
        comp = registry.composition_for(plan.method, False)
        chain = comp.chain(spec.kind)
    if chain is None:
        raise ValueError(
            f"composition {comp.name!r} declares no {spec.kind!r} chain")
    return comp, chain


def _window_idx(n: int, k: int, largest: bool) -> jax.Array:
    return jnp.arange(n - k, n) if largest else jnp.arange(k)


def _bind_chain(lib, plan: SolverPlan, spec: ProgramSpec, chain) -> list:
    """``[(role, stage fn), ...]`` for a resolved chain."""
    return [(sig.role, _STAGE_BUILDERS[(sig.role, sig.name)](lib, plan, spec))
            for sig in chain]


def _run_chain(stages: list, state: dict) -> dict:
    """Thread ``state`` through the stages, each under a ``jax.named_scope``
    of its role, so every device op's metadata names the stage that
    issued it (reduce, spectrum, components, recover, verify, ...)."""
    for role, f in stages:
        with jax.named_scope(role):
            state.update(f(state))
    return state


def _build_program(plan: SolverPlan, spec: ProgramSpec):
    """Jitted graph executor for one ``(plan, spec)``."""
    lib = registry.get_backend(plan)
    _, chain = _resolve_chain(plan, spec)
    if spec.verify:
        if spec.kind != "topk":
            raise ValueError("verify is only supported for topk programs")
        chain = chain + (_VERIFY_SIG,)
    stages = _bind_chain(lib, plan, spec, chain)

    def fn(a):
        n = a.shape[-1]
        state = {"a": a}
        if spec.kind in ("topk", "eigenvalues"):
            # Always present for these kinds — the registry validates
            # chains against exactly this initial state, so a validated
            # chain can never KeyError here.  k=0 (full eigenvalues) gets
            # the identity window.
            state["idx"] = _window_idx(n, spec.k or n, spec.largest)
        state = _run_chain(stages, state)
        if spec.kind == "topk":
            result = TopkResult(state["lam_sel"], state["vecs"])
            if not spec.verify:
                return result
            flags = state["flags"]
            if "steps" in state:
                # A Krylov reduce: report its steps, as a float32 copy.
                # Returning the loop counter itself kept it in an output
                # buffer through the loop, which slowed the n = 8192
                # program by 1% on a TPU v5e (3.207 s against 3.175 s).
                flags = flags._replace(
                    steps=state["steps"].astype(jnp.float32))
            return result, flags
        if spec.kind == "solve":
            return SolveResult(state["lam"], state["mags"])
        if "lam_sel" in state:  # windowed eigenvalue chain
            return state["lam_sel"]
        lam = state["lam"]
        # Windowed query on a full chain (eigh / dense methods): slice —
        # bitwise-identical, every lane is index-independent.
        return lam[..., state["idx"]] if spec.k else lam

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _solve_program(plan: SolverPlan):
    return _build_program(plan, ProgramSpec("solve"))


@functools.lru_cache(maxsize=None)
def topk_program(plan: SolverPlan, k: int, largest: bool,
                 verify: bool = False):
    """The jitted batched top-k program for one ``(plan, k, largest)``.

    Public because the serving runtime's ``ProgramCache`` AOT-compiles it
    per shape bucket, and the stream-conformance tests replay it as the
    synchronous oracle a dispatched stack must match bitwise.  The
    ``lru_cache`` is thread-safe; the returned jitted callable is too.

    With ``verify=True`` the program appends the backend's ``verify`` stage
    and returns ``(TopkResult, VerifyFlags)`` — the serving path's default,
    so no unverified vector reaches a caller.  On a Krylov plan the flags
    carry the Lanczos steps of each row (``VerifyFlags.steps``).
    """
    return _build_program(
        plan, ProgramSpec("topk", int(k), bool(largest), bool(verify)))


@functools.lru_cache(maxsize=None)
def _eigenvalues_program(plan: SolverPlan, k: int = 0, largest: bool = True):
    return _build_program(
        plan, ProgramSpec("eigenvalues", int(k), bool(largest)))


def _build_packed_program(plan: SolverPlan, spec: ProgramSpec):
    """Jitted executor for a segment-packed stack.

    Same graph walk as :func:`_build_program`, but the program takes the
    segment layout as traced operands — ``fn(a, seg_off, seg_len)`` — and
    the final state carries per-slot windows.  The slot count ``S`` is a
    property of the operand shapes, not the cache key: lowering at a
    different ``(b, N, S)`` retraces the same python callable.
    """
    lib = registry.get_backend(plan)
    _, chain = _resolve_chain(plan, spec)
    if spec.verify:
        chain = chain + (_PACKED_VERIFY_SIG,)
    stages = _bind_chain(lib, plan, spec, chain)

    def fn(a, seg_off, seg_len):
        state = _run_chain(stages, {
            "a": a, "seg_off": seg_off.astype(jnp.int32),
            "seg_len": seg_len.astype(jnp.int32)})
        result = PackedTopkResult(state["lam_seg"], state["vecs_seg"])
        return (result, state["flags"]) if spec.verify else result

    return jax.jit(fn)


def _build_update_program(plan: SolverPlan, spec: ProgramSpec):
    """Jitted executor for the streaming rank-1 ``update`` kind.

    ``fn(a_prev, basis, theta, u, rho)`` applies the rank-1 perturbation on
    device (``a = a_prev + rho * u u^T``, ``u`` unit, ``rho`` signed
    ``||u||^2``), walks the method's ``update`` chain and *always* appends
    the verify stage — the session's drift monitor reads the flags, so the
    fast path can never silently hand back stale eigenpairs.  Returns
    ``(TopkResult, VerifyFlags, a, basis', theta')``; the trailing state is
    what the session caches (device-resident) for the next update.
    """
    lib = registry.get_backend(plan)
    _, chain = _resolve_chain(plan, spec)
    chain = chain + (_VERIFY_SIG,)
    stages = _bind_chain(lib, plan, spec, chain)

    def fn(a_prev, basis, theta, u, rho):
        a = a_prev + rho[..., None, None] * u[..., :, None] * u[..., None, :]
        n = a.shape[-1]
        state = _run_chain(stages, {
            "a": a, "basis": basis, "theta": theta, "u": u, "rho": rho,
            "idx": _window_idx(n, spec.k, spec.largest)})
        result = TopkResult(state["lam_sel"], state["vecs"])
        return result, state["flags"], a, state["basis"], state["theta"]

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def update_program(plan: SolverPlan, k: int, largest: bool, m_keep: int,
                   ext: int):
    """The jitted batched rank-1 update program for one session geometry.

    ``m_keep`` is the retained Ritz window (``k`` + the session's buffer)
    and ``ext`` the total augmentation directions (u + Lanczos extensions;
    0 when the basis already spans the frame).  Cached like
    :func:`topk_program`; sessions with the same geometry share compiles.
    """
    return _build_update_program(
        plan, ProgramSpec("update", int(k), bool(largest), True,
                          int(m_keep), int(ext)))


@functools.lru_cache(maxsize=None)
def packed_topk_program(plan: SolverPlan, k: int, largest: bool,
                        verify: bool = False):
    """The jitted per-slot top-k program for one segment-packed layout.

    ``k`` is the *slot window* (the packer's fixed per-slot lane count —
    every packed request's ``k`` is <= it); the serving runtime slices each
    request's ``k' <= k`` window out per slot on retire, exactly as the
    bucketed path slices its pow2-k window.  With ``verify=True`` the
    program returns ``(PackedTopkResult, flags (b, S))`` — per-slot flags,
    so one poisoned segment degrades one request, not its whole row.
    """
    return _build_packed_program(
        plan, ProgramSpec("packed_topk", int(k), bool(largest),
                          bool(verify)))


@dataclasses.dataclass(frozen=True)
class SolverEngine:
    """Batched EEI solver executing one :class:`SolverPlan`."""

    plan: SolverPlan = SolverPlan()

    @classmethod
    def for_problem(cls, shape: tuple, **kwargs) -> "SolverEngine":
        """Engine with a plan inferred from problem shape (see ``plan_for``)."""
        return cls(plan_for(shape, **kwargs))

    # -- public batched API ---------------------------------------------------

    def solve(self, a: jax.Array) -> SolveResult:
        """Eigenvalues + the full ``|v[i, j]|^2`` table for ``a``.

        ``a`` is ``(n, n)`` or a stack ``(b, n, n)``; results carry the same
        leading axis.  On every backend the magnitudes live in the dense
        basis (the tridiagonal path back-transforms with ``Q``).  Always
        runs the method's *full* composition — a full table needs every
        spectrum row, so ``plan.spectrum`` does not apply here.
        """
        return self._run(_solve_program(self.plan), a)

    def topk(self, a: jax.Array, k: int, largest: bool = True) -> TopkResult:
        """Top-k (eigenvalue, signed unit eigenvector) pairs per matrix."""
        if k < 1 or k > a.shape[-1]:
            raise ValueError(f"k={k} out of range for n={a.shape[-1]}")
        return self._run(topk_program(self.plan, int(k), bool(largest)), a)

    def eigenvalues(self, a: jax.Array, k: Optional[int] = None,
                    largest: bool = True) -> jax.Array:
        """Eigenvalues only: ``(..., n)`` ascending, or — with ``k`` — the
        ``k`` extremal eigenvalues ``(..., k)`` ascending via the windowed
        spectrum stage (index-targeted bisection on the tridiagonal path:
        ``k`` lanes instead of ``n``, bitwise-equal to the full slice)."""
        if k is not None and (k < 1 or k > a.shape[-1]):
            raise ValueError(f"k={k} out of range for n={a.shape[-1]}")
        # `largest` is dead without a window — normalize it out of the
        # program cache key so k=None never compiles twice.
        program = _eigenvalues_program(
            self.plan, int(k or 0), bool(largest) if k else True)
        return self._run(program, a)

    # -- streaming sessions ---------------------------------------------------

    def open_session(self, a: jax.Array, k: int, largest: bool = True,
                     config=None):
        """Open a :class:`~repro.engine.session.SpectralSession` on one
        ``(n, n)`` matrix: a full solve seeds the retained Ritz window and
        subsequent :meth:`update` calls maintain it under rank-1 drift."""
        from repro.engine import session as session_mod

        return session_mod.open_session(
            self, a, int(k), bool(largest), config)

    def update(self, session, delta):
        """Apply a rank-1 (or small rank-r, as r sequential rank-1) update
        ``A <- A + sign * u u^T`` to a session and return the refreshed
        :class:`TopkResult`.  ``delta`` is ``u``, ``(u, sign)``, a
        :class:`~repro.engine.session.Rank1Update`, or a sequence of those.
        The fast warm-started path runs unless the session's drift monitor
        (accumulated ``|rho|``, verify flags, update cadence) demands a
        full re-solve — it can never silently return stale eigenpairs."""
        from repro.engine import session as session_mod

        return session_mod.apply_update(self, session, delta)

    # -- execution helpers ----------------------------------------------------

    def _run(self, program, a: jax.Array):
        a = jnp.asarray(a)
        if a.ndim not in (2, 3):
            raise ValueError(f"expected (n, n) or (b, n, n), got {a.shape}")
        squeeze = a.ndim == 2
        if squeeze:
            a = a[None]
        if self.plan.precision is not None:
            a = a.astype(jnp.dtype(
                {"float32": jnp.float32, "float64": jnp.float64}
                [self.plan.precision]))
        b = a.shape[0]
        if b == 0:
            raise ValueError("cannot solve an empty matrix stack")
        step = self.plan.max_batch if self.plan.max_batch > 0 else b
        # Every chunk runs at the full `step` shape — the ragged tail (e.g.
        # b=100, max_batch=64 -> a 36-row remainder) is padded up and sliced
        # so chunked solves reuse one compiled executable instead of
        # compiling a second program for the tail shape.
        pad_to = step if b > step else 0
        outs = [self._run_chunk(program, a[i0:i0 + step], pad_to=pad_to)
                for i0 in range(0, b, step)]
        out = outs[0] if len(outs) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *outs)
        return jax.tree.map(lambda x: x[0], out) if squeeze else out

    def _run_chunk(self, program, a: jax.Array, pad_to: int = 0):
        # Pad the stack up to `pad_to` (tail chunks of a microbatched run)
        # and to the mesh batch axis (the sharded backend needs the stack
        # divisible by it) by repeating the first matrix; slice back after.
        b = a.shape[0]
        mult = self.plan.batch_axis_size
        target = max(b, pad_to)
        target += (-target) % mult
        pad = target - b
        if pad:
            a = jnp.concatenate(
                [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])])
        out = program(a)
        if pad:
            out = jax.tree.map(lambda x: x[:b], out)
        return out
