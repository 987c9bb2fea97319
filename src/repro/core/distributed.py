"""Distributed EEI — the SolverEngine's ``sharded`` backend.

Formerly a pair of free ``shard_map`` functions; now the mesh logic is a
proper backend (``make_sharded_backend``) registered with the engine
registry, so distributed execution is chosen by a ``SolverPlan`` like any
other backend.  Three axes, composable:

* ``batch axis`` (= the mesh data axis): the matrix *stack* is sharded —
  each device runs the whole tridiagonalize -> Sturm -> EEI -> signs
  pipeline on its slice of the batch.  Zero collectives; this is the
  serving-throughput axis the engine pads/unpads for.
* ``minor axis`` (components ``j``, = the mesh model axis): within the
  dense method each device owns a slice of minors, computes their spectra
  and its column-block of ``|v[i, j]|^2`` — the embarrassingly-parallel
  outer loop the paper could not express with CPython threads
  (``minor_sharded_magnitudes``).
* ``term axis`` (product terms ``k``): the *inner* product is sharded; each
  device log-reduces a contiguous batch of eigenvalue-difference terms,
  joined with one ``psum``.  This is Algorithm 2's ``dispatch``/``join``
  (lines 9-15) verbatim with batch boundary = shard boundary — the paper's
  Amdahl bottleneck (thread management) becomes a single collective
  (``term_sharded_component``).

All programs are ``shard_map`` over an explicit mesh and lower/compile on
the production meshes (see ``launch/dryrun.py --arch paper-eei``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import identity, minors
from repro.engine.plan import SolverPlan
from repro.engine.registry import StageLibrary


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: stages contain
    custom-call primitives (eigvalsh) without replication rules, and mesh
    axes the specs don't mention (e.g. ``model``) would otherwise fail it.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Sharded backend: batch axis = data axis
# ---------------------------------------------------------------------------


def make_sharded_backend(plan: SolverPlan) -> StageLibrary:
    """Stage library running the fused-jnp stages under ``shard_map``.

    Every stage shards its leading batch axis over ``plan.batch_axis``; the
    pipeline is batch-parallel, so no collectives are needed until a caller
    gathers.  The engine guarantees divisibility by padding the stack.
    Replicated side inputs (the windowed ``idx`` gather) carry a
    no-axis spec.
    """
    from repro.engine.backends import make_jnp_backend

    inner = make_jnp_backend(plan)
    mesh, axis = plan.mesh, plan.batch_axis

    def spec(rank) -> P:
        if rank == "r1":  # rank-1 replicated side input (e.g. idx)
            return P(None)
        return P(*((axis,) + (None,) * (rank - 1)))

    def shard(fn, in_ranks, out_ranks):
        return _shard_map(
            fn,
            mesh,
            tuple(spec(r) for r in in_ranks),
            (tuple(spec(r) for r in out_ranks)
             if isinstance(out_ranks, tuple) else spec(out_ranks)),
        )

    def tridiagonalize(a, with_q=True):
        if with_q:
            return shard(lambda x: inner.tridiagonalize(x, True),
                         (3,), (2, 2, 3))(a)
        d, e = shard(lambda x: inner.tridiagonalize(x, False)[:2],
                     (3,), (2, 2))(a)
        return d, e, None

    def tridiag_eigenvalues_windowed(d, e, k, largest):
        # k/largest are static at trace time — close over them so the
        # shard_mapped callable is array-only.
        return shard(
            lambda dd, ee: inner.tridiag_eigenvalues_windowed(
                dd, ee, k, largest),
            (2, 2), 2)(d, e)

    def tridiag_eigenvalues_bracketed(d, e, lo, hi, k, largest):
        return shard(
            lambda dd, ee, ll, hh: inner.tridiag_eigenvalues_bracketed(
                dd, ee, ll, hh, k, largest),
            (2, 2, 2, 2), 2)(d, e, lo, hi)

    def krylov_reduce(a, k, largest):
        # Batch-parallel like every other stage: each device runs the
        # Lanczos loop on its slice of the stack (k/largest static).
        return shard(lambda x: inner.krylov_reduce(x, k, largest),
                     (3,), (2, 2, 3, 1))(a)

    def krylov_shift_invert_reduce(a, k, largest):
        return shard(
            lambda x: inner.krylov_shift_invert_reduce(x, k, largest),
            (3,), (2, 2, 3, 1, 1))(a)

    return StageLibrary("sharded", {
        "tridiagonalize": tridiagonalize,
        "tridiag_eigenvalues": shard(inner.tridiag_eigenvalues, (2, 2), 2),
        "tridiag_eigenvalues_windowed": tridiag_eigenvalues_windowed,
        "tridiag_eigenvalues_bracketed": tridiag_eigenvalues_bracketed,
        "tridiag_minor_spectra": shard(
            inner.tridiag_minor_spectra, (2, 2), 3),
        "dense_eigenvalues": shard(inner.dense_eigenvalues, (3,), 2),
        "dense_minor_spectra": shard(inner.dense_minor_spectra, (3,), 3),
        "magnitudes": shard(inner.magnitudes, (2, 3), 3),
        "magnitudes_windowed": shard(
            inner.magnitudes_windowed, (2, 3, "r1"), 3),
        "minor_det_components": shard(
            inner.minor_det_components, (2, 2, 2), 3),
        "tridiag_signs": shard(inner.tridiag_signs, (2, 2, 2, 3), 3),
        "dense_signs": shard(inner.dense_signs, (3, 2, 3), 3),
        "krylov_reduce": krylov_reduce,
        "krylov_shift_invert_reduce": krylov_shift_invert_reduce,
        # Verification is element-wise over the batch axis with no
        # cross-shard dataflow — plain jnp under the enclosing jit lets
        # GSPMD partition it; no shard_map wrapper needed.
        "verify_topk": inner.verify_topk,
    })


# ---------------------------------------------------------------------------
# Minor / term axes (single matrix, model axis) — as before, used by the
# dense method when one matrix must spread over many devices.
# ---------------------------------------------------------------------------


def minor_sharded_magnitudes(a: jax.Array, mesh: Mesh, axis: str = "model"):
    """All ``|v[i, j]|^2`` with minors sharded over ``axis``.

    ``n`` must be divisible by the axis size.  Input ``a`` is replicated;
    output is sharded over components ``j``.
    """

    def block(a_rep, j_block):
        # j_block: (n_local,) global component indices owned by this device.
        lam = jnp.linalg.eigvalsh(a_rep)
        mu = jax.vmap(
            lambda j: jnp.linalg.eigvalsh(minors.minor(a_rep, j))
        )(j_block)
        log_num = identity.logabs_numerator(lam, mu)  # (n, n_local)
        log_den = identity.logabs_denominator(lam)  # (n,)
        return jnp.exp(log_num - log_den[:, None])

    n = a.shape[0]
    j_all = jnp.arange(n)
    fn = _shard_map(block, mesh, (P(), P(axis)), P(None, axis))
    return fn(a, j_all)


# Backwards-compatible alias (pre-engine name).
sharded_magnitudes = minor_sharded_magnitudes


def term_sharded_component(
    lam: jax.Array, mu_j: jax.Array, i: int, mesh: Mesh, axis: str = "model"
):
    """Single component with the *product terms* sharded (Algorithm 2 dispatch).

    ``lam`` (n,), ``mu_j`` (n-1,) replicated in; each device log-reduces its
    term shard; one ``psum`` joins.  Term vectors are padded with 1.0
    (``log 1 = 0``) to a multiple of the axis size.
    """

    def block(numer_terms_local, denom_terms_local):
        part = jnp.sum(jnp.log(jnp.abs(numer_terms_local))) - jnp.sum(
            jnp.log(jnp.abs(denom_terms_local))
        )
        return jax.lax.psum(part, axis)

    lam_wo_i = minors.delete_index(lam, jnp.asarray(i))
    numer_terms = lam[i] - mu_j
    denom_terms = lam[i] - lam_wo_i
    axis_size = mesh.shape[axis]
    pad = (-numer_terms.shape[0]) % axis_size
    if pad:
        ones = jnp.ones((pad,), lam.dtype)
        numer_terms = jnp.concatenate([numer_terms, ones])
        denom_terms = jnp.concatenate([denom_terms, ones])
    fn = _shard_map(block, mesh, (P(axis), P(axis)), P())
    return jnp.exp(fn(numer_terms, denom_terms))
