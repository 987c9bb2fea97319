"""Pallas TPU kernel: tiled log-space eigenvalue-difference products.

Computes ``out[b, i, j] = sum_k mask[k, j] * log|lam[b, i] - mu_t[b, k, j]|``
— the EEI numerator hot loop (O(b n^3) log-diff terms for full component
tables over a stack of ``b`` matrices).

Design (TPU-native re-think of the paper's "batched products"):

* **batch is a first-class grid axis**: one ``pallas_call`` covers the whole
  ``(b, n, n)`` stack with a 4-D ``(B/bb, I/bi, J/bj, K/bk)`` grid — no
  ``jax.vmap`` lifting, no per-matrix program launches, and the validity
  mask is *shared* across the batch (every matrix in a stack has the same
  shape) so it is fetched once per tile instead of once per matrix;
* **the batch axis itself is tiled** (``bb >= 1`` matrices per grid step):
  at very small ``n`` a single matrix's ``(bi, bj)`` tile underfills the
  8x128 VPU registers, so a batch tile stacks ``bb`` matrices into one
  rank-4 ``(bb, bi, chunk, bj)`` VPU op and recovers sublane occupancy;
  ``bb = 1`` reproduces the PR-2 one-matrix-per-step grid exactly;
* the paper's batch = our VMEM tile; per-batch partial ratios = per-tile
  partial log-sums accumulated across the ``k`` grid axis;
* log-space replaces the paper's ratio-pairing as the overflow fix, so tile
  shape is chosen purely for VMEM/VPU efficiency, not numerics;
* layout: ``i`` on sublanes, ``j`` on lanes, ``k`` swept inside the tile in
  sublane-sized chunks (a ``fori_loop`` of rank-3 ``(bi, 8, bj)`` VPU ops —
  8 mu rows per step instead of one, working set = one ``(bk, bj)`` mu tile
  + one chunk + one ``(bi, bj)`` accumulator).  Each chunk is read straight
  from the VMEM ref at a sublane-aligned offset
  (``pl.ds(pl.multiple_of(c * 8, 8), 8)``): Mosaic does not lower a dynamic
  slice of an already-loaded value.  VMEM: at the static ``128^3``,
  ``bb = 1`` tiles, lam (its ``(bi, 1)`` column pads to 128 lanes), mu,
  mask and out are one 64 KiB f32 tile each, about 0.5 MiB double-buffered
  — far under v5e's 16 MiB scoped limit;
* ``mu`` is passed transposed ``(K, J)`` so the lane dimension of every load
  matches the lane dimension of the output tile (no in-kernel transposes).

Grid: ``(B/bb, I/bi, J/bj, K/bk)`` with ``k`` innermost; the output block is
revisited across ``k`` steps and accumulated in place (initialized at
``k == 0``).  The legacy single-matrix 3-D grid (the PR-1 kernel this
replaces on the engine path) is kept as ``logabs_sum_padded`` — it is the
vmapped baseline the batched grid is benchmarked against
(``benchmarks/throughput.py``) and parity-tested against
(``tests/test_kernels.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: ``k`` rows consumed per inner-loop step (one f32 sublane granule).
K_CHUNK = 8


def _logabs_sum_batched_kernel(
    lam_ref, mut_ref, mask_ref, floor_ref, out_ref, *, block_k
):
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lam = lam_ref[...]  # (bb, bi, 1)
    floor = floor_ref[...]  # (bb, 1, 1) per-matrix gap clamp

    def body(c, acc):
        rows = pl.ds(pl.multiple_of(c * K_CHUNK, K_CHUNK), K_CHUNK)
        mu_c = mut_ref[:, rows, :]  # (bb, K_CHUNK, bj)
        m_c = mask_ref[rows, :]  # (K_CHUNK, bj), shared across the batch
        # (bb, bi, K_CHUNK, bj): bb matrices advance in one VPU op.
        ad = jnp.abs(lam[:, :, :, None] - mu_c[:, None, :, :])
        ad = jnp.where(
            m_c[None, None, :, :] > 0,
            jnp.maximum(ad, floor[:, :, :, None]), 1.0)
        return acc + jnp.sum(jnp.log(ad), axis=2)

    acc = jax.lax.fori_loop(
        0, block_k // K_CHUNK, body, jnp.zeros(out_ref.shape, out_ref.dtype)
    )
    out_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_i", "block_j", "block_k", "interpret"),
)
def logabs_sum_batched_padded(
    lam_col: jax.Array,  # (B, I, 1), B % block_b == 0, I % block_i == 0
    mu_t: jax.Array,  # (B, K, J), K % block_k == 0, J % block_j == 0
    mask_t: jax.Array,  # (K, J) 1.0 valid / 0.0 padded — shared across B
    floor: jax.Array,  # (B, 1, 1) per-matrix gap clamp (1.0 on padded rows)
    *,
    block_b: int = 1,
    block_i: int = 128,
    block_j: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Natively batched pallas_call on pre-padded operands (see ops)."""
    if block_k % K_CHUNK:
        raise ValueError(f"block_k must be a multiple of {K_CHUNK}, got {block_k}")
    b_total, i_total, _ = lam_col.shape
    k_total, j_total = mask_t.shape
    if b_total % block_b:
        raise ValueError(
            f"batch {b_total} not a multiple of block_b={block_b}")
    grid = (b_total // block_b, i_total // block_i, j_total // block_j,
            k_total // block_k)
    return pl.pallas_call(
        functools.partial(_logabs_sum_batched_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_i, 1), lambda b, i, j, k: (b, i, 0)),
            pl.BlockSpec(
                (block_b, block_k, block_j), lambda b, i, j, k: (b, k, j)),
            pl.BlockSpec((block_k, block_j), lambda b, i, j, k: (k, j)),
            pl.BlockSpec((block_b, 1, 1), lambda b, i, j, k: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_b, block_i, block_j), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b_total, i_total, j_total), lam_col.dtype),
        interpret=interpret,
    )(lam_col, mu_t, mask_t, floor)


def _logabs_sum_batched_masked_kernel(
    lam_ref, mut_ref, mask_ref, floor_ref, out_ref, *, block_k
):
    """Per-batch-mask twin of :func:`_logabs_sum_batched_kernel`.

    The mask tile carries a leading batch axis — each matrix in the stack
    masks its *own* ``(k, j)`` validity pattern.  This is the packed-dispatch
    plumbing: a segment-packed stack is ragged per row (each row's valid
    ``(j, k)`` region is its own segment layout), so the shared-mask
    assumption of the uniform bucketed path no longer holds.
    """
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lam = lam_ref[...]  # (bb, bi, 1)
    floor = floor_ref[...]  # (bb, 1, 1)

    def body(c, acc):
        rows = pl.ds(pl.multiple_of(c * K_CHUNK, K_CHUNK), K_CHUNK)
        mu_c = mut_ref[:, rows, :]  # (bb, K_CHUNK, bj)
        m_c = mask_ref[:, rows, :]  # (bb, K_CHUNK, bj) — per-matrix validity
        ad = jnp.abs(lam[:, :, :, None] - mu_c[:, None, :, :])
        ad = jnp.where(
            m_c[:, None, :, :] > 0,
            jnp.maximum(ad, floor[:, :, :, None]), 1.0)
        return acc + jnp.sum(jnp.log(ad), axis=2)

    acc = jax.lax.fori_loop(
        0, block_k // K_CHUNK, body, jnp.zeros(out_ref.shape, out_ref.dtype)
    )
    out_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_i", "block_j", "block_k", "interpret"),
)
def logabs_sum_batched_masked_padded(
    lam_col: jax.Array,  # (B, I, 1)
    mu_t: jax.Array,  # (B, K, J)
    mask_t: jax.Array,  # (B, K, J) 1.0 valid / 0.0 masked — per matrix
    floor: jax.Array,  # (B, 1, 1)
    *,
    block_b: int = 1,
    block_i: int = 128,
    block_j: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Batched pallas_call with a per-matrix validity mask (see ops)."""
    if block_k % K_CHUNK:
        raise ValueError(f"block_k must be a multiple of {K_CHUNK}, got {block_k}")
    b_total, i_total, _ = lam_col.shape
    _, k_total, j_total = mask_t.shape
    if b_total % block_b:
        raise ValueError(
            f"batch {b_total} not a multiple of block_b={block_b}")
    grid = (b_total // block_b, i_total // block_i, j_total // block_j,
            k_total // block_k)
    return pl.pallas_call(
        functools.partial(_logabs_sum_batched_masked_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_i, 1), lambda b, i, j, k: (b, i, 0)),
            pl.BlockSpec(
                (block_b, block_k, block_j), lambda b, i, j, k: (b, k, j)),
            pl.BlockSpec(
                (block_b, block_k, block_j), lambda b, i, j, k: (b, k, j)),
            pl.BlockSpec((block_b, 1, 1), lambda b, i, j, k: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_b, block_i, block_j), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b_total, i_total, j_total), lam_col.dtype),
        interpret=interpret,
    )(lam_col, mu_t, mask_t, floor)


# ---------------------------------------------------------------------------
# Legacy single-matrix 3-D grid (PR-1) — kept as the vmapped baseline.
# ---------------------------------------------------------------------------


def _logabs_sum_kernel(lam_ref, mut_ref, mask_ref, floor_ref, out_ref, *, block_k):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lam = lam_ref[...]  # (bi, 1) sublane vector
    floor = floor_ref[0, 0]

    def body(kk, acc):
        mu_row = mut_ref[pl.ds(kk, 1), :]  # (1, bj)
        m_row = mask_ref[pl.ds(kk, 1), :]  # (1, bj)
        ad = jnp.abs(lam - mu_row)  # (bi, bj)
        ad = jnp.where(m_row > 0, jnp.maximum(ad, floor), 1.0)
        return acc + jnp.log(ad)

    acc = jax.lax.fori_loop(0, block_k, body, jnp.zeros_like(out_ref[...]))
    out_ref[...] += acc


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_j", "block_k", "interpret")
)
def logabs_sum_padded(
    lam_col: jax.Array,  # (I, 1), I % block_i == 0
    mu_t: jax.Array,  # (K, J), K % block_k == 0, J % block_j == 0
    mask_t: jax.Array,  # (K, J) 1.0 valid / 0.0 padded
    floor: jax.Array,  # (1, 1) gap clamp
    *,
    block_i: int = 128,
    block_j: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Legacy per-matrix pallas_call on pre-padded operands (see ops)."""
    i_total, _ = lam_col.shape
    k_total, j_total = mu_t.shape
    grid = (i_total // block_i, j_total // block_j, k_total // block_k)
    return pl.pallas_call(
        functools.partial(_logabs_sum_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_k, block_j), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k, block_j), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, block_j), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((i_total, j_total), lam_col.dtype),
        interpret=interpret,
    )(lam_col, mu_t, mask_t, floor)
