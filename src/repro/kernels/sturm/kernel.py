"""Pallas TPU kernel: batched Sturm-sequence bisection eigenvalues.

One program instance computes a ``(bb, bm)`` tile of eigenvalues — ``bb``
tridiagonal matrices on sublanes, ``bm`` eigenvalue indices on lanes.  Every
bisection iteration runs the Sturm recurrence sequentially over the matrix
dimension ``N`` with all ``bb * bm`` bisection brackets advancing in lockstep
— bisection is branch-free, so the "divide" of divide-&-conquer becomes pure
lane parallelism, which is the TPU adaptation of LAPACK's recursion (see
DESIGN.md §2).

Inputs are pre-padded by ``ops.py``:
  d      (B, N)   diagonals (padded columns decoupled by a zero ``e``)
  e      (B, N)   sub-diagonal *shifted by one*: ``e[:, k]`` couples rows
                  ``k - 1`` and ``k``, so ``e[:, 0]`` (and padding) = 0
  bounds (B, 4)   [lo, hi, pivmin, n_valid] per matrix; padded eigenvalue
                  indices (>= n_valid) converge onto ``hi`` and are sliced
                  off by the wrapper.

Band layout.  The band stays compact, ``(bb, N)`` with ``N`` on lanes, so a
block costs ``bb * N * 4`` bytes of VMEM per operand (N=8192, bb=8: 256 KiB;
with d and e double-buffered, 1 MiB — far inside v5e's 16 MiB scoped
limit).  The recurrence reads one band column per step, and Mosaic only
slices the lane axis at 128-aligned dynamic offsets, so the sweep loads
``LANE_CHUNK`` columns at an aligned offset and unrolls the steps inside the
chunk with *static* lane indices; ``ops.py`` pads ``N`` to a multiple of the
chunk (bands of at most one chunk are swept in one static piece).  Step 0
needs no special case: with ``e[:, 0] = 0`` and the carry seeded at
``q = 1`` the generic step yields ``q_0 = d_0 - x`` exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Band columns per aligned load (one lane width); ``N`` pads to a multiple.
LANE_CHUNK = 128


def band_width(n: int) -> int:
    """Padded band width the kernels accept for an ``n``-column band: the
    8-aligned width when the band fits one chunk, else a chunk multiple."""
    if n <= LANE_CHUNK:
        return max(8, -(-n // 8) * 8)
    return -(-n // LANE_CHUNK) * LANE_CHUNK


def _count_below(d_ref, e_ref, x, pivmin, in_window=None):
    """Sturm count per (matrix, lane): #{k : q_k(x) < 0}, x: (bb, bm).

    ``in_window(k)`` (optional) masks the count to a per-lane column window
    — the segmented kernel's per-segment count.
    """
    n_total = d_ref.shape[1]
    chunk = min(LANE_CHUNK, n_total)
    n_chunks = n_total // chunk

    def sweep(c, carry):
        base = c * chunk
        if n_chunks > 1:  # traced chunk index: assert the lane alignment
            base = pl.multiple_of(base, LANE_CHUNK)
        cols = pl.ds(base, chunk)
        dc = d_ref[:, cols]  # (bb, chunk)
        ec = e_ref[:, cols]
        e2c = ec * ec
        q, cnt = carry
        for j in range(chunk):
            q = dc[:, j:j + 1] - x - e2c[:, j:j + 1] / q
            q = jnp.where(jnp.abs(q) < pivmin, -pivmin, q)
            neg = q < 0
            if in_window is not None:
                neg = neg & in_window(c * chunk + j)
            cnt = cnt + neg.astype(jnp.int32)
        return q, cnt

    carry = (jnp.ones_like(x), jnp.zeros(x.shape, jnp.int32))
    if n_chunks == 1:  # the whole band is one static piece
        return sweep(0, carry)[1]
    return jax.lax.fori_loop(0, n_chunks, sweep, carry)[1]


def _bisect(lo, hi, targets, count_below, n_iter):
    def step(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        go_right = count_below(mid) <= targets
        return jnp.where(go_right, mid, lo), jnp.where(go_right, hi, mid)

    lo, hi = jax.lax.fori_loop(0, n_iter, step, (lo, hi))
    return 0.5 * (lo + hi)


def _sturm_kernel(d_ref, e_ref, bounds_ref, out_ref, *, n_iter, block_m,
                  target_base):
    bb = d_ref.shape[0]
    pivmin = bounds_ref[:, 2:3]  # (bb, 1)

    # ``target_base`` windows the eigenvalue-index axis: lane ``m`` of grid
    # step ``g`` brackets index ``target_base + g * block_m + m``.  The full
    # spectrum is ``target_base = 0`` with the grid spanning all n indices; a
    # top-k window starts the grid at ``n - k`` — bisection lanes are
    # independent, so a windowed lane is bitwise-equal to the same lane of a
    # full-spectrum run.
    m0 = target_base + pl.program_id(1) * block_m
    targets = m0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_m), 1)
    lo = jnp.broadcast_to(bounds_ref[:, 0:1], (bb, block_m))
    hi = jnp.broadcast_to(bounds_ref[:, 1:2], (bb, block_m))
    out_ref[...] = _bisect(
        lo, hi, targets,
        lambda x: _count_below(d_ref, e_ref, x, pivmin), n_iter)


def _sturm_segmented_kernel(d_ref, e_ref, lo_ref, hi_ref, piv_ref,
                            start_ref, end_ref, targ_ref, out_ref, *,
                            n_iter):
    """Per-segment windowed bisection over packed block-diagonal bands.

    Lane arrays replace the per-matrix bounds row: every lane carries its own
    bracket ``[lo, hi]``, ``pivmin``, segment window ``[start, end)`` and
    eigenvalue-index target.  The Sturm recurrence still runs over the whole
    packed band — junction off-diagonals are exactly zero in the packed
    layout, so ``q`` restarts by itself (``e2/q = 0``) — but the *count* is
    masked to the lane's segment, making each lane bracket eigenvalue
    ``target`` of its own diagonal block and nothing else.
    """
    start = start_ref[...]  # (bb, bm) int32
    end = end_ref[...]
    pivmin = piv_ref[...]

    def in_window(k):
        return (start <= k) & (k < end)

    out_ref[...] = _bisect(
        lo_ref[...], hi_ref[...], targ_ref[...],
        lambda x: _count_below(d_ref, e_ref, x, pivmin, in_window), n_iter)


@functools.partial(
    jax.jit,
    static_argnames=("n_iter", "block_b", "block_m", "interpret"),
)
def sturm_segmented_padded(
    d: jax.Array,  # (B, N)
    e: jax.Array,  # (B, N) shifted sub-diagonal
    lo: jax.Array,  # (B, M) f32 lane brackets
    hi: jax.Array,  # (B, M)
    pivmin: jax.Array,  # (B, M)
    start: jax.Array,  # (B, M) int32 segment start (inclusive)
    end: jax.Array,  # (B, M) int32 segment end (exclusive)
    targets: jax.Array,  # (B, M) int32 per-segment eigenvalue index
    *,
    n_iter: int,
    block_b: int = 8,
    block_m: int = 128,
    interpret: bool = False,
):
    """Tiled segment-masked bisection: lane ``(b, m)`` brackets eigenvalue
    ``targets[b, m]`` of the diagonal block ``[start, end)`` of band row
    ``b``.  All operands pre-padded to block multiples by ``ops.py``."""
    b_total, n_total = d.shape
    m_total = targets.shape[1]
    grid = (b_total // block_b, m_total // block_m)
    lane = pl.BlockSpec((block_b, block_m), lambda b, m: (b, m))
    band = pl.BlockSpec((block_b, n_total), lambda b, m: (b, 0))
    return pl.pallas_call(
        functools.partial(_sturm_segmented_kernel, n_iter=n_iter),
        grid=grid,
        in_specs=[band, band, lane, lane, lane, lane, lane, lane],
        out_specs=lane,
        out_shape=jax.ShapeDtypeStruct((b_total, m_total), d.dtype),
        interpret=interpret,
    )(d, e, lo, hi, pivmin, start, end, targets)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_iter", "block_b", "block_m", "interpret", "m_total", "target_base"),
)
def sturm_padded(
    d: jax.Array,  # (B, N)
    e: jax.Array,  # (B, N) shifted sub-diagonal
    bounds: jax.Array,  # (B, 4)
    *,
    n_iter: int,
    block_b: int = 8,
    block_m: int = 128,
    interpret: bool = False,
    m_total: int | None = None,
    target_base: int = 0,
):
    """Tiled bisection over ``m_total`` eigenvalue indices starting at
    ``target_base`` (defaults: the full spectrum — every band index)."""
    b_total, n_total = d.shape
    if m_total is None:
        m_total = n_total
    grid = (b_total // block_b, m_total // block_m)
    band = pl.BlockSpec((block_b, n_total), lambda b, m: (b, 0))
    return pl.pallas_call(
        functools.partial(
            _sturm_kernel, n_iter=n_iter, block_m=block_m,
            target_base=target_base,
        ),
        grid=grid,
        in_specs=[
            band,
            band,
            pl.BlockSpec((block_b, 4), lambda b, m: (b, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda b, m: (b, m)),
        out_shape=jax.ShapeDtypeStruct((b_total, m_total), d.dtype),
        interpret=interpret,
    )(d, e, bounds)
