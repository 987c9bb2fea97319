"""Jit'd public wrappers for the sturm kernel (bounds, padding, slicing).

``sturm_eigenvalues`` runs one tiled program over a ``(B, n)`` stack of
tridiagonal bands; ``sturm_minor_spectra`` is the stacked-minor-band layout —
all ``b * n`` minor bisection problems of a ``(b, n)`` batch flattened onto
the kernel's row axis so the whole stack is one pallas_call, not ``b``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blocks
from repro.kernels.sturm import kernel as _kernel


def _default_iters(dtype) -> int:
    return 64 if dtype == jnp.float64 else 32


def _shifted_band(e: jax.Array, d_p: jax.Array) -> jax.Array:
    """The kernels' sub-diagonal layout: ``e`` ``(B, n-1)`` placed at
    columns ``1..n-1`` of a zero array shaped like the padded band."""
    n = e.shape[1] + 1
    e_p = jnp.zeros_like(d_p)
    if n > 1:
        e_p = e_p.at[:e.shape[0], 1:n].set(e)
    return e_p


@functools.partial(
    jax.jit,
    static_argnames=("n_iter", "block_b", "block_m", "interpret", "window"),
)
def sturm_eigenvalues(
    d: jax.Array,  # (B, n)
    e: jax.Array,  # (B, n-1)
    *,
    n_iter: int = 0,
    block_b: int = 8,
    block_m: int = 128,
    interpret: bool | None = None,
    window: tuple | None = None,
) -> jax.Array:
    """All eigenvalues of ``B`` symmetric tridiagonal matrices, ``(B, n)``.

    Decoupled systems (zero off-diagonal entries, e.g. EEI minors of a
    tridiagonal matrix) need no special handling — the Sturm count is exact
    across decoupling points.

    ``window=(k, largest)`` restricts the eigenvalue-index grid to the ``k``
    extremal indices (the counting function brackets eigenvalues *by
    index*, so a partial-spectrum query runs ``k`` bisection lanes instead
    of ``n``); the returned ``(B, k)`` window is ascending and
    bitwise-equal to the matching slice of the full-spectrum result
    (bisection lanes are independent).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b_n, n = d.shape
    dtype = d.dtype
    if n_iter == 0:
        n_iter = _default_iters(dtype)
    m_targets = n
    target_base = 0
    if window is not None:
        k_w, largest = int(window[0]), bool(window[1])
        if not 1 <= k_w <= n:
            raise ValueError(f"window k={k_w} out of range for n={n}")
        m_targets = k_w
        target_base = n - k_w if largest else 0

    # Per-matrix Gershgorin bounds + pivmin (computed on unpadded bands).
    abs_e = jnp.abs(e)
    r = jnp.zeros_like(d)
    if n > 1:
        r = r.at[:, :-1].add(abs_e)
        r = r.at[:, 1:].add(abs_e)
    lo = jnp.min(d - r, axis=1)
    hi = jnp.max(d + r, axis=1)
    span = jnp.maximum(hi - lo, 1.0)
    eps = jnp.asarray(jnp.finfo(dtype).eps, dtype)
    lo = lo - eps * span
    hi = hi + eps * span
    scale = jnp.maximum(
        jnp.max(jnp.abs(d), axis=1),
        jnp.max(abs_e, axis=1) if n > 1 else jnp.zeros((b_n,), dtype),
    )
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    pivmin = jnp.maximum(eps * eps * scale * scale, tiny)
    bounds = jnp.stack([lo, hi, pivmin, jnp.full((b_n,), n, dtype)], axis=1)

    # Clamp blocks to the padded problem shape: a 128-lane target tile on an
    # n=8 problem must shrink to 8 (align 8 keeps lanes aligned; the batch
    # axis clamps unaligned — padded rows are pure waste).  The band pads
    # separately, to the kernel's chunk width; a window tiles k target lanes
    # over the full band.
    block_m = blocks.clamp_block(block_m, m_targets)
    block_b = blocks.clamp_block(block_b, b_n, align=1)
    pad_m = (-m_targets) % block_m
    pad_n = _kernel.band_width(n) - n
    pad_b = (-b_n) % block_b
    # Padded diagonal entries sit above hi (decoupled via zero e), so padded
    # eigenvalue indices converge onto hi and are sliced off below.
    d_p = jnp.pad(d, ((0, pad_b), (0, pad_n)), constant_values=0.0)
    if pad_n or n >= 1:
        big = (jnp.abs(hi) + span)[:, None]
        col = jnp.arange(n + pad_n)[None, :]
        d_p = jnp.where(
            col >= n, jnp.pad(big, ((0, pad_b), (0, 0)), constant_values=1.0), d_p
        )
    e_p = _shifted_band(e, d_p)
    bounds_p = jnp.pad(bounds, ((0, pad_b), (0, 0)), constant_values=1.0)

    out = _kernel.sturm_padded(
        d_p,
        e_p,
        bounds_p,
        n_iter=n_iter,
        block_b=block_b,
        block_m=block_m,
        interpret=interpret,
        m_total=m_targets + pad_m,
        target_base=target_base,
    )
    return out[:b_n, :m_targets]


@functools.partial(
    jax.jit,
    static_argnames=("k", "largest", "n_iter", "block_b", "block_m",
                     "interpret"),
)
def sturm_eigenvalues_segmented(
    d: jax.Array,  # (B, N) packed block-diagonal bands
    e: jax.Array,  # (B, N-1) off-diagonals (zero at segment junctions)
    seg_off: jax.Array,  # (B, S) int32 segment start columns
    seg_len: jax.Array,  # (B, S) int32 segment lengths (0 = empty slot)
    *,
    k: int,
    largest: bool,
    n_iter: int = 0,
    block_b: int = 8,
    block_m: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """The ``k`` extremal eigenvalues of every *segment* of packed bands.

    Each band row carries up to ``S`` independent tridiagonal blocks
    (segments) laid out by the serving packer: block ``s`` of row ``b``
    occupies columns ``[seg_off[b, s], seg_off[b, s] + seg_len[b, s])`` and
    junction off-diagonals are exactly zero, so the Sturm count restricted
    to a segment window is the exact count for that block (decoupling is a
    property of the recurrence, not an approximation).  Lane ``(s, t)``
    brackets per-segment index ``len - k + t`` (largest, clamped at 0) or
    ``t`` (smallest, clamped at ``len - 1``) — clamped lanes duplicate the
    boundary eigenvalue and sit *outside* the slice a ``k' <= len`` request
    reads, mirroring the guard convention of the bucketed path.

    Returns ``(B, S, k)``, ascending per segment; empty slots return zeros.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b_n, n = d.shape
    s_slots = seg_off.shape[1]
    dtype = d.dtype
    if n_iter == 0:
        n_iter = _default_iters(dtype)
    if k < 1:
        raise ValueError(f"window k={k} must be >= 1")

    seg_off = seg_off.astype(jnp.int32)
    seg_len = seg_len.astype(jnp.int32)
    seg_end = seg_off + seg_len

    # Per-segment Gershgorin bounds + pivmin via masked reductions over the
    # band (the segment layout is traced data, so everything stays jittable).
    e_full = jnp.zeros_like(d)
    if n > 1:
        e_full = e_full.at[:, : n - 1].set(jnp.abs(e))
    r = jnp.zeros_like(d)
    if n > 1:
        r = r.at[:, :-1].add(e_full[:, : n - 1])
        r = r.at[:, 1:].add(e_full[:, : n - 1])
    col = jnp.arange(n, dtype=jnp.int32)[None, None, :]  # (1, 1, N)
    in_seg = (seg_off[:, :, None] <= col) & (col < seg_end[:, :, None])
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)
    lo_s = jnp.min(
        jnp.where(in_seg, (d - r)[:, None, :], big), axis=2)  # (B, S)
    hi_s = jnp.max(jnp.where(in_seg, (d + r)[:, None, :], -big), axis=2)
    empty = seg_len == 0
    lo_s = jnp.where(empty, 0.0, lo_s)
    hi_s = jnp.where(empty, 0.0, hi_s)
    span = jnp.maximum(hi_s - lo_s, 1.0)
    eps = jnp.asarray(jnp.finfo(dtype).eps, dtype)
    lo_s = lo_s - eps * span
    hi_s = hi_s + eps * span
    scale = jnp.max(
        jnp.where(in_seg, jnp.abs(d)[:, None, :], 0.0), axis=2)
    scale = jnp.maximum(
        scale, jnp.max(jnp.where(in_seg, e_full[:, None, :], 0.0), axis=2))
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    piv_s = jnp.maximum(eps * eps * scale * scale, tiny)

    # Lane layout: m = s * k + t.  Targets are per-segment indices.
    t = jnp.arange(k, dtype=jnp.int32)[None, None, :]  # (1, 1, k)
    if largest:
        targ = jnp.maximum(seg_len[:, :, None] - k + t, 0)
    else:
        targ = jnp.minimum(t, jnp.maximum(seg_len[:, :, None] - 1, 0))

    m_total = s_slots * k
    block_m = blocks.clamp_block(block_m, m_total)
    block_b = blocks.clamp_block(block_b, b_n, align=1)
    pad_m = (-m_total) % block_m
    pad_b = (-b_n) % block_b
    pad_n = _kernel.band_width(n) - n

    def pad_lane(x, value):
        """Broadcast (B, S[, k]) to lanes (B, S*k) and pad to blocks."""
        x = jnp.broadcast_to(x[:, :, None] if x.ndim == 2 else x,
                             (b_n, s_slots, k)).reshape(b_n, m_total)
        return jnp.pad(x, ((0, pad_b), (0, pad_m)), constant_values=value)

    lo_l = pad_lane(lo_s, 0.0)
    hi_l = pad_lane(hi_s, 0.0)
    piv_l = pad_lane(piv_s, 1.0)
    start_l = pad_lane(seg_off, 0)
    end_l = pad_lane(seg_end, 0)  # padded lanes: empty window, count 0
    targ_l = pad_lane(targ, 0)

    d_p = jnp.pad(d, ((0, pad_b), (0, pad_n)), constant_values=1.0)
    e_p = _shifted_band(e, d_p)

    out = _kernel.sturm_segmented_padded(
        d_p, e_p, lo_l, hi_l, piv_l, start_l, end_l, targ_l,
        n_iter=n_iter, block_b=block_b, block_m=block_m,
        interpret=interpret)
    return out[:b_n, :m_total].reshape(b_n, s_slots, k)


@functools.partial(
    jax.jit,
    static_argnames=("k", "largest", "n_iter", "block_b", "block_m",
                     "interpret"),
)
def sturm_eigenvalues_bracketed(
    d: jax.Array,  # (B, n)
    e: jax.Array,  # (B, n-1)
    lo: jax.Array,  # (B, k) per-lane warm lower brackets
    hi: jax.Array,  # (B, k) per-lane warm upper brackets
    *,
    k: int,
    largest: bool,
    n_iter: int = 0,
    block_b: int = 8,
    block_m: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """The ``k`` extremal eigenvalues from caller-supplied per-lane brackets.

    The warm-started twin of the ``window=`` entry of
    :func:`sturm_eigenvalues`: each bisection lane starts from its own
    ``(lo, hi)`` (interlacing-tightened brackets from a previous spectrum —
    see ``repro.linalg.interlace.rank1_update_brackets``) instead of the
    matrix-wide Gershgorin interval.  Implemented on the *segmented* kernel
    with one full-band segment per row: the segmented program already
    threads per-lane bounds and per-lane target indices, so the warm path
    reuses the packed-dispatch machinery rather than growing a third kernel.

    Warm brackets are validated, never trusted: one pair of host-side Sturm
    sweeps checks ``count(lo[t]) <= target_t < count(hi[t])`` and any lane
    whose bracket cannot prove containment of its index restarts from the
    Gershgorin interval — stale sessions cost iterations, not correctness.
    Returns ``(B, k)``, ascending.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b_n, n = d.shape
    dtype = d.dtype
    if n_iter == 0:
        n_iter = _default_iters(dtype)
    if not 1 <= k <= n:
        raise ValueError(f"window k={k} out of range for n={n}")

    # Per-matrix Gershgorin bounds + pivmin (the validation fallback).
    abs_e = jnp.abs(e)
    r = jnp.zeros_like(d)
    if n > 1:
        r = r.at[:, :-1].add(abs_e)
        r = r.at[:, 1:].add(abs_e)
    lo_g = jnp.min(d - r, axis=1)
    hi_g = jnp.max(d + r, axis=1)
    span = jnp.maximum(hi_g - lo_g, 1.0)
    eps = jnp.asarray(jnp.finfo(dtype).eps, dtype)
    lo_g = lo_g - eps * span
    hi_g = hi_g + eps * span
    scale = jnp.maximum(
        jnp.max(jnp.abs(d), axis=1),
        jnp.max(abs_e, axis=1) if n > 1 else jnp.zeros((b_n,), dtype),
    )
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    piv = jnp.maximum(eps * eps * scale * scale, tiny)

    targ = (jnp.arange(n - k, n) if largest else jnp.arange(k))
    targ = jnp.broadcast_to(targ.astype(jnp.int32)[None, :], (b_n, k))
    lo = jnp.asarray(lo, dtype)
    hi = jnp.asarray(hi, dtype)
    from repro.linalg.sturm import sturm_count  # pure-jnp count, vmappable

    counts = jax.vmap(sturm_count)(d, e, jnp.concatenate([lo, hi], axis=1))
    ok = (counts[:, :k] <= targ) & (counts[:, k:] > targ) & (lo <= hi)
    lo = jnp.where(ok, lo, lo_g[:, None])
    hi = jnp.where(ok, hi, hi_g[:, None])

    block_m = blocks.clamp_block(block_m, k)
    block_b = blocks.clamp_block(block_b, b_n, align=1)
    pad_m = (-k) % block_m
    pad_b = (-b_n) % block_b
    pad_n = _kernel.band_width(n) - n

    def pad_lane(x, value):
        return jnp.pad(x, ((0, pad_b), (0, pad_m)), constant_values=value)

    lo_l = pad_lane(lo, 0.0)
    hi_l = pad_lane(hi, 0.0)
    piv_l = pad_lane(jnp.broadcast_to(piv[:, None], (b_n, k)), 1.0)
    start_l = pad_lane(jnp.zeros((b_n, k), jnp.int32), 0)
    end_l = pad_lane(jnp.full((b_n, k), n, jnp.int32), 0)
    targ_l = pad_lane(targ, 0)

    d_p = jnp.pad(d, ((0, pad_b), (0, pad_n)), constant_values=1.0)
    e_p = _shifted_band(e, d_p)

    out = _kernel.sturm_segmented_padded(
        d_p, e_p, lo_l, hi_l, piv_l, start_l, end_l, targ_l,
        n_iter=n_iter, block_b=block_b, block_m=block_m,
        interpret=interpret)
    return out[:b_n, :k]


@functools.partial(
    jax.jit, static_argnames=("n_iter", "block_b", "block_m", "interpret")
)
def sturm_minor_spectra(
    dm: jax.Array,  # (b, n, m) stacked minor diagonals
    em: jax.Array,  # (b, n, m-1) stacked minor off-diagonals
    *,
    n_iter: int = 0,
    block_b: int = 8,
    block_m: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Spectra of all ``b * n`` stacked minor bands as one tiled program.

    The stacked-minor-band layout: the ``(b, n)`` leading axes flatten onto
    the kernel's row (sublane) axis, so every bisection problem in the whole
    batch advances in lockstep inside a single pallas_call — per-program
    launch overhead is amortized across the stack instead of paid ``b``
    times.  Returns ``(b, n, m)``.
    """
    b_n, n, m = dm.shape
    mu = sturm_eigenvalues(
        dm.reshape(b_n * n, m),
        em.reshape(b_n * n, m - 1),
        n_iter=n_iter,
        block_b=block_b,
        block_m=block_m,
        interpret=interpret,
    )
    return mu.reshape(b_n, n, m)
