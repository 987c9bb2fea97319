"""Inputs made on the device from ``--seed``, one jitted call per matrix.

Clients hold host arrays, so each matrix is pulled to the host in set-up
and freed on the device before the next is made: making the pool never
holds more than one matrix and a few thin slabs on the device.  The same
seed gives the same inputs.  All products run at ``HIGHEST`` precision so
the float32 matrices are float32-accurate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = "highest"
#: Columns of ``X`` made at a time, and rows of ``X X^T`` added at a time.
CHUNK = 512


def jax_key(seed: int, stream: int = 0):
    """A JAX key from any whole-number seed (also past 32 bits)."""
    words = np.random.SeedSequence([abs(int(seed)), int(stream)]
                                   ).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def host_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), int(stream)])


def spike_strengths(n: int, samples: int, multiples) -> np.ndarray:
    """Population spikes ``h_i`` given as multiples of the BBP detection
    threshold ``sqrt(n / samples)`` (Baik, Ben Arous and Peche 2005): a
    spike ``1 + h`` separates from the Marchenko-Pastur bulk of the sample
    covariance only where ``h`` exceeds it."""
    return np.asarray(multiples, np.float64) * np.sqrt(n / samples)


@functools.partial(jax.jit, static_argnames=("samples",),
                   donate_argnames=("zeros",))
def _spiked_wishart(key, strengths, zeros, *, samples):
    """``X X^T / samples`` with the columns of ``X`` drawn from
    ``N(0, I + V diag(h) V^T)``, ``V`` a seeded ``(n, k)`` orthonormal
    basis; ``(n, n)`` float32, symmetric up to rounding.  Built in place
    in the donated ``zeros``, so the device holds one matrix and thin
    slabs."""
    n = zeros.shape[0]
    cols, rows = min(CHUNK, samples), min(CHUNK, n)
    kv, kz = jax.random.split(key)
    v, _ = jnp.linalg.qr(jax.random.normal(kv, (n, strengths.shape[0]),
                                           jnp.float32))
    # Sigma^{1/2} = I + V diag(sqrt(1 + h) - 1) V^T; 1/sqrt(N) folded in.
    vs = v * (jnp.sqrt(1.0 + strengths) - 1.0)[None, :]
    scale = float(samples) ** -0.5

    def add_columns(c, a):
        z = jax.random.normal(jax.random.fold_in(kz, c), (n, cols),
                              jnp.float32)
        x = scale * (z + jnp.matmul(vs, jnp.matmul(v.T, z, precision=HIGHEST),
                                    precision=HIGHEST))

        def add_rows(r, a):
            xr = lax.dynamic_slice_in_dim(x, r * rows, rows, 0)
            cur = lax.dynamic_slice_in_dim(a, r * rows, rows, 0)
            new = cur + jnp.matmul(xr, x.T, precision=HIGHEST)
            return lax.dynamic_update_slice_in_dim(a, new, r * rows, 0)

        return lax.fori_loop(0, n // rows, add_rows, a)

    return lax.fori_loop(0, samples // cols, add_columns, zeros)


def _mirror_upper(a: np.ndarray, block: int) -> None:
    """Copy the upper triangle of ``a`` onto its lower one, in place and
    block by block.  Row block ``r`` of ``X X^T`` is one product on the
    device, so ``a[i, j]`` and ``a[j, i]`` were rounded apart; a mirror in
    place on the device would cost XLA a copy of the whole matrix."""
    n = a.shape[0]
    lower = np.tril_indices(block, -1)
    for r in range(0, n, block):
        for c in range(0, r, block):
            a[r:r + block, c:c + block] = a[c:c + block, r:r + block].T
        diag = a[r:r + block, r:r + block]
        diag[lower] = diag.T[lower]


def spiked_wishart_pool(seed: int, count: int, n: int, samples: int,
                        multiples) -> np.ndarray:
    """``count`` sample covariances of Johnstone's spiked model (Ann.
    Statist. 2001): ``X X^T / samples``, ``X`` of ``samples`` columns from
    ``N(0, I + sum_i h_i v_i v_i^T)``, spikes ``h_i`` from
    :func:`spike_strengths`, a fresh basis ``v`` per matrix.  Returns
    ``(count, n, n)`` float32 on the host, exactly symmetric."""
    for size in (n, samples):
        if size % min(CHUNK, size):
            raise ValueError(f"{size} is not a multiple of {CHUNK}")
    strengths = jnp.asarray(spike_strengths(n, samples, multiples),
                            jnp.float32)
    key = jax_key(seed, 2)
    pool = np.empty((count, n, n), np.float32)
    for i in range(count):
        a = _spiked_wishart(jax.random.fold_in(key, i), strengths,
                            jnp.zeros((n, n), jnp.float32), samples=samples)
        pool[i] = np.asarray(a)
        a.delete()
        _mirror_upper(pool[i], min(CHUNK, n))
    return pool
