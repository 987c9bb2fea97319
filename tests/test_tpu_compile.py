"""Every Pallas kernel the engine reaches compiles for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a *described* v5e chip.
Interpret mode (what every other test runs) cannot see what Mosaic refuses
— a dynamic slice of a loaded value, an unaligned lane index, too much
VMEM — so these compiles guard the chip path at real widths (b = 16) with
the tiles a chip actually gets (the static fallbacks of
``engine/backends.py``: the CPU calibration table is skipped on a TPU).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

B = 16
K = 16
PD_TILES = dict(block_b=1, block_i=128, block_j=128, block_k=128)
STURM_TILES = dict(block_b=8, block_m=128)


@pytest.fixture(scope="module")
def chip_config():
    """The configuration the chip path runs under, whatever earlier tests
    in this worker left behind: 32-bit JAX (the Pallas TPU lowering takes
    no int64 indices) and no persistent cache (a compile for a described
    chip would be written there and could not be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = (jax.config.jax_enable_x64, jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", prev[0])
    jax.config.update("jax_enable_compilation_cache", prev[1])
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(chip_config):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _prod_diff(n, masked):
    from repro.kernels.prod_diff import ops

    def fn(lam, mu, *mask):
        return ops.logabs_sum_batched(
            lam, mu, 1e-6, mask=mask[0] if mask else None,
            interpret=False, **PD_TILES)

    shapes = [(B, n), (B, n, n - 1)] + ([(B, n, n - 1)] if masked else [])
    return fn, shapes


def _eei_windowed(n):
    from repro.kernels.prod_diff import ops

    def fn(lam, mu):
        return ops.eei_magnitudes_windowed(
            lam, mu, jnp.arange(n - min(K, n), n), interpret=False,
            **PD_TILES)

    return fn, [(B, n), (B, n, n - 1)]


def _sturm(n, window):
    from repro.kernels.sturm import ops

    def fn(d, e):
        return ops.sturm_eigenvalues(
            d, e, window=window, interpret=False, **STURM_TILES)

    return fn, [(B, n), (B, n - 1)]


def _sturm_minors(n):
    from repro.kernels.sturm import ops

    def fn(dm, em):
        return ops.sturm_minor_spectra(dm, em, interpret=False,
                                       **STURM_TILES)

    return fn, [(B, n, n - 1), (B, n, n - 2)]


def _sturm_bracketed(n):
    from repro.kernels.sturm import ops

    k = min(K, n)

    def fn(d, e, lo, hi):
        return ops.sturm_eigenvalues_bracketed(
            d, e, lo, hi, k=k, largest=True, interpret=False, **STURM_TILES)

    return fn, [(B, n), (B, n - 1), (B, k), (B, k)]


def _sturm_segmented(n):
    from repro.kernels.sturm import ops

    def fn(d, e, seg_off, seg_len):
        return ops.sturm_eigenvalues_segmented(
            d, e, seg_off.astype(jnp.int32), seg_len.astype(jnp.int32),
            k=4, largest=True, interpret=False, **STURM_TILES)

    return fn, [(B, n), (B, n - 1), (B, 4), (B, 4)]


KERNELS = {
    "prod_diff_shared_mask": lambda n: _prod_diff(n, masked=False),
    "prod_diff_masked": lambda n: _prod_diff(n, masked=True),
    "eei_magnitudes_windowed": _eei_windowed,
    "sturm_full": lambda n: _sturm(n, None),
    "sturm_windowed": lambda n: _sturm(n, (min(K, n), True)),
    "sturm_minor_spectra": _sturm_minors,
    "sturm_bracketed": _sturm_bracketed,
    "sturm_segmented": _sturm_segmented,
}


# n = 16 is a band of one lane chunk (the session's warm-update band);
# 256 and 1024 are served widths with two and eight chunks.
@pytest.mark.parametrize("n", [16, 256, 1024])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, n):
    fn, shapes = KERNELS[kernel](n)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
