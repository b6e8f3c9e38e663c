"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel body on CPU)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st  # hypothesis or fallback

from repro.kernels.fft.kernel import dft_planes, dft_tables, fft_rows_pallas
from repro.kernels.fft.ops import (KERNEL_KINDS, fft_rows_op, pick_block_rows,
                                   vmem_bytes)
from repro.kernels.fft.ref import fft_rows_ref
from repro.kernels.transpose.kernel import transpose_pallas
from repro.kernels.transpose.ops import transpose_op
from repro.kernels.transpose.ref import transpose_ref


def cplanes(rng, rows, n, dtype=np.float32):
    re = rng.standard_normal((rows, n)).astype(dtype)
    im = rng.standard_normal((rows, n)).astype(dtype)
    return jnp.asarray(re), jnp.asarray(im)


# ---------------------------------------------------------------- fft kernel

@pytest.mark.parametrize("n", [8, 32, 128, 512, 2048])
@pytest.mark.parametrize("rows", [1, 4, 8])
def test_dft_planes_shape_sweep(rng, n, rows):
    re, im = cplanes(rng, rows, n)
    ore, oim = dft_planes(re, im, dft_tables(n))
    rre, rim = fft_rows_ref(re, im)
    tol = 1e-3 * n ** 0.5
    np.testing.assert_allclose(np.asarray(ore), np.asarray(rre), atol=tol)
    np.testing.assert_allclose(np.asarray(oim), np.asarray(rim), atol=tol)


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("block_rows", [1, 2, 8])
def test_fft_kernel_pallas_call(rng, inverse, block_rows):
    rows, n = 16, 64
    re, im = cplanes(rng, rows, n)
    ore, oim = fft_rows_pallas(re, im, block_rows=block_rows, inverse=inverse,
                               interpret=True)
    rre, rim = fft_rows_ref(re, im, inverse=inverse)
    np.testing.assert_allclose(np.asarray(ore), np.asarray(rre), atol=1e-3)
    np.testing.assert_allclose(np.asarray(oim), np.asarray(rim), atol=1e-3)


def test_fft_kernel_rejects_bad_rows(rng):
    re, im = cplanes(rng, 5, 16)
    with pytest.raises(ValueError):
        fft_rows_pallas(re, im, block_rows=4, interpret=True)


@pytest.mark.parametrize("rows", [3, 8, 13])
@pytest.mark.parametrize("n", [16, 256])
def test_fft_op_complex_roundtrip(rng, rows, n):
    x = (rng.standard_normal((rows, n))
         + 1j * rng.standard_normal((rows, n))).astype(np.complex64)
    x = jnp.asarray(x)
    out = fft_rows_op(x, block_rows=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft(x, axis=-1)),
                               atol=2e-3)
    back = fft_rows_op(out, inverse=True, block_rows=4, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=2e-3)


def test_fft_op_batched_leading_dims(rng):
    x = (rng.standard_normal((2, 3, 32))
         + 1j * rng.standard_normal((2, 3, 32))).astype(np.complex64)
    out = fft_rows_op(jnp.asarray(x), block_rows=2, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft(x, axis=-1)), atol=2e-3)


def test_fft_op_rejects_non_pow2():
    with pytest.raises(ValueError):
        fft_rows_op(jnp.ones((4, 12), jnp.complex64), interpret=True)


@pytest.mark.parametrize("kind", KERNEL_KINDS)
def test_pick_block_rows_vmem_budget(kind):
    """Blocks tile legally on v5e — whole (8, 128) f32 tiles of rows, and
    128 rows where they are the fused output's lane axis — and a plain
    kernel's block fits Mosaic's 16 MiB default scoped VMEM."""
    for log2n in range(7, 15):
        n = 1 << log2n
        b = pick_block_rows(n, kind)
        assert b % 8 == 0
        if kind in ("fused", "rfused"):
            assert b == 128
        else:
            assert vmem_bytes(kind, n, b) <= 16 * 1024 * 1024


@given(n=st.sampled_from([8, 16, 64, 256]), rows=st.integers(1, 6),
       seed=st.integers(0, 50))
@settings(max_examples=25, deadline=None)
def test_fft_kernel_property_linear(n, rows, seed):
    """DFT linearity: F(a x + y) = a F(x) + F(y)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, n)).astype(np.complex64))
    y = jnp.asarray(rng.standard_normal((rows, n)).astype(np.complex64))
    a = 2.5
    lhs = fft_rows_op(a * x + y, block_rows=2, interpret=True)
    rhs = a * fft_rows_op(x, block_rows=2, interpret=True) + \
        fft_rows_op(y, block_rows=2, interpret=True)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=5e-3)


# ---------------------------------------------------------- transpose kernel

@pytest.mark.parametrize("shape", [(128, 128), (256, 128), (384, 256)])
def test_transpose_kernel_exact(rng, shape):
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    out = transpose_pallas(x, block=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(transpose_ref(x)))


def test_transpose_kernel_rejects_unaligned(rng):
    with pytest.raises(ValueError):
        transpose_pallas(jnp.ones((100, 128)), block=128, interpret=True)


@given(r=st.integers(1, 300), c=st.integers(1, 300), seed=st.integers(0, 20))
@settings(max_examples=30, deadline=None)
def test_transpose_op_any_shape(r, c, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((r, c)).astype(np.float32))
    out = transpose_op(x, block=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x.T))


def test_transpose_op_complex(rng):
    x = (rng.standard_normal((130, 70))
         + 1j * rng.standard_normal((130, 70))).astype(np.complex64)
    out = transpose_op(jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), x.T)


def test_transpose_involution(rng):
    x = jnp.asarray(rng.standard_normal((200, 150)).astype(np.float32))
    out = transpose_op(transpose_op(x, interpret=True), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
