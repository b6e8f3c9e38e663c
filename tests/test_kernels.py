"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel body on CPU)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st  # hypothesis or fallback

from repro.kernels.fft.kernel import (CMUL_PRODUCTS, bmm_left, dft_macs,
                                      dft_planes, dft_tables,
                                      fft_rows_pallas, hi_dot, split_length)
from repro.kernels.fft.ops import (KERNEL_KINDS, fft_rows_op, pick_block_rows,
                                   tpu_unsupported, vmem_bytes)
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


def _dft_matrices(n, inverse):
    """The complex DFT matrices ``dft_tables`` holds, in float64: the
    direct one, or the four-step's (F1, twiddle, F2)."""
    n1, n2 = split_length(n)
    sign = 1.0 if inverse else -1.0

    def cis(num, den):
        return np.exp(sign * 2j * np.pi * (num % den) / den)

    i1, i2 = np.arange(n1), np.arange(n2)
    f2 = cis(np.outer(i2, i2), n2) / (n if inverse else 1)
    if n1 == 1:
        return (f2,)
    return cis(np.outer(i1, i1), n1), cis(np.outer(i1, i2), n), f2


def _four_product_dft(re, im, n, inverse):
    """The row DFT with every complex product as four real products on
    f32 ``(re, im)`` tables rounded from float64: the oracle the
    three-product kernel's error is held to."""
    mats = [(jnp.asarray(m.real, jnp.float32), jnp.asarray(m.imag, jnp.float32))
            for m in _dft_matrices(n, inverse)]

    def cdot(dot, xr, xi, fr, fi):
        return dot(xr, fr) - dot(xi, fi), dot(xr, fi) + dot(xi, fr)

    def right(x, f):
        return hi_dot(x, f)

    if len(mats) == 1:
        return cdot(right, re, im, *mats[0])
    (f1r, f1i), (twr, twi), (f2r, f2i) = mats
    rows = re.shape[0]
    n1, n2 = split_length(n)
    yr, yi = cdot(lambda x, f: bmm_left(f, x), re.reshape(rows, n1, n2),
                  im.reshape(rows, n1, n2), f1r, f1i)
    zr, zi = yr * twr - yi * twi, yr * twi + yi * twr
    xr, xi = cdot(right, zr.reshape(-1, n2), zi.reshape(-1, n2), f2r, f2i)
    return tuple(jnp.swapaxes(x.reshape(rows, n1, n2), 1, 2).reshape(rows, n)
                 for x in (xr, xi))


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("n", [128, 512, 1024, 8192, 32768])
def test_three_product_error_vs_four(rng, n, inverse):
    """Three real products per complex product stay within 1.6x the rms
    error of four, against a float64 DFT (over at least 2**15 elements,
    so that the ratio is not one draw's)."""
    re, im = cplanes(rng, max(4, 32768 // n), n)
    x = np.asarray(re, np.float64) + 1j * np.asarray(im, np.float64)
    ref = (np.fft.ifft if inverse else np.fft.fft)(x, axis=-1)

    def rms_err(planes):
        out = np.asarray(planes[0], np.float64) + 1j * np.asarray(planes[1])
        return np.linalg.norm(out - ref) / np.linalg.norm(ref)

    three = rms_err(dft_planes(re, im, dft_tables(n, inverse)))
    four = rms_err(_four_product_dft(re, im, n, inverse))
    assert three <= 1.6 * four, (three, four)
    assert three < 1e-5


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("n", [256, 1024, 32768])
def test_tables_rebuild_the_dft_matrices(n, inverse):
    """Each DFT matrix ``C + iS`` is held as ``(C, C + S, S - C)``, each
    rounded once to f32: ``(C + S) - C`` and ``(S - C) + C`` give ``S``
    back within f32 rounding; the twiddle stays ``(re, im)``."""
    tables = [t.astype(np.float64) for t in dft_tables(n, inverse)]
    mats = _dft_matrices(n, inverse)
    held = ([tables[:3]] if len(mats) == 1
            else [tables[:3], tables[3:5], tables[5:]])
    assert len(held) == len(mats) and sum(map(len, held)) == len(tables)
    for m, parts in zip(mats, held):
        tol = 2.0 ** -22 * np.abs(m).max()
        if len(parts) == 2:
            np.testing.assert_allclose(parts[0] + 1j * parts[1], m, rtol=0,
                                       atol=tol)
            continue
        c, cps, smc = parts
        np.testing.assert_allclose(c, m.real, rtol=0, atol=tol)
        np.testing.assert_allclose(cps - c, m.imag, rtol=0, atol=2 * tol)
        np.testing.assert_allclose(smc + c, m.imag, rtol=0, atol=2 * tol)


@pytest.mark.parametrize("n", [256, 1024, 32768])
def test_dft_macs_counts_three_products(n):
    """Real MACs of the DFT products: n complex MACs an element direct,
    n1 + n2 four-step, each CMUL_PRODUCTS = 3 real products."""
    n1, n2 = split_length(n)
    per_element = n if n1 == 1 else n1 + n2
    assert CMUL_PRODUCTS == 3
    assert dft_macs(8, n) == 3 * 8 * n * per_element


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


_PINNED_BLOCKS = {  # n = 128 .. 16384
    "fft": (256, 256, 128, 16, 16, 16, 8, 8),
    "rfft": (256, 256, 64, 8, 8, 8, 8, 8),
    "fused": (128,) * 8,
    "rfused": (128,) * 8,
}
_PINNED_REFUSED = {"fft": (), "rfft": (), "fused": (16384,),
                   "rfused": (8192, 16384)}


@pytest.mark.parametrize("kind", KERNEL_KINDS)
def test_block_rows_and_refusals_pinned(kind):
    """Every kernel's block and every length it refuses, pinned: the
    three-product tables, half as large again, move none of them."""
    lengths = [1 << k for k in range(7, 15)]
    assert tuple(pick_block_rows(n, kind) for n in lengths) == \
        _PINNED_BLOCKS[kind]
    assert tuple(n for n in lengths if tpu_unsupported(kind, n)) == \
        _PINNED_REFUSED[kind]


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
