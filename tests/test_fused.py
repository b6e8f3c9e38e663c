"""Fused FFT->transpose path, radix-4 stages, and batched segment dispatch:
equivalence against the unfused/radix-2/looped references."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.pfft import (_pfft_limb, plan_segment_batches, pfft_lb,
                             segment_row_ffts)
from repro.fft.fft2d import (fft2d_fused, fft2d_rowcol,
                             fft_rows_then_transpose)
from repro.plan import PlanConfig
from repro.kernels.fft.kernel import (dft_digits, dft_planes, dft_tables,
                                      split_length, to_natural, to_transposed)
from repro.kernels.fft.ops import (KernelUnsupported, fft_rows_op,
                                   tpu_unsupported)
from repro.kernels.fused.kernel import fft_rows_transpose_pallas
from repro.kernels.fused.ops import fft_rows_transpose_op


def csignal(rng, rows, n, dtype=np.complex64):
    return jnp.asarray((rng.standard_normal((rows, n))
                        + 1j * rng.standard_normal((rows, n))).astype(dtype))


# ------------------------------------------------------ four-step digit layouts

@pytest.mark.parametrize("n", [2, 16, 128, 512, 1024, 2048, 4096])
def test_k1_major_digits_match_row_major(rng, n):
    """The fused kernels' k1-major digit layout, transposed out, equals the
    plain kernels' row-major layout in natural order, transposed."""
    rows = 8
    re = jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
    im = jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
    tables = dft_tables(n)
    row_major = dft_digits(re, im, tables)
    k1_major = dft_digits(re, im, tables, k1_major=True)
    for a, b in zip(row_major, k1_major):
        np.testing.assert_allclose(np.asarray(to_transposed(b, rows)),
                                   np.asarray(to_natural(a, rows)).T,
                                   atol=1e-3 * n ** 0.5)


@pytest.mark.parametrize("inverse", [False, True])
def test_dft_planes_inverse_roundtrip(rng, inverse):
    n = 1024
    re = jnp.asarray(rng.standard_normal((2, n)).astype(np.float32))
    im = jnp.asarray(rng.standard_normal((2, n)).astype(np.float32))
    fr, fi = dft_planes(re, im, dft_tables(n, inverse))
    br, bi = dft_planes(fr, fi, dft_tables(n, not inverse))
    np.testing.assert_allclose(np.asarray(br), np.asarray(re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(bi), np.asarray(im), atol=1e-4)


def test_split_length():
    for log2n in range(0, 15):
        n = 1 << log2n
        n1, n2 = split_length(n)
        assert n1 * n2 == n
        assert (n1, n2) == ((1, n) if n <= 512 else (n // 128, 128))
    with pytest.raises(ValueError):
        split_length(12)
    t = dft_tables(1024)
    assert [a.shape for a in t] == [(8, 8)] * 3 + [(8, 128)] * 2 + \
        [(128, 128)] * 3
    assert all(a.dtype == np.float32 and not a.flags.writeable for a in t)


def test_tpu_unsupported_lengths():
    """Plain kernels fit v5e VMEM at every length the planner offers;
    the fused kernels' 128-row lane block stops fitting at long rows."""
    for log2n in range(7, 15):
        n = 1 << log2n
        assert tpu_unsupported("fft", n) is None
        assert tpu_unsupported("rfft", n) is None
    assert tpu_unsupported("fused", 8192) is None
    assert "VMEM" in tpu_unsupported("fused", 16384)
    assert tpu_unsupported("rfused", 4096) is None
    assert "VMEM" in tpu_unsupported("rfused", 8192)


def test_fused_op_refuses_unsupported_length_on_chip():
    """Compiled (not interpreted), an unsupported length raises the named
    error instead of falling back to another FFT."""
    x = jnp.ones((8, 16384), jnp.complex64)
    with pytest.raises(KernelUnsupported, match="fused kernel at n=16384"):
        jax.eval_shape(lambda a: fft_rows_transpose_op(a, interpret=False), x)


@pytest.mark.parametrize("n", [16, 1024])
def test_fft_op_four_step_vs_oracle(rng, n):
    x = csignal(rng, 5, n)
    out = fft_rows_op(x, block_rows=2, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft(x, axis=-1)),
                               atol=2e-3 * (n / 16) ** 0.5)


# ------------------------------------------------------------- fused kernel

@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("block_rows", [1, 4])
def test_fused_kernel_pallas_call(rng, inverse, block_rows):
    rows, n = 8, 64
    re = jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
    im = jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
    ore, oim = fft_rows_transpose_pallas(re, im, block_rows=block_rows,
                                         inverse=inverse, interpret=True)
    x = np.asarray(re) + 1j * np.asarray(im)
    ref = (np.fft.ifft if inverse else np.fft.fft)(x, axis=-1).T
    np.testing.assert_allclose(np.asarray(ore), ref.real, atol=2e-3)
    np.testing.assert_allclose(np.asarray(oim), ref.imag, atol=2e-3)


@pytest.mark.parametrize("rows,n", [(8, 64), (13, 32), (64, 256)])
def test_fused_op_vs_unfused(rng, rows, n):
    x = csignal(rng, rows, n)
    out = fft_rows_transpose_op(x, interpret=True)
    assert out.shape == (n, rows)
    ref = jnp.fft.fft(x, axis=-1).T
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)
    # The plane form runs the same kernel: the complex form's planes, exactly.
    ore, oim = fft_rows_transpose_op((jnp.real(x), jnp.imag(x)),
                                     interpret=True)
    assert ore.dtype == oim.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(ore), np.asarray(out.real))
    np.testing.assert_array_equal(np.asarray(oim), np.asarray(out.imag))


def test_fused_op_rejects_bad_inputs():
    with pytest.raises(ValueError):
        fft_rows_transpose_op(jnp.ones((4, 12), jnp.complex64), interpret=True)
    with pytest.raises(ValueError):
        fft_rows_transpose_op(jnp.ones((2, 4, 8), jnp.complex64),
                              interpret=True)
    with pytest.raises(ValueError, match="one shape"):
        fft_rows_transpose_op((jnp.ones((4, 8)), jnp.ones((4, 16))),
                              interpret=True)


@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
@pytest.mark.parametrize("n", [16, 32, 64, 128])
def test_fft2d_fused_vs_unfused_equivalence(rng, dtype, n):
    """The tentpole equivalence: fused=True computes the same 2-D DFT."""
    m = csignal(rng, n, n, dtype=dtype)
    fused = fft2d_rowcol(m, fused=True)
    unfused = fft2d_rowcol(m)
    # complex128 (when x64 is enabled) must take the full-precision
    # fallback, not the f32-plane kernel; judge by the realised dtype.
    tol = 1e-8 if m.dtype == jnp.complex128 else 1e-2 * n ** 0.5
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(jnp.fft.fft2(m)),
                               atol=tol)


@pytest.mark.parametrize("n", [64, 256])
def test_fft2d_fused_equals_op_composition(rng, n):
    """Planes carried between the phases give the array the complex op
    gives twice (the join and re-split between them is exact)."""
    m = csignal(rng, n, n)
    planes = fft2d_fused(m)
    composed = fft_rows_transpose_op(fft_rows_transpose_op(m))
    assert planes.dtype == composed.dtype == jnp.complex64
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(composed))
    ref = np.fft.fft2(np.asarray(m, np.complex128))
    for out in (planes, composed):
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-2 * n ** 0.5)


@pytest.mark.parametrize("shape", [(4, 32), (8, 32), (6, 32), (32, 12)])
def test_fft2d_fused_shapes(rng, shape):
    """Rectangular input (phase 2's row length is the row count), padded
    blocks, and lengths the kernel does not take."""
    m = csignal(rng, *shape)
    out = fft2d_fused(m)
    assert out.shape == shape
    np.testing.assert_allclose(np.asarray(out),
                               np.fft.fft2(np.asarray(m, np.complex128)),
                               atol=1e-3)


def test_pfft_limb_fused_equals_fft2d_fused(rng):
    m = csignal(rng, 64, 64)
    limb = _pfft_limb(m, np.array([20, 24, 20]), config=PlanConfig(fused=True))
    np.testing.assert_array_equal(np.asarray(limb),
                                  np.asarray(fft2d_rowcol(m, fused=True)))


def test_fused_phase_fallbacks(rng):
    # non-pow2 length and batched input take the unfused fallback path
    x = csignal(rng, 6, 12)
    np.testing.assert_allclose(
        np.asarray(fft_rows_then_transpose(x)),
        np.asarray(jnp.fft.fft(x, axis=-1).T), atol=1e-4)
    xb = jnp.stack([csignal(rng, 4, 8), csignal(rng, 4, 8)])
    np.testing.assert_allclose(
        np.asarray(fft_rows_then_transpose(xb)),
        np.asarray(jnp.fft.fft(xb, axis=-1).swapaxes(-1, -2)), atol=1e-4)


def test_pfft_lb_fused_matches(rng):
    m = csignal(rng, 64, 64)
    np.testing.assert_allclose(
        np.asarray(pfft_lb(m, 3, config=PlanConfig(fused=True))),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)


# ------------------------------------------------- batched segment dispatch

def test_segment_batching_plan(rng):
    n = 32
    d = np.array([10, 7, 0, 15])
    pads = np.array([40, 32, 48, 40])
    plan = plan_segment_batches(d, pads, n)
    # one dispatch per *distinct* pad length among non-empty segments
    assert sorted(plan.keys()) == [32, 40]
    covered = np.sort(np.concatenate(list(plan.values())))
    np.testing.assert_array_equal(covered, np.arange(n))


@pytest.mark.parametrize("pads", [None, [40, 32, 40]])
def test_segment_batched_equals_looped(rng, pads):
    n = 32
    m = csignal(rng, n, n)
    d = np.array([10, 7, 15])
    pads = np.array(pads) if pads is not None else None
    batched = segment_row_ffts(m, d, pad_lengths=pads,
                               config=PlanConfig(batched=True))
    looped = segment_row_ffts(m, d, pad_lengths=pads,
                              config=PlanConfig(batched=False))
    np.testing.assert_allclose(np.asarray(batched), np.asarray(looped),
                               atol=1e-4)
