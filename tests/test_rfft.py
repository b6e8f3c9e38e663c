"""Real-input rfft2 pipeline: packed-row kernels vs the rfft oracle,
Hermitian/round-trip property tests over odd/even N and both float
precisions, the FPM-partitioned limbs (padded real == padded complex
half spectrum, bin for bin), the planner's real-vs-complex race and
wisdom round trip, and the distributed half-spectrum exchange (via the
shared dist rigs — subprocess for tier-1, ``multi_device`` marks for
the forced-4-device CI job)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st  # hypothesis or fallback
from repro.core import FPMSet, PlanConfig, plan_pfft
from repro.core.fpm import SpeedFunction
from repro.core.pfft import (halfspec_distribution, pfft_fpm_pad, rpfft_fpm,
                             rpfft_fpm_pad, rpfft_lb, segment_row_rffts)
from repro.fft import irfft2, rfft2, rfft_rows, rfft_rows_then_transpose
from repro.plan import (dist_comm_bytes, estimate_cost, halfspec_cols,
                        rfft_pad_lengths, tune_rfft)


def real_signal(n, seed=0, dtype=np.float32, rows=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((rows or n, n)).astype(dtype))


def hetero_fpms(n, p=3):
    """One slow + (p-1) fast processors whose speed peaks at the next
    pow2, so the FPM pad selection actually engages (mirrors the
    test_pfft rig)."""
    xs = np.array(sorted({1, max(n // 2, 1), n}))
    npow2 = 1 << int(np.ceil(np.log2(n + 1)))
    ys = np.array(sorted({n, npow2, 2 * npow2}))
    fast = np.tile([1e9, 4e9, 1e9], (len(xs), 1))
    slow = np.full((len(xs), len(ys)), 2.5e8)
    return FPMSet([SpeedFunction(xs, ys, slow if i == 0 else fast,
                                 name=f"P{i}") for i in range(p)])


def _tol(x):
    # float64 stays fp64 only when scripts/test.sh enabled x64
    return 1e-3 if jnp.asarray(x).dtype == jnp.float32 else 1e-8


# ------------------------------------------------------------- kernels

@pytest.mark.parametrize("rows,n", [(8, 64), (7, 64), (1, 32), (13, 128)])
def test_packed_rfft_kernel_matches_oracle(rows, n):
    x = real_signal(n, seed=1, rows=rows)
    out = rfft_rows(x, backend="pallas")
    ref = np.fft.rfft(np.asarray(x), axis=-1)
    assert out.shape == (rows, n // 2 + 1)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-3)


@pytest.mark.parametrize("k1_major", [False, True])
@pytest.mark.parametrize("n", [8, 512, 1024, 4096])
def test_reverse_digits_is_exact_bin_reversal(n, k1_major):
    """``reverse_digits`` puts bin (n - k) mod n where bin k was, exactly
    (0/1 permutation products), in both digit layouts."""
    from repro.kernels.fft.kernel import (dft_digits, dft_tables, to_natural,
                                          to_transposed)
    from repro.kernels.fft.real import reverse_digits, reverse_tables
    rows = 8
    rng = np.random.default_rng(n)
    re = jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
    im = jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
    z, _ = dft_digits(re, im, dft_tables(n), k1_major=k1_major)
    rz = reverse_digits(z, reverse_tables(n), k1_major=k1_major)
    if k1_major:
        z, rz = to_transposed(z, rows).T, to_transposed(rz, rows).T
    else:
        z, rz = to_natural(z, rows), to_natural(rz, rows)
    k = np.arange(n)
    np.testing.assert_array_equal(np.asarray(rz), np.asarray(z)[:, (-k) % n])


@pytest.mark.parametrize("transposed", [False, True])
@pytest.mark.parametrize("n", [256, 1024])
def test_packed_spectra_slices_dft_then_reverse_tables(n, transposed):
    """The real kernels' tables are the three-product DFT tables, then the
    reverse tables: ``packed_spectra`` cuts them at ``len(dft_tables(n))``
    and so feeds ``reverse_digits`` its own, whatever the DFT form."""
    from repro.kernels.fft.kernel import dft_tables
    from repro.kernels.fft.real import (packed_spectra, real_tables,
                                        reverse_tables)
    tables = real_tables(n)
    k = len(dft_tables(n))
    assert k == (3 if n <= 512 else 8)
    for got, want in zip(tables, dft_tables(n) + reverse_tables(n),
                         strict=True):
        assert got is want
    rows = 8
    rng = np.random.default_rng(n)
    a, b = (jnp.asarray(rng.standard_normal((rows, n)).astype(np.float32))
            for _ in range(2))
    out = packed_spectra(a, b, tuple(map(jnp.asarray, tables)),
                         transposed=transposed)
    for (re, im), x in zip((out[:2], out[2:]), (a, b)):
        ref = np.fft.fft(np.asarray(x, np.float64), axis=-1)
        got = np.asarray(re) + 1j * np.asarray(im)
        np.testing.assert_allclose(got.T if transposed else got, ref,
                                   atol=1e-5 * np.sqrt(n))


@pytest.mark.parametrize("n", [1024, 4096])
def test_packed_rfft_four_step_matches_oracle(n):
    from repro.kernels.fused.real import rfft_rows_transpose_op
    x = real_signal(n, seed=4, rows=10)
    ref = np.fft.rfft(np.asarray(x, np.float64), axis=-1)
    tol = 1e-5 * np.sqrt(np.mean(np.abs(ref) ** 2))
    np.testing.assert_allclose(np.asarray(rfft_rows(x, backend="pallas")),
                               ref, atol=tol)
    np.testing.assert_allclose(np.asarray(rfft_rows_transpose_op(x)), ref.T,
                               atol=tol)


def test_packed_rfft_kernel_leading_dims():
    x = jnp.asarray(np.random.default_rng(2)
                    .standard_normal((2, 3, 6, 32)).astype(np.float32))
    out = rfft_rows(x, backend="pallas")
    np.testing.assert_allclose(np.asarray(out),
                               np.fft.rfft(np.asarray(x), axis=-1),
                               atol=1e-3)


def test_fused_rfft_transpose_matches_unfused():
    x = real_signal(64, seed=3, rows=24)
    fused = rfft_rows_then_transpose(x)
    ref = np.fft.rfft(np.asarray(x), axis=-1).T
    assert fused.shape == (64 // 2 + 1, 24)
    np.testing.assert_allclose(np.asarray(fused), ref, atol=1e-3)


def test_stockham_backend_packs_rows_too():
    x = real_signal(32, seed=4, rows=5)
    out = rfft_rows(x, backend="stockham")
    np.testing.assert_allclose(np.asarray(out),
                               np.fft.rfft(np.asarray(x), axis=-1),
                               atol=1e-3)


# --------------------------------------------- rfft2 oracle & round trip

@settings(max_examples=25, deadline=None)
@given(n_i=st.integers(0, 5), dtype_i=st.integers(0, 1),
       seed=st.integers(0, 2 ** 16))
def test_rfft2_matches_library_oracle(n_i, dtype_i, seed):
    """Hermitian acceptance: the half spectrum equals jnp.fft.rfft2's
    across odd and even N and both float precisions (the oracle *is* the
    Hermitian-unique half — matching it bin for bin pins both the values
    and the symmetry convention)."""
    n = (7, 8, 15, 16, 33, 48)[n_i]
    dtype = (np.float32, np.float64)[dtype_i]
    x = real_signal(n, seed=seed, dtype=dtype)
    out = rfft2(x)
    ref = jnp.fft.rfft2(x)
    assert out.shape == (n, n // 2 + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=_tol(x), rtol=1e-4)


@settings(max_examples=25, deadline=None)
@given(n_i=st.integers(0, 5), dtype_i=st.integers(0, 1),
       seed=st.integers(0, 2 ** 16))
def test_irfft2_round_trips(n_i, dtype_i, seed):
    n = (7, 8, 15, 16, 33, 48)[n_i]
    dtype = (np.float32, np.float64)[dtype_i]
    x = real_signal(n, seed=seed, dtype=dtype)
    back = irfft2(rfft2(x), n=n)  # odd N needs the explicit length
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=_tol(x))


def test_full_spectrum_reconstructs_hermitian_symmetric():
    """The half spectrum really is the Hermitian-unique half: mirroring
    it reproduces the full complex fft2 of the real signal."""
    n = 16
    x = real_signal(n, seed=9)
    half = np.asarray(rfft2(x))
    full = np.asarray(jnp.fft.fft2(x.astype(jnp.complex64)))
    # X[-u, -v] == conj(X[u, v]): mirror the stored half into the rest
    rec = np.zeros_like(full)
    rec[:, :n // 2 + 1] = half
    for u in range(n):
        for v in range(n // 2 + 1, n):
            rec[u, v] = np.conj(half[(-u) % n, (n - v)])
    np.testing.assert_allclose(rec, full, atol=2e-3)


# ------------------------------------------------------ partitioned limbs

def test_rpfft_lb_matches_oracle():
    n = 64
    x = real_signal(n, seed=5)
    ref = np.fft.rfft2(np.asarray(x))
    for p in (1, 2, 3):
        out = rpfft_lb(x, p)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)
    fused = rpfft_lb(x, 2, config=PlanConfig(radix=4, fused=True, real=True))
    np.testing.assert_allclose(np.asarray(fused), ref, atol=2e-3)


def test_rpfft_fpm_partitioned_matches_oracle():
    n = 48
    x = real_signal(n, seed=6)
    fpms = hetero_fpms(n)
    out, part = rpfft_fpm(x, fpms, return_partition=True)
    assert len(part.d) == 3 and int(np.sum(part.d)) == n
    np.testing.assert_allclose(np.asarray(out),
                               np.fft.rfft2(np.asarray(x)), atol=2e-3)


def test_rpfft_fpm_pad_equals_complex_half_spectrum():
    """The padded real phase must equal the padded *complex* path's half
    spectrum bin for bin — same partition, same pad lengths, same
    crop — or the planner's apples-to-apples race would be comparing
    different transforms.  (The pad-and-crop semantics are the paper's
    interpolation, deliberately != the exact DFT when padding engages,
    so the complex limb on identical (d, pads) is the only oracle.)"""
    from repro.core.pfft import _pfft_limb
    n = 48
    x = real_signal(n, seed=7)
    fpms = hetero_fpms(n)
    out, part, pads = rpfft_fpm_pad(x, fpms, return_partition=True)
    assert any(int(L) > n for L in pads)  # padding actually engages
    ref = _pfft_limb(x.astype(jnp.complex64), part.d, pad_lengths=pads,
                     config=PlanConfig(pad="fpm"))[:, :n // 2 + 1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


def test_rfft_pad_lengths_are_even():
    n = 48
    fpms = hetero_fpms(n)
    d = np.array([16, 16, 16])
    pads = rfft_pad_lengths(fpms, d, n)
    assert pads.shape == (3,)
    assert all(int(L) == n or (int(L) > n and int(L) % 2 == 0)
               for L in pads)


def test_halfspec_distribution_prefix_clips():
    nh = 33  # n=64
    np.testing.assert_array_equal(
        halfspec_distribution(np.array([16, 16, 16, 16]), nh),
        [16, 16, 1, 0])
    np.testing.assert_array_equal(
        halfspec_distribution(np.array([40, 24]), nh), [33, 0])
    d2 = halfspec_distribution(np.array([10, 0, 30, 24]), nh)
    assert int(d2.sum()) == nh and (d2 >= 0).all()


def test_segment_row_rffts_heterogeneous_lengths():
    """Mixed padded/unpadded segments: each real segment must equal the
    complex segment path's crop under the same (d, pads) — the padded
    segments run the paper's pad-and-crop interpolation, so the complex
    path is the oracle."""
    from repro.core.pfft import segment_row_ffts
    n = 32
    x = real_signal(n, seed=8)
    d = np.array([10, 12, 10])
    pads = np.array([n, 64, n], dtype=np.int64)
    out = segment_row_rffts(x, d, pad_lengths=pads,
                            config=PlanConfig(pad="fpm", real=True))
    ref = segment_row_ffts(x.astype(jnp.complex64), d, pad_lengths=pads,
                           config=PlanConfig(pad="fpm"))[:, :n // 2 + 1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)
    # the unpadded segments additionally match the exact rfft
    exact = np.fft.rfft(np.asarray(x), axis=-1)
    np.testing.assert_allclose(np.asarray(out[:10]), exact[:10], atol=1e-3)


# ----------------------------------------------------------- cost model

def test_real_comm_bytes_at_most_60_percent():
    """The half-spectrum panel is strictly smaller everywhere and at
    most 60% of the complex panel on the CI-relevant shapes (small
    (n, p) pay a lane-padding tax on ceil(nh/p)*p, approaching the
    asymptotic 1/2 as n grows)."""
    for n in (16, 64, 128, 256):
        for p in (2, 4, 8):
            full = dist_comm_bytes(n, p)
            half = dist_comm_bytes(n, p, real=True)
            assert half <= full, (n, p)  # n=16,p=8 degenerates to equal
            assert half == n * halfspec_cols(n, p) * 8 * (p - 1) / p
    for n, p in ((64, 4), (128, 4), (256, 4), (256, 8)):
        ratio = dist_comm_bytes(n, p, real=True) / dist_comm_bytes(n, p)
        assert ratio <= 0.6, (n, p, ratio)


def test_estimate_prefers_real_config():
    n = 64
    cplx = PlanConfig()
    real = PlanConfig(real=True)
    assert estimate_cost(real, n=n) < estimate_cost(cplx, n=n)


# -------------------------------------------------------------- planner

def test_tune_rfft_measure_races_both_families():
    sched, info = tune_rfft(64, mode="measure", top_k=2, reps=2)
    fams = {c["real"] for c, _ in info["measured"]}
    assert fams == {True, False}
    assert info["chosen_path"] in ("real", "complex")
    assert sched.anchor_config.real == (info["chosen_path"] == "real")


def test_plan_pfft_real_methods_match_oracle():
    from repro.core.pfft import _pfft_limb
    n = 48
    x = real_signal(n, seed=10)
    ref = np.fft.rfft2(np.asarray(x))
    fpms = hetero_fpms(n)
    for kwargs in (dict(p=3, method="rfft-lb"),
                   dict(p=2, method="rfft-lb", tune="estimate"),
                   dict(fpms=fpms, method="rfft-fpm")):
        plan = plan_pfft(n, dtype="float32", **kwargs)
        out = plan.execute(x)
        assert out.shape == (n, n // 2 + 1)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)
    # fpm-pad runs the padded interpolation, so its oracle is the complex
    # limb on the plan's own (d, pads), cropped to the half spectrum
    plan = plan_pfft(n, fpms=fpms, method="rfft-fpm-pad", tune="estimate",
                     dtype="float32")
    pad_ref = _pfft_limb(x.astype(jnp.complex64), plan.d,
                         pad_lengths=plan.pad_lengths,
                         config=PlanConfig(pad="fpm"))[:, :n // 2 + 1]
    np.testing.assert_allclose(np.asarray(plan.execute(x)),
                               np.asarray(pad_ref), atol=2e-3)


def test_plan_pfft_real_method_dtype_validation():
    with pytest.raises(ValueError, match="transforms real input"):
        plan_pfft(32, p=2, method="rfft-lb")  # default complex64
    with pytest.raises(ValueError, match="transforms complex input"):
        plan_pfft(32, p=2, method="lb", dtype="float32")
    with pytest.raises(ValueError, match="no Bluestein"):
        PlanConfig(real=True, pad="czt")


def test_plan_pfft_real_explicit_config_is_real_flagged():
    n = 32
    x = real_signal(n, seed=11)
    plan = plan_pfft(n, p=2, method="rfft-lb", dtype="float32",
                     config=PlanConfig(radix=2))
    assert plan.config.real
    np.testing.assert_allclose(np.asarray(plan.execute(x)),
                               np.fft.rfft2(np.asarray(x)), atol=2e-3)


def test_real_wisdom_round_trip_zero_remeasure(tmp_path):
    n = 32
    w = str(tmp_path / "wisdom.json")
    x = real_signal(n, seed=12)
    p1 = plan_pfft(n, p=2, method="rfft-lb", tune="measure", wisdom=w,
                   dtype="float32")
    assert p1.tuning["source"] == "measure"
    assert "method=rfft-lb" in p1.tuning["wisdom_key"]
    assert "dtype=float32" in p1.tuning["wisdom_key"]
    p2 = plan_pfft(n, p=2, method="rfft-lb", tune="measure", wisdom=w,
                   dtype="float32")
    assert p2.tuning["source"] == "wisdom"      # served from disk,
    assert "measured" not in p2.tuning          # zero re-measurement
    np.testing.assert_allclose(np.asarray(p2.execute(x)),
                               np.fft.rfft2(np.asarray(x)), atol=2e-3)


# ---------------------------------------------------------- distributed

_RFFT_DIST_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import plan_pfft
from repro.core.pfft_dist import (irpfft2_distributed, pfft2_distributed,
                                  rpfft2_distributed)
from repro.plan import PlanConfig, dist_comm_bytes

n = 64
mesh = jax.make_mesh((4,), ("fft",))
rng = np.random.default_rng(13)
x = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
ref = np.fft.rfft2(np.asarray(x))

out = rpfft2_distributed(x, mesh)
assert np.abs(np.asarray(out) - ref).max() < 2e-3, "dist oracle"
crop = np.asarray(pfft2_distributed(x.astype(jnp.complex64), mesh))[:, :n//2+1]
assert np.abs(np.asarray(out) - crop).max() < 2e-3, "vs complex crop"
back = irpfft2_distributed(out, mesh)
assert np.abs(np.asarray(back) - np.asarray(x)).max() < 1e-4, "round trip"
assert dist_comm_bytes(n, 4, real=True) <= 0.6 * dist_comm_bytes(n, 4)

plan = plan_pfft(n, method="rfft-lb", mesh=mesh, tune="measure",
                 dtype="float32")
assert np.abs(np.asarray(plan.execute(x)) - ref).max() < 2e-3, "planned"
assert plan.tuning["dist"]["comm_ratio_real"] <= 0.6
fams = {c["real"] for c, _ in plan.tuning["measured"]}
assert fams == {True, False}, f"one-family race: {fams}"
print("RFFT_DIST_OK")
"""


def test_real_distributed_via_subprocess(dist_subprocess):
    """Tier-1 acceptance: the half-spectrum exchange matches the oracle
    (and the complex path's crop) on a real 4-device mesh, the planner
    races both families end to end, and the recorded comm ratio is
    <= 0.6 — via the shared conftest dist rig."""
    dist_subprocess(_RFFT_DIST_SCRIPT, devices=4, sentinel="RFFT_DIST_OK")


@pytest.mark.multi_device
def test_real_distributed_forced_devices():
    """The forced-device CI job's in-process variant."""
    from repro.core.pfft_dist import irpfft2_distributed, rpfft2_distributed
    p = min(jax.device_count(), 4)
    n = 16 * p
    mesh = jax.make_mesh((p,), ("fft",))
    x = real_signal(n, seed=14)
    out = rpfft2_distributed(x, mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.fft.rfft2(np.asarray(x)), atol=2e-3)
    back = irpfft2_distributed(out, mesh)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-4)


@pytest.mark.multi_device
def test_real_distributed_plan_forced_devices(tmp_path):
    p = min(jax.device_count(), 4)
    n = 16 * p  # hc = 9p for nh = 8p + 1, so the comm ratio is 0.5625
    mesh = jax.make_mesh((p,), ("fft",))
    x = real_signal(n, seed=15)
    ref = np.fft.rfft2(np.asarray(x))
    w = str(tmp_path / "wisdom.json")
    p1 = plan_pfft(n, method="rfft-lb", mesh=mesh, tune="measure",
                   wisdom=w, dtype="float32")
    np.testing.assert_allclose(np.asarray(p1.execute(x)), ref, atol=2e-3)
    assert p1.tuning["dist"]["comm_ratio_real"] <= 0.6
    p2 = plan_pfft(n, method="rfft-lb", mesh=mesh, tune="measure",
                   wisdom=w, dtype="float32")
    assert p2.tuning["source"] == "wisdom"
    np.testing.assert_allclose(np.asarray(p2.execute(x)), ref, atol=2e-3)


def test_real_dist_program_shape_is_validated():
    """The half-spectrum exchange supports the homogeneous unfused
    monolithic program only — everything else is refused eagerly."""
    from repro.core.pfft_dist import _validate_real_dist
    with pytest.raises(ValueError, match="real config"):
        _validate_real_dist(PlanConfig(), None)
    with pytest.raises(ValueError, match="unfused and monolithic"):
        _validate_real_dist(PlanConfig(real=True, fused=True), None)
    with pytest.raises(ValueError, match="unfused and monolithic"):
        _validate_real_dist(PlanConfig(real=True, pipeline_panels=2), None)


def test_plan_pfft_mesh_rejects_real_fpm_methods():
    fpms = hetero_fpms(64, p=1)
    mesh = jax.make_mesh((1,), ("fft",))
    with pytest.raises(ValueError, match="byte-identically"):
        plan_pfft(64, method="rfft-fpm", fpms=fpms, mesh=mesh,
                  dtype="float32")
    with pytest.raises(ValueError, match="homogeneous unpadded"):
        plan_pfft(64, method="rfft-fpm-pad", fpms=fpms, mesh=mesh,
                  dtype="float32")
