"""Heterogeneous per-segment schedules: SegmentSchedule round-trips,
degenerate equivalence with the PR-2 config= paths, mixed-backend phase
correctness vs the naive DFT oracle, wisdom v2 schedule persistence (and
the v1 migration-to-miss), cost-param calibration, the distributed
routing, and the ISSUE-3 acceptance scenario (one slow + p-1 fast
processors => >= 2 distinct per-segment configs, makespan estimate no
worse than the best homogeneous config)."""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st  # hypothesis or fallback
from repro.core import FPMSet, PlanConfig, SpeedFunction, plan_pfft
from repro.core.pfft import (_pfft_limb, pfft_fpm_czt, plan_segment_batches,
                             segment_row_ffts)
from repro.core.partition import lb_partition
from repro.fft.dft_ref import dft1d_naive
from repro.plan import (CostParams, SegmentPlan, SegmentSchedule,
                        candidate_configs, estimate_cost,
                        estimate_schedule_cost, fit_cost_params, load_wisdom,
                        lookup_wisdom, record_wisdom, tune_schedule,
                        wisdom_key)
from repro.plan.cost import V5E_KIND
from repro.plan.wisdom import WISDOM_VERSION


def random_signal(n, seed=0, dtype=np.complex64):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.standard_normal((n, n))
                        + 1j * rng.standard_normal((n, n))).astype(dtype))


def hetero_fpms(n, p=3, slow_factor=8.0):
    """One slow processor, p-1 fast ones, with a speed landscape that
    makes padding n -> next pow2 attractive for the fast processors."""
    xs = np.array(sorted({1, max(n // 4, 1), max(n // 2, 1), n}))
    npow2 = 1 << int(np.ceil(np.log2(n)))
    ys = np.array(sorted({n, npow2, 2 * npow2}))
    base = np.outer(np.maximum(xs, 1), np.log2(np.maximum(ys, 2))) + 5.0
    fns = [SpeedFunction(xs, ys, base / (slow_factor if i == 0 else 1.0),
                         name=f"P{i}") for i in range(p)]
    return FPMSet(fns)


# ------------------------------------------------------- schedule round-trip

def test_segment_schedule_dict_roundtrip():
    sched = SegmentSchedule.from_parts(
        48, [16, 32], [48, 64],
        [PlanConfig(pad="fpm"), PlanConfig(radix=4, pad="fpm")])
    assert SegmentSchedule.from_dict(sched.to_dict()) == sched
    assert len(sched) == 2 and sched.total_rows == 48
    assert sched.common_config is None
    assert len(sched.configs) == 2
    # anchor = makespan-dominant (most rows) entry's config
    assert sched.anchor_config == PlanConfig(radix=4, pad="fpm")
    with pytest.raises(ValueError):
        SegmentSchedule.from_dict({**sched.to_dict(), "warp_drive": 1})
    with pytest.raises(ValueError):
        SegmentPlan.from_dict({"index": 0, "rows": 1, "length": 8,
                               "config": {}, "alien": True})


def test_segment_schedule_validation():
    cfg = PlanConfig()
    with pytest.raises(ValueError):
        SegmentSchedule(n=8, entries=())
    with pytest.raises(ValueError):
        SegmentPlan(index=0, rows=0, length=8, config=cfg)
    with pytest.raises(TypeError):
        SegmentPlan(index=0, rows=4, length=8, config="xla")
    with pytest.raises(ValueError):  # non-ascending indices
        SegmentSchedule(n=8, entries=(
            SegmentPlan(index=1, rows=4, length=8, config=cfg),
            SegmentPlan(index=0, rows=4, length=8, config=cfg)))
    with pytest.raises(ValueError):  # more rows than N
        SegmentSchedule(n=4, entries=(
            SegmentPlan(index=0, rows=8, length=4, config=cfg),))


def test_schedule_matches_partition_structure():
    d = np.array([16, 0, 16])
    pads = np.array([32, 32, 40])
    sched = SegmentSchedule.homogeneous(PlanConfig(pad="fpm"), 32, d, pads)
    assert [e.index for e in sched] == [0, 2]  # empty segment skipped
    assert sched.matches(d, pads)
    assert not sched.matches(np.array([8, 8, 16]), pads)
    assert not sched.matches(d, np.array([32, 32, 48]))
    assert not sched.matches(np.array([16, 16]))


def test_batch_groups_merge_and_optout():
    shared = PlanConfig()
    loner = PlanConfig(batched=False)
    sched = SegmentSchedule.from_parts(
        32, [8, 8, 8, 8], None, [shared, shared, loner, loner])
    groups = sched.batch_groups()
    # two batched segments share one dispatch; each batched=False segment
    # opts out into its own
    assert len(groups) == 3
    assert [len(idx) for _, _, idx in groups] == [16, 8, 8]


def test_plan_segment_batches_by_length_and_config():
    n = 32
    d = np.array([8, 8, 8, 8])
    pads = np.array([n, 64, 64, n], dtype=np.int64)
    by_len = plan_segment_batches(d, pads, n)
    assert sorted(by_len) == [32, 64]
    fast = PlanConfig(radix=4, pad="fpm")
    slow = PlanConfig(pad="fpm")
    by_cfg = plan_segment_batches(d, pads, n,
                                  configs=[slow, fast, slow, slow])
    # same 64-length rows split across two dispatches now: one per config
    assert sorted(k[0] for k in by_cfg) == [32, 64, 64]
    total = np.sort(np.concatenate(list(by_cfg.values())))
    np.testing.assert_array_equal(total, np.arange(n))


# ------------------------------------------- degenerate (PR-2) equivalence

@pytest.mark.parametrize("cfg", [
    PlanConfig(),
    PlanConfig(batched=False),
    PlanConfig(radix=2),
    PlanConfig(radix=4, fused=True),
])
def test_degenerate_schedule_matches_config_path(cfg):
    """schedule=homogeneous(config) is bit-identical to config= — the PR-2
    API is now a shim over the schedule executor."""
    n = 32
    d = lb_partition(n, 3).d
    m = random_signal(n, seed=7)
    sched = SegmentSchedule.homogeneous(cfg, n, d)
    via_schedule = _pfft_limb(m, d, schedule=sched)
    via_config = _pfft_limb(m, d, config=cfg)
    np.testing.assert_array_equal(np.asarray(via_schedule),
                                  np.asarray(via_config))


def test_degenerate_schedule_matches_config_path_padded():
    n = 32
    d = lb_partition(n, 3).d
    pads = np.array([n, 2 * n, n], dtype=np.int64)
    m = random_signal(n, seed=8)
    for cfg in (PlanConfig(pad="fpm"), PlanConfig(pad="fpm", batched=False)):
        sched = SegmentSchedule.homogeneous(cfg, n, d, pads)
        via_schedule = segment_row_ffts(m, d, schedule=sched)
        via_config = segment_row_ffts(m, d, pad_lengths=pads, config=cfg)
        np.testing.assert_array_equal(np.asarray(via_schedule),
                                      np.asarray(via_config))


def test_schedule_and_config_conflict_is_an_error():
    n, d = 16, lb_partition(16, 2).d
    m = random_signal(n)
    sched = SegmentSchedule.homogeneous(PlanConfig(), n, d)
    with pytest.raises(ValueError):
        segment_row_ffts(m, d, schedule=sched, config=PlanConfig())
    with pytest.raises(ValueError):
        _pfft_limb(m, d, schedule=sched, config=PlanConfig())
    # pad_lengths conflicts too: the schedule carries its own lengths
    pads = np.array([16, 32], dtype=np.int64)
    with pytest.raises(ValueError):
        segment_row_ffts(m, d, schedule=sched, pad_lengths=pads)
    with pytest.raises(ValueError):
        _pfft_limb(m, d, schedule=sched, pad_lengths=pads)


def test_plan_segment_batches_configs_matches_executor_dispatch_count():
    """len(plan_segment_batches(configs=)) must equal the number of
    dispatch groups the executor actually runs, batched=False opt-outs
    included."""
    n = 32
    d = np.array([8, 8, 8, 8])
    pads = np.array([n, 64, 64, n], dtype=np.int64)
    cfgs = [PlanConfig(batched=False, pad="fpm")] * 4
    by_cfg = plan_segment_batches(d, pads, n, configs=cfgs)
    sched = SegmentSchedule.from_parts(n, d, pads, cfgs)
    assert len(by_cfg) == len(sched.batch_groups()) == 4
    total = np.sort(np.concatenate(list(by_cfg.values())))
    np.testing.assert_array_equal(total, np.arange(n))


# -------------------------------------------------- mixed-backend phases

def test_mixed_backend_phase_matches_dft_ref():
    """A schedule mixing the library FFT, the pure-jnp Stockham, and the
    Pallas kernel across segments of one phase computes the same row DFT
    as the naive oracle (satellite acceptance)."""
    n = 32
    d = np.array([12, 10, 10])
    m = random_signal(n, seed=11)
    sched = SegmentSchedule.from_parts(
        n, d, None,
        [PlanConfig(), PlanConfig(radix=2), PlanConfig(radix=4)])
    assert len(sched.configs) == 3
    out = segment_row_ffts(m, d, schedule=sched)
    ref = dft1d_naive(m, axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-2, rtol=1e-3)


def test_mixed_backend_full_limb_matches_fft2():
    n = 32
    d = np.array([16, 16])
    m = random_signal(n, seed=12)
    sched = SegmentSchedule.from_parts(
        n, d, None, [PlanConfig(), PlanConfig(radix=2)])
    out = _pfft_limb(m, d, schedule=sched)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft2(m)), atol=5e-2)


# ----------------------------------------------------------- wisdom v2

def test_wisdom_schedule_roundtrip(tmp_path):
    path = str(tmp_path / "wisdom.json")
    key = wisdom_key(n=48, dtype="complex64", p=3, method="fpm-pad",
                     backend="cpu", detail="cafe0123")
    sched = SegmentSchedule.from_parts(
        48, [16, 32], [48, 64],
        [PlanConfig(pad="fpm"), PlanConfig(radix=4, pad="fpm")])
    record_wisdom(path, key, sched, mode="measure", time_s=3e-4)
    got, entry = lookup_wisdom(path, key)
    assert isinstance(got, SegmentSchedule) and got == sched
    assert entry["mode"] == "measure" and "schedule" in entry
    # configs and schedules coexist in one store
    key2 = wisdom_key(n=48, dtype="complex64", p=3, method="lb", backend="cpu")
    record_wisdom(path, key2, PlanConfig(radix=2), mode="estimate")
    got2, _ = lookup_wisdom(path, key2)
    assert got2 == PlanConfig(radix=2)
    assert lookup_wisdom(path, key)[0] == sched  # first entry survived


def test_wisdom_v1_entries_become_misses(tmp_path):
    """A pre-schedule (v1) store is a whole-file miss — never a crash —
    and recording over it rewrites a clean v2 store."""
    path = str(tmp_path / "wisdom.json")
    key = wisdom_key(n=32, dtype="complex64", p=2, method="lb", backend="cpu")
    v1_doc = {"version": 1, "entries": {key: {
        "config": {"radix": None, "fused": False, "batched": True,
                   "pad": "none", "pipeline_panels": 1},
        "mode": "measure", "time_s": 1e-4}}}
    with open(path, "w") as fh:
        json.dump(v1_doc, fh)
    assert WISDOM_VERSION == 3
    assert load_wisdom(path) == {}
    assert lookup_wisdom(path, key) is None
    plan = plan_pfft(32, p=2, method="lb", wisdom=path)  # miss, no crash
    assert plan.tuning["source"] == "off"
    record_wisdom(path, key, PlanConfig(), mode="measure")
    assert json.load(open(path))["version"] == WISDOM_VERSION
    assert lookup_wisdom(path, key) is not None


def test_stale_schedule_structure_is_a_miss(tmp_path):
    """A stored schedule that no longer describes the current partition
    (e.g. a hand-edited store) is a miss, not an error."""
    path = str(tmp_path / "wisdom.json")
    key = wisdom_key(n=32, dtype="complex64", p=2, method="lb", backend="cpu")
    wrong = SegmentSchedule.from_parts(32, [10, 22], None,
                                       [PlanConfig(), PlanConfig()])
    record_wisdom(path, key, wrong, mode="measure")
    plan = plan_pfft(32, p=2, method="lb", wisdom=path)  # lb d = [16, 16]
    assert plan.tuning["source"] == "off"
    assert plan.schedule.matches(plan.d)


def test_explicit_config_keeps_method_pad_semantics():
    """pad is semantics owned by the method: an explicit config= with the
    wrong pad is normalized, so fpm-czt still runs Bluestein (exact DFT)
    instead of pad-and-crop at Bluestein lengths, and fpm-pad keeps its
    padded-signal semantics (PR-2 behavior)."""
    n = 16
    fpms = hetero_fpms(n)
    m = random_signal(n, seed=21)
    plan = plan_pfft(n, fpms=fpms, method="fpm-czt", config=PlanConfig())
    assert plan.config.pad == "czt"
    np.testing.assert_allclose(np.asarray(plan.execute(m)),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)
    plan_pad = plan_pfft(n, fpms=fpms, method="fpm-pad",
                         config=PlanConfig(radix=2))
    assert plan_pad.config.pad == "fpm" and plan_pad.config.radix == 2
    ref = plan_pfft(n, fpms=fpms, method="fpm-pad")
    np.testing.assert_allclose(np.asarray(plan_pad.execute(m)),
                               np.asarray(ref.execute(m)), atol=5e-2)
    # fused drops on padded methods, like the legacy shim documents
    plan_f = plan_pfft(n, fpms=fpms, method="fpm-pad",
                       config=PlanConfig(radix=4, fused=True))
    assert not plan_f.config.fused and plan_f.config.pad == "fpm"


def test_heterogeneous_schedule_served_from_wisdom(tmp_path):
    """A genuinely mixed per-segment schedule recorded for the plan's
    exact partition structure is served back intact and executes."""
    path = str(tmp_path / "wisdom.json")
    n = 48
    probe = plan_pfft(n, p=2, method="lb", wisdom=path)
    key = probe.tuning["wisdom_key"]
    mixed = SegmentSchedule.from_parts(
        n, probe.d, None, [PlanConfig(), PlanConfig(radix=2)])
    assert len(mixed.configs) == 2
    record_wisdom(path, key, mixed, mode="measure", time_s=1e-3)
    served = plan_pfft(n, p=2, method="lb", wisdom=path)
    assert served.tuning["source"] == "wisdom"
    assert served.schedule == mixed
    m = random_signal(n, seed=22)
    np.testing.assert_allclose(np.asarray(served.execute(m)),
                               np.asarray(jnp.fft.fft2(m)), atol=5e-2)


def test_plan_pfft_persists_and_serves_schedules(tmp_path):
    path = str(tmp_path / "wisdom.json")
    n = 32
    p1 = plan_pfft(n, p=2, method="lb", tune="measure", wisdom=path)
    assert p1.tuning["source"] == "measure"
    assert isinstance(p1.schedule, SegmentSchedule)
    p2 = plan_pfft(n, p=2, method="lb", tune="measure", wisdom=path)
    assert p2.tuning["source"] == "wisdom"
    assert p2.schedule == p1.schedule
    m = random_signal(n)
    np.testing.assert_allclose(np.asarray(p2.execute(m)),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)


# ------------------------------------------------------ acceptance scenario

def test_hetero_fpms_produce_multi_config_schedule():
    """ISSUE 3 acceptance: one slow + p-1 fast processors, estimate mode,
    accelerator cost constants => a schedule with >= 2 distinct configs
    whose makespan estimate is <= the best homogeneous config's."""
    n = 48  # non-pow2: the unpadded group keeps the library FFT
    d = np.array([16, 16, 16])
    pads = np.array([48, 64, 64], dtype=np.int64)  # fast procs pad to pow2
    fpms = hetero_fpms(n)
    params = CostParams.for_backend("tpu", device_kind=V5E_KIND)
    sched, info = tune_schedule(n, d=d, pad_lengths=pads, fpms=fpms,
                                mode="estimate", pad="fpm", params=params)
    assert len(sched.configs) >= 2
    assert info["chosen"] == "heterogeneous"
    est_hetero = estimate_schedule_cost(sched, fpms=fpms, params=params)
    est_homo = min(
        estimate_cost(c, n=n, d=d, pad_lengths=pads, fpms=fpms, params=params)
        for c in candidate_configs(n, pad="fpm", d=d))
    assert est_hetero <= est_homo
    assert info["heterogeneous"]["est_s"] <= info["homogeneous"]["est_s"]

    # The schedule executes to the padded-signal oracle (pad-and-crop
    # DFT semantics, per segment) with the exact same values as the
    # homogeneous library path at the same lengths.
    m = random_signal(n, seed=13)
    out = _pfft_limb(m, d, schedule=sched)
    ref = _pfft_limb(m, d, pad_lengths=pads, config=PlanConfig(pad="fpm"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-2, rtol=1e-3)


def test_tune_schedule_single_length_delegates_to_homogeneous():
    n = 64
    d = lb_partition(n, 3).d
    sched, info = tune_schedule(n, d=d, mode="estimate",
                                params=CostParams.for_backend("cpu"))
    assert info["chosen"] == "homogeneous"
    assert sched.common_config is not None
    assert "ranked" in info  # PR-2 audit trail preserved


def test_tune_schedule_measure_mode_multi_length():
    n = 24
    d = np.array([8, 8, 8])
    pads = np.array([24, 32, 32], dtype=np.int64)
    sched, info = tune_schedule(n, d=d, pad_lengths=pads, mode="measure",
                                pad="fpm", top_k=2, reps=1)
    assert sched.matches(d, pads)
    assert info["time_s"] > 0
    assert "group_measured" in info and "measured" in info
    m = random_signal(n, seed=14)
    out = _pfft_limb(m, d, schedule=sched)
    ref = _pfft_limb(m, d, pad_lengths=pads, config=PlanConfig(pad="fpm"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-2, rtol=1e-3)


# ------------------------------------------------------------ batched czt

def test_czt_same_length_segments_share_a_dispatch():
    n = 16
    d = np.array([6, 6, 4])
    lens = np.array([32, 32, 32], dtype=np.int64)
    cfgs = [PlanConfig(pad="czt")] * 3
    sched = SegmentSchedule.from_parts(n, d, lens, cfgs)
    assert len(sched.batch_groups()) == 1  # one Bluestein dispatch
    m = random_signal(n, seed=15)
    out = _pfft_limb(m, d, schedule=sched)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)


def test_pfft_fpm_czt_matches_exact_dft_via_schedule_path():
    n = 24
    fpms = hetero_fpms(n)
    m = random_signal(n, seed=16)
    out, part, lens = pfft_fpm_czt(m, fpms, return_partition=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)
    assert np.all(lens >= 2 * n - 1)


# ------------------------------------------------------------- calibration

def _synth_wisdom_entries(params: CostParams, n_entries: int = 10) -> dict:
    """Measured entries whose times are exactly the cost model's
    prediction under ``params`` — a fit must recover the constants."""
    entries = {}
    for i in range(n_entries):
        n = 32 * (1 + i % 4)
        p = 2 + i % 3
        cfg = PlanConfig(radix=2 if i % 2 else None)
        d = lb_partition(n, p).d
        t = estimate_cost(cfg, n=n, d=d, params=params)
        key = wisdom_key(n=n, dtype="complex64", p=p, method="lb",
                         backend="cpu")
        entries[f"{key}|i={i}"] = {"config": cfg.to_dict(),
                                   "mode": "measure", "time_s": float(t)}
    return entries


def test_fit_cost_params_recovers_synthetic_constants():
    true = CostParams.for_backend("cpu")
    entries = _synth_wisdom_entries(true, 12)
    fitted = fit_cost_params(entries, backend="cpu")
    assert fitted.backend_factor["xla"] == pytest.approx(
        true.backend_factor["xla"], rel=0.2)
    assert fitted.backend_factor["stockham"] == pytest.approx(
        true.backend_factor["stockham"], rel=0.2)
    assert fitted.dispatch_overhead_s == pytest.approx(
        true.dispatch_overhead_s, rel=0.2)
    # pallas never sampled -> hard-coded constant kept
    assert fitted.backend_factor["pallas"] == true.backend_factor["pallas"]


def test_fit_cost_params_falls_back_below_threshold():
    true = CostParams.for_backend("cpu")
    entries = _synth_wisdom_entries(true, 3)
    assert fit_cost_params(entries, backend="cpu") == true  # < 8 entries
    assert fit_cost_params({}, backend="cpu") == true
    # corrupt entries are skipped, not fatal
    bad = dict(entries)
    bad["n=oops"] = {"time_s": "NaN?"}
    assert fit_cost_params(bad, backend="cpu") == true


def test_fit_cost_params_from_file(tmp_path):
    path = str(tmp_path / "wisdom.json")
    true = CostParams.for_backend("cpu")
    for key, entry in _synth_wisdom_entries(true, 9).items():
        record_wisdom(path, key, PlanConfig.from_dict(entry["config"]),
                      mode="measure", time_s=entry["time_s"])
    fitted = fit_cost_params(path, backend="cpu")
    assert fitted.backend_factor["xla"] == pytest.approx(
        true.backend_factor["xla"], rel=0.2)


# ------------------------------------------------------------- distributed

def test_dist_rejects_unloweable_heterogeneous_schedule():
    """Heterogeneous row-FFT mixes lower as device-group programs now;
    what still raises the named SPMD error are program-knob mixes (fused
    here) and entries that cannot tile the mesh's equal shards."""
    from repro.core.pfft_dist import pfft2_distributed
    mesh = jax.make_mesh((1,), ("fft",))
    n = 16
    fused_mix = SegmentSchedule.from_parts(
        n, [8, 8], None, [PlanConfig(radix=4, fused=True), PlanConfig()])
    with pytest.raises(ValueError, match="SPMD"):
        pfft2_distributed(random_signal(n), mesh, "fft", schedule=fused_mix)
    # 1-device mesh: n_loc = 16, entries of 8 rows can't tile the shard
    untileable = SegmentSchedule.from_parts(
        n, [8, 8], None, [PlanConfig(), PlanConfig(radix=2)])
    with pytest.raises(ValueError, match="SPMD"):
        pfft2_distributed(random_signal(n), mesh, "fft", schedule=untileable)


def test_dist_schedule_carries_fpm_pad_length():
    """The schedule's FPM-chosen effective length reaches the local
    phase (not the model-free smooth default); mixed lengths run at the
    schedule's max — the device-group uniform-length rule."""
    from repro.core.pfft_dist import pfft2_distributed
    mesh = jax.make_mesh((1,), ("fft",))
    n = 48
    m = random_signal(n, seed=23)
    sched = SegmentSchedule.homogeneous(PlanConfig(pad="fpm"), n, [n],
                                        np.array([64]))
    out = pfft2_distributed(m, mesh, "fft", schedule=sched)
    ref = pfft2_distributed(m, mesh, "fft", padded="crop", pad_len=64)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    mixed_len = SegmentSchedule.from_parts(
        n, [24, 24], np.array([48, 64]), [PlanConfig(pad="fpm")] * 2)
    out_mixed = pfft2_distributed(m, mesh, "fft", schedule=mixed_len)
    np.testing.assert_array_equal(np.asarray(out_mixed), np.asarray(ref))


def test_dist_schedule_and_fused_single_device():
    from repro.core.pfft_dist import pfft2_distributed
    mesh = jax.make_mesh((1,), ("fft",))
    n = 32
    m = random_signal(n, seed=17)
    ref = jnp.fft.fft2(m)
    sched = SegmentSchedule.homogeneous(PlanConfig(radix=4, fused=True), n, [n])
    out = pfft2_distributed(m, mesh, "fft", schedule=sched)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)
    # fused pipelined panels agree with the unfused path too
    out_p = pfft2_distributed(
        m, mesh, "fft", config=PlanConfig(radix=4, fused=True,
                                          pipeline_panels=4))
    un = pfft2_distributed(m, mesh, "fft")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(un), atol=2e-2)


_FUSED_2DEV_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.pfft_dist import pfft2_distributed
from repro.plan import PlanConfig

mesh = jax.make_mesh((2,), ("fft",))
rng = np.random.default_rng(5)
m = jnp.asarray((rng.standard_normal((32, 32))
                 + 1j*rng.standard_normal((32, 32))).astype(np.complex64))
ref = jnp.fft.fft2(m)
unfused = pfft2_distributed(m, mesh, "fft")
fused = pfft2_distributed(m, mesh, "fft", config=PlanConfig(radix=4, fused=True))
assert float(jnp.max(jnp.abs(fused - ref))) < 1e-2, "fused vs fft2"
assert float(jnp.max(jnp.abs(fused - unfused))) < 1e-2, "fused vs unfused"
fp = pfft2_distributed(m, mesh, "fft",
                       config=PlanConfig(radix=4, fused=True, pipeline_panels=2))
assert float(jnp.max(jnp.abs(fp - unfused))) < 1e-2, "fused pipelined"
print("FUSED_DIST_OK")
"""


def test_fused_equals_unfused_on_two_device_mesh(dist_subprocess):
    """Satellite acceptance: the planner's fused pick reaches the
    distributed local phase and matches the unfused path on a real
    (faked) 2-device mesh — via the shared conftest dist rig."""
    dist_subprocess(_FUSED_2DEV_SCRIPT, devices=2, sentinel="FUSED_DIST_OK")


# ------------------------------------------------------- property tests
# Randomly generated valid field values: wisdom keys must be injective
# over every field (topology included — the schema-v3 point), and the
# dict round-trips that back the wisdom wire format must be identity.

_KEY_NS = (16, 32, 48)
# The real pipeline plans float inputs and its method names carry the
# "rfft-" prefix — both dimensions must stay injective alongside the
# complex vocabulary (a real plan served to a complex problem, or one
# precision's plan served to another, would execute the wrong transform).
_KEY_DTYPES = ("complex64", "complex128", "float32", "float64")
_KEY_METHODS = ("lb", "fpm", "fpm-pad", "fpm-czt",
                "rfft-lb", "rfft-fpm", "rfft-fpm-pad",
                # The 3-D pencil family and the four-step huge-1-D method
                # share the store with the 2-D vocabulary.
                "pfft3-lb", "pfft1-large")
_KEY_BACKENDS = ("cpu", "tpu")
_KEY_DETAILS = (None, "cafe0123", "70a61b03")
# The 2-D-mesh digest ('+'-joined per-axis terms) must stay injective
# against every 1-D digest and against its own transposed mesh, and the
# multi-host prefix ("<hosts>hx") against every single-host form and
# every other host factorization of the same device count.
_KEY_TOPOS = (None, "2xfft.cpu.k1", "4xfft.cpu.k1-2-4", "4xrows.cpu.k1",
              "4xfft_r+2xfft_c.cpu.k1-2", "2xfft_r+4xfft_c.cpu.k1-2",
              "8xfft.cpu.k1-2-4-8", "2hx8xfft.cpu.k1-2-4-8",
              "4hx8xfft.cpu.k1-2-4-8", "2hx4xfft_r+2xfft_c.cpu.k1-2")


def _key_tuple_from_draws(n_i, dtype_i, p, method_i, backend_i, detail_i,
                          topo_i):
    return (_KEY_NS[n_i], _KEY_DTYPES[dtype_i], p, _KEY_METHODS[method_i],
            _KEY_BACKENDS[backend_i], _KEY_DETAILS[detail_i],
            _KEY_TOPOS[topo_i])


@given(a_n=st.integers(0, 2), a_dtype=st.integers(0, 3), a_p=st.integers(1, 8),
       a_method=st.integers(0, 8), a_backend=st.integers(0, 1),
       a_detail=st.integers(0, 2), a_topo=st.integers(0, 9),
       b_n=st.integers(0, 2), b_dtype=st.integers(0, 3), b_p=st.integers(1, 8),
       b_method=st.integers(0, 8), b_backend=st.integers(0, 1),
       b_detail=st.integers(0, 2), b_topo=st.integers(0, 9))
@settings(max_examples=150, deadline=None)
def test_wisdom_keys_never_collide(a_n, a_dtype, a_p, a_method, a_backend,
                                   a_detail, a_topo, b_n, b_dtype, b_p,
                                   b_method, b_backend, b_detail, b_topo):
    ta = _key_tuple_from_draws(a_n, a_dtype, a_p, a_method, a_backend,
                               a_detail, a_topo)
    tb = _key_tuple_from_draws(b_n, b_dtype, b_p, b_method, b_backend,
                               b_detail, b_topo)
    ka = wisdom_key(n=ta[0], dtype=ta[1], p=ta[2], method=ta[3],
                    backend=ta[4], detail=ta[5], topology=ta[6])
    kb = wisdom_key(n=tb[0], dtype=tb[1], p=tb[2], method=tb[3],
                    backend=tb[4], detail=tb[5], topology=tb[6])
    assert (ka == kb) == (ta == tb), f"{ta} vs {tb}: {ka!r} vs {kb!r}"


@settings(max_examples=100, deadline=None)
@given(radix_i=st.integers(0, 2), fused=st.sampled_from((False, True)),
       batched=st.sampled_from((False, True)),
       pad=st.sampled_from(("none", "fpm", "czt")),
       panels=st.integers(1, 8), real=st.sampled_from((False, True)))
def test_plan_config_roundtrip_is_identity(radix_i, fused, batched, pad,
                                           panels, real):
    if fused:
        pad = "none"  # the one structural constraint on valid configs
    if real and pad == "czt":
        pad = "fpm"  # the real pipeline has no Bluestein form
    cfg = PlanConfig(radix=(None, 2, 4)[radix_i], fused=fused,
                     batched=batched, pad=pad, pipeline_panels=panels,
                     real=real)
    assert PlanConfig.from_dict(cfg.to_dict()) == cfg


_CFG_POOL = (PlanConfig(), PlanConfig(radix=2), PlanConfig(radix=4),
             PlanConfig(batched=False), PlanConfig(pad="fpm"),
             PlanConfig(pad="czt"), PlanConfig(radix=4, fused=True),
             PlanConfig(pipeline_panels=4), PlanConfig(real=True),
             PlanConfig(radix=2, real=True, pad="fpm"))


@settings(max_examples=100, deadline=None)
@given(p=st.integers(1, 4), r1=st.integers(1, 8), r2=st.integers(1, 8),
       r3=st.integers(1, 8), r4=st.integers(1, 8),
       pad_mult=st.sampled_from((1, 2, 4)), slack=st.integers(0, 5),
       cfg0=st.integers(0, len(_CFG_POOL) - 1),
       cfg_step=st.integers(0, len(_CFG_POOL) - 1))
def test_segment_schedule_roundtrip_is_identity(p, r1, r2, r3, r4, pad_mult,
                                                slack, cfg0, cfg_step):
    rows = [r1, r2, r3, r4][:p]
    n = sum(rows) + slack  # schedules may cover fewer rows than N
    pads = np.array([n * pad_mult] * p, dtype=np.int64)
    configs = [_CFG_POOL[(cfg0 + k * cfg_step) % len(_CFG_POOL)]
               for k in range(p)]
    sched = SegmentSchedule.from_parts(n, np.array(rows), pads, configs)
    assert SegmentSchedule.from_dict(sched.to_dict()) == sched
    assert sched.total_rows == sum(rows)
    # the wire format survives a JSON round trip too (wisdom on disk)
    assert SegmentSchedule.from_dict(
        json.loads(json.dumps(sched.to_dict()))) == sched


@settings(max_examples=150, deadline=None)
@given(a_hosts=st.integers(1, 4), a_local=st.integers(1, 4),
       b_hosts=st.integers(1, 4), b_local=st.integers(1, 4))
def test_topology_digest_host_injectivity(a_hosts, a_local,
                                          b_hosts, b_local):
    """The host component keeps every (hosts, local) factorization of a
    device axis distinct — a 2-host x 4-device topology must never be
    served a 1x8 or 4x2 measurement — while single-host digests keep the
    exact pre-multi-host grammar, so v3 stores tuned before the host
    component keep serving single-host lookups."""
    from repro.plan.wisdom import topology_digest

    def digest(hosts, local):
        return topology_digest(None, "fft", devices=hosts * local,
                               platform="cpu", panels=(1,), hosts=hosts)

    da, db = digest(a_hosts, a_local), digest(b_hosts, b_local)
    assert (da == db) == ((a_hosts, a_local) == (b_hosts, b_local)), \
        f"{(a_hosts, a_local)} vs {(b_hosts, b_local)}: {da!r} vs {db!r}"
    if a_hosts == 1:
        # hosts=1 is the flat axis: the digest is byte-identical to the
        # host-agnostic form old stores were recorded under.
        assert da == topology_digest(None, "fft", devices=a_local,
                                     platform="cpu", panels=(1,))
        assert "hx" not in da
    else:
        assert da.startswith(f"{a_hosts}hx")
