"""Planner subsystem: PlanConfig, cost model, tuner, wisdom store, and the
plan_pfft tune/wisdom lifecycle (including equivalence with the
pre-refactor flag paths and batched execute)."""

import json
import os
import shutil

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import FPMSet, PlanConfig, SpeedFunction, plan_pfft
from repro.core.pfft import _pfft_limb, segment_row_ffts
from repro.core.partition import lb_partition
from repro.plan import (WISDOM_VERSION, CostParams, candidate_configs,
                        czt_fft_lengths, estimate_cost, fpm_pad_lengths,
                        load_wisdom, lookup_wisdom, record_wisdom,
                        tune_config, wisdom_key)
from repro.plan.cost import V5E_KIND
from repro.core.padding import determine_pad_length, smooth_candidates
import plan_cases


def fpms_for(n, p=3, hetero=True):
    xs = np.array(sorted({1, max(n // 4, 1), max(n // 2, 1), n}))
    ys = np.array(sorted({n // 2, n, n + 64, 2 * n}))
    sp = np.outer(xs, np.log2(np.maximum(ys, 2))) + 3.0
    fns = [SpeedFunction(xs, ys, sp * (i + 1 if hetero else 1), name=f"P{i}")
           for i in range(p)]
    return FPMSet(fns)


def random_signal(n, seed=0, dtype=np.complex64):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.standard_normal((n, n))
                        + 1j * rng.standard_normal((n, n))).astype(dtype))


# ---------------------------------------------------------------- PlanConfig

def test_config_validation():
    with pytest.raises(ValueError):
        PlanConfig(radix=3)
    with pytest.raises(ValueError):
        PlanConfig(pad="crop")  # dist vocabulary, not a strategy name
    with pytest.raises(ValueError):
        PlanConfig(pipeline_panels=0)
    with pytest.raises(ValueError):
        PlanConfig(fused=True, pad="fpm")  # fused has no per-segment pads


def test_config_dict_roundtrip_and_unknown_fields():
    cfg = PlanConfig(radix=4, fused=True, pipeline_panels=2)
    assert PlanConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError):
        PlanConfig.from_dict({"radix": 4, "warp_drive": True})


def test_config_flag_bridge_and_backend():
    cfg = PlanConfig.from_flags(use_stockham=True, batched=False)
    assert cfg.radix == 2 and cfg.use_stockham and not cfg.batched
    assert cfg.fft_backend == "stockham"
    assert PlanConfig().fft_backend == "xla"
    assert PlanConfig(radix=4).fft_backend == "pallas"
    assert PlanConfig(pad="fpm").dist_padded == "crop"
    assert PlanConfig(pad="czt").dist_padded == "czt"


# ------------------------------------------------------------- pads helpers

def test_fpm_pad_lengths_matches_per_processor_rule():
    n = 32
    fpms = fpms_for(n)
    d = lb_partition(n, fpms.p).d
    pads = fpm_pad_lengths(fpms, d, n)
    expect = [determine_pad_length(fpms[i], int(d[i]), n)
              for i in range(fpms.p)]
    np.testing.assert_array_equal(pads, expect)


def test_czt_fft_lengths_matches_argmin_loop():
    n = 32
    fpms = fpms_for(n)
    d = lb_partition(n, fpms.p).d
    lens = czt_fft_lengths(fpms, d, n)
    cands = smooth_candidates(2 * n - 1, limit_ratio=2.0)
    for i in range(fpms.p):
        times = [fpms[i].time_at(int(d[i]), int(c)) for c in cands]
        assert lens[i] == int(cands[int(np.argmin(times))])
    assert np.all(lens >= 2 * n - 1)


# ---------------------------------------------------------------- cost model

def test_cost_batched_beats_looped_on_dispatch_overhead():
    n = 64
    d = np.array([16, 16, 16, 16])
    params = CostParams.for_backend("cpu")
    c_b = estimate_cost(PlanConfig(batched=True), n=n, d=d, params=params)
    c_l = estimate_cost(PlanConfig(batched=False), n=n, d=d, params=params)
    assert c_b < c_l  # 1 dispatch/phase vs 4


def test_cost_cpu_prefers_library_accel_prefers_kernels():
    n, d = 256, np.array([64] * 4)
    cpu = CostParams.for_backend("cpu")
    tpu = CostParams.for_backend("tpu", device_kind=V5E_KIND)
    lib = PlanConfig()
    fused = PlanConfig(radix=4, fused=True)
    assert estimate_cost(lib, n=n, d=d, params=cpu) < \
        estimate_cost(fused, n=n, d=d, params=cpu)  # interpret-mode penalty
    assert estimate_cost(fused, n=n, d=d, params=tpu) < \
        estimate_cost(lib, n=n, d=d, params=tpu)  # no HBM round trip


def test_cost_params_keyed_by_device_kind():
    from repro.plan.cost import UnknownDeviceKind
    v5e = CostParams.for_backend("tpu", device_kind=V5E_KIND)
    assert v5e.hbm_bytes_per_s == pytest.approx(819e9)
    with pytest.raises(UnknownDeviceKind, match="TPU v9"):
        CostParams.for_backend("tpu", device_kind="TPU v9")


@pytest.mark.parametrize("n,fused_ok,real_fused_ok",
                         [(4096, True, True), (8192, True, False),
                          (16384, False, False)])
def test_tpu_candidates_drop_kernels_that_cannot_compile(
        monkeypatch, n, fused_ok, real_fused_ok):
    """On the TPU backend the fused configs whose kernels outgrow VMEM
    are not offered, and ``kernel_exclusions`` names why."""
    from repro.plan.tune import _real_candidates, kernel_exclusions
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cands = candidate_configs(n)
    assert any(c.fused for c in cands) == fused_ok
    assert any(c.fused for c in _real_candidates(cands, n)) == real_fused_ok
    excl = kernel_exclusions(n)
    assert ("fused" in excl) != fused_ok
    assert ("fused-real" in excl) != real_fused_ok
    assert all("VMEM" in why for why in excl.values())
    monkeypatch.undo()
    assert kernel_exclusions(n) == {}   # interpret mode runs every length
    assert any(c.fused for c in candidate_configs(n))


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    is the fixed ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.use_compile_cache()
        assert path == str(cc.REPO_CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cost_uses_fpm_times():
    n, d = 64, np.array([32, 32])
    slow = FPMSet([SpeedFunction([1, 32], [32, 64, 128],
                                 np.full((2, 3), s), name="P")
                   for s in (1e6, 1e6)])
    fast = FPMSet([SpeedFunction([1, 32], [32, 64, 128],
                                 np.full((2, 3), s), name="P")
                   for s in (1e9, 1e9)])
    cfg = PlanConfig()
    assert estimate_cost(cfg, n=n, d=d, fpms=slow) > \
        estimate_cost(cfg, n=n, d=d, fpms=fast)


# -------------------------------------------------------------------- tuner

def test_candidate_space_constraints():
    # non-pow2: no kernel radices, no fused
    cands = candidate_configs(48, d=np.array([24, 24]))
    assert all(c.radix is None and not c.fused for c in cands)
    # pow2 with pads strategy: fused excluded, pad carried through
    cands = candidate_configs(64, pad="fpm", d=np.array([32, 32]))
    assert all(not c.fused and c.pad == "fpm" for c in cands)
    # single-segment partitions don't enumerate batched=False
    cands = candidate_configs(64, d=np.array([64]))
    assert all(c.batched for c in cands)


def test_estimate_equals_bruteforce_cheapest_on_synthetic_fpms():
    """The planner's pick is exactly argmin of the cost model over the
    candidate space (satellite acceptance)."""
    n = 64
    fpms = fpms_for(n)
    d = lb_partition(n, fpms.p).d
    params = CostParams.for_backend("cpu")
    chosen, info = tune_config(n, d=d, fpms=fpms, mode="estimate",
                               params=params)
    brute = min(candidate_configs(n, d=d),
                key=lambda c: estimate_cost(c, n=n, d=d, fpms=fpms,
                                            params=params))
    assert chosen == brute
    ranked_costs = [c for _, c in info["ranked"]]
    assert ranked_costs == sorted(ranked_costs)


def test_measure_mode_times_finalists():
    n = 32
    d = lb_partition(n, 2).d
    chosen, info = tune_config(n, d=d, mode="measure", top_k=2, reps=1)
    assert len(info["measured"]) == 2
    assert chosen in candidate_configs(n, d=d)
    assert info["time_s"] > 0


@pytest.mark.parametrize("tuner", ["tune_config", "tune_schedule",
                                   "tune_dist_config", "tune_dist_schedule",
                                   "tune_pfft3", "tune_pfft1_large",
                                   "tune_rfft", "tune_rfft_dist"])
def test_tune_rejects_bad_mode(tuner):
    import repro.plan.tune as tune_mod
    on_mesh = tuner in ("tune_dist_config", "tune_dist_schedule",
                        "tune_rfft_dist")
    args = (jax.make_mesh((1,), ("fft",)),) if on_mesh else ()
    with pytest.raises(ValueError, match="mode must be"):
        getattr(tune_mod, tuner)(32, *args, mode="exhaustive")


def test_measure_mode_without_partition():
    """d=None means one whole-matrix segment in measure mode too (it would
    otherwise crash deep inside the limb)."""
    chosen, info = tune_config(16, mode="measure", top_k=1, reps=1)
    assert chosen in candidate_configs(16)
    assert info["time_s"] > 0


# ------------------------------------------------- one loop, every family

@pytest.fixture(scope="module")
def tuner_runs(dist_subprocess, tmp_path_factory):
    """The golden and policy results of this tree (``plan_cases``), from
    one run on eight forced CPU devices."""
    out = tmp_path_factory.mktemp("tuners") / "runs.json"
    dist_subprocess(f"import sys; sys.path.insert(0, {plan_cases.HERE!r})\n"
                    f"import plan_cases; plan_cases.run_all({str(out)!r})\n"
                    "print('TUNERS_OK')", devices=8, sentinel="TUNERS_OK")
    with open(out) as f:
        return json.load(f)


# What the benchmark cells run on the chip (PERF.md): (radix, fused,
# pipeline_panels) of the one config the schedule carries.
CELL_PICKS = {"cell-1chip-n8192": (4, True, 1),
              "cell-4chip-n32768": (4, False, 8)}


@pytest.mark.parametrize("case", plan_cases.golden_names())
def test_tuner_choices_match_golden(tuner_runs, case):
    """Under the v5e constants every family tuner picks what it picked
    before the tuners shared one loop, with the same ``info`` (wisdom
    stores and calibration read it)."""
    with open(plan_cases.GOLDEN) as f:
        want = json.load(f)[case]
    got = tuner_runs["golden"][case]
    assert got["winner"] == want["winner"]
    assert got["info"] == want["info"]
    if case in CELL_PICKS:
        (entry,) = {json.dumps(e["config"], sort_keys=True)
                    for e in got["winner"]["entries"]}
        cfg = json.loads(entry)
        assert (cfg["radix"], cfg["fused"],
                cfg["pipeline_panels"]) == CELL_PICKS[case]


@pytest.mark.parametrize("family", list(plan_cases.POLICY))
def test_measure_policy(tuner_runs, family):
    """Measure mode under a fake timer: the finalists are distinct
    programs, at most ``top_k`` (a real pot adds the best of the other
    family); the fastest wins; the comm sample is end to end minus the
    local phases, clamped at 0; a failing measurement raises, or with
    retries falls back to the estimate winner."""
    runs = tuner_runs["policy"]
    facts = runs["clamped"][family]
    top_k = plan_cases.POLICY[family]
    programs = [json.dumps(m[:-1], sort_keys=True) for m in facts["measured"]]
    assert len(set(programs)) == len(programs)
    if family in plan_cases.REAL_POTS:
        assert {m[0]["real"] for m in facts["measured"]} == {True, False}
        assert len(programs) == top_k + 1
    else:
        assert len(programs) <= top_k
    if family == "config":
        # Every padded length is non-pow2: the radices are one program.
        assert len(programs) == 2

    times = [m[-1] for m in facts["measured"]]
    assert facts["time_s"] == min(times)
    winner = facts["winner"] if isinstance(facts["winner"], list) \
        else [facts["winner"]]
    assert [m[:-1] for m in facts["measured"]
            if m[-1] == min(times)][0] == winner

    phases = plan_cases.COMM_PHASES.get(family)
    if phases is not None:
        local = "local_pass_s" if "pfft3" in family else "local_phase_s"
        assert facts["stats"]["comm_time_meas_s"] == 0.0   # clamped
        small = runs["small"][family]
        assert small["stats"]["comm_time_meas_s"] == pytest.approx(
            small["time_s"] - phases * small["stats"][local])
        assert small["stats"]["comm_time_meas_s"] > 0

    assert facts["raised"] == "device lost"
    if "fallback" in facts:
        assert facts["fallback"].startswith(
            "measurement failed after 1 retries")
        assert facts["fallback_winner"] == facts["estimate"]


@pytest.mark.parametrize("plan", ["lb", "rfft-lb", "pfft3", "pfft1-large"])
def test_wisdom_written_before_the_fold_is_served(tmp_path, monkeypatch,
                                                  plan):
    """A store the planner wrote before the tuners shared one loop still
    serves every family, with no measurement."""
    import repro.plan.tune as tune_mod
    from repro.core.api import plan_pfft1_large, plan_pfft3

    def no_measure(*a, **k):
        raise AssertionError("re-measured a wisdom-served plan")
    monkeypatch.setattr(tune_mod, "_time_programs", no_measure)
    path = str(tmp_path / "wisdom.json")
    shutil.copy(os.path.join(plan_cases.HERE, "plan_wisdom_parent.json"),
                path)
    built = {"lb": lambda: plan_pfft(32, p=2, method="lb", tune="measure",
                                     wisdom=path),
             "rfft-lb": lambda: plan_pfft(32, p=2, method="rfft-lb",
                                          tune="measure", wisdom=path,
                                          dtype=np.float32),
             "pfft3": lambda: plan_pfft3(8, tune="measure", wisdom=path),
             "pfft1-large": lambda: plan_pfft1_large(360, tune="measure",
                                                     wisdom=path)}[plan]()
    assert built.tuning["source"] == "wisdom"


# ------------------------------------------------------------------- wisdom

def test_wisdom_miss_hit_and_overwrite(tmp_path):
    path = str(tmp_path / "wisdom.json")
    key = wisdom_key(n=64, dtype="complex64", p=4, method="lb", backend="cpu")
    assert lookup_wisdom(path, key) is None  # missing file -> miss
    cfg = PlanConfig(radix=4, fused=True)
    record_wisdom(path, key, cfg, mode="measure", time_s=1e-3)
    got, entry = lookup_wisdom(path, key)
    assert got == cfg and entry["mode"] == "measure"
    assert lookup_wisdom(path, key + "|x") is None  # other key -> miss
    record_wisdom(path, key, PlanConfig(), mode="estimate")
    got2, entry2 = lookup_wisdom(path, key)
    assert got2 == PlanConfig() and "time_s" not in entry2


def test_wisdom_version_mismatch_and_corruption_are_misses(tmp_path):
    path = str(tmp_path / "wisdom.json")
    key = wisdom_key(n=8, dtype="complex64", p=2, method="lb", backend="cpu")
    record_wisdom(path, key, PlanConfig(), mode="measure")
    doc = json.load(open(path))
    doc["version"] = WISDOM_VERSION + 1
    json.dump(doc, open(path, "w"))
    assert load_wisdom(path) == {} and lookup_wisdom(path, key) is None
    with open(path, "w") as fh:
        fh.write("{ not json")
    assert load_wisdom(path) == {}
    # recording over a corrupt store rewrites it cleanly
    record_wisdom(path, key, PlanConfig(), mode="measure")
    assert lookup_wisdom(path, key) is not None


def test_wisdom_hit_applies_even_with_tune_off(tmp_path):
    """Passing wisdom=path IS the request to use stored plans (FFTW reads
    wisdom regardless of planner rigor)."""
    path = str(tmp_path / "wisdom.json")
    plan_pfft(32, p=2, method="lb", tune="measure", wisdom=path)
    served = plan_pfft(32, p=2, method="lb", wisdom=path)  # tune defaults off
    assert served.tuning["source"] == "wisdom"
    cold = plan_pfft(32, p=2, method="lb")
    assert cold.tuning["source"] == "off"


def test_wisdom_key_digests_fpm_partition(tmp_path):
    """Different FPMSets give different partitions; one model's measured
    config must not be served to another model's plan."""
    path = str(tmp_path / "wisdom.json")
    n = 32
    hetero = fpms_for(n, hetero=True)
    homo = fpms_for(n, hetero=False)
    p1 = plan_pfft(n, fpms=hetero, method="fpm", tune="measure", wisdom=path)
    assert p1.tuning["source"] == "measure"
    p2 = plan_pfft(n, fpms=homo, method="fpm", tune="measure", wisdom=path)
    if np.array_equal(p1.d, p2.d):  # partitions happened to coincide
        assert p2.tuning["wisdom_key"] == p1.tuning["wisdom_key"]
    else:
        assert p2.tuning["wisdom_key"] != p1.tuning["wisdom_key"]
        assert p2.tuning["source"] == "measure"  # miss, re-measured
    # same model again: hit
    p3 = plan_pfft(n, fpms=hetero, method="fpm", tune="measure", wisdom=path)
    assert p3.tuning["source"] == "wisdom"


def test_plan_pfft_wisdom_lifecycle(tmp_path):
    """measure persists the choice; a later plan (fresh-process analogue)
    is served from wisdom without re-measuring."""
    path = str(tmp_path / "wisdom.json")
    n = 32
    p1 = plan_pfft(n, p=2, method="lb", tune="measure", wisdom=path)
    assert p1.tuning["source"] == "measure" and "measured" in p1.tuning
    p2 = plan_pfft(n, p=2, method="lb", tune="measure", wisdom=path)
    assert p2.tuning["source"] == "wisdom"
    assert "measured" not in p2.tuning  # no re-measure
    assert p2.config == p1.config
    m = random_signal(n)
    np.testing.assert_allclose(np.asarray(p2.execute(m)),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)


# --------------------------------------------------- plan_pfft tune plumbing

def test_plan_pfft_estimate_selects_without_flags():
    n = 64
    fpms = fpms_for(n)
    for method in ("fpm", "fpm-pad"):
        plan = plan_pfft(n, fpms=fpms, method=method, tune="estimate")
        assert plan.tuning["source"] == "estimate"
        assert plan.config in candidate_configs(
            n, pad=plan.config.pad, d=plan.d)
        m = random_signal(n)
        out = plan.execute(m)
        assert out.shape == (n, n)


def test_plan_pfft_explicit_config_skips_tuning():
    cfg = PlanConfig(radix=2, batched=False)
    plan = plan_pfft(32, p=2, method="lb", tune="estimate", config=cfg)
    assert plan.config == cfg and plan.tuning["source"] == "explicit"


def test_plan_pfft_rejects_bad_tune_mode():
    with pytest.raises(ValueError):
        plan_pfft(32, p=2, method="lb", tune="turbo")


# --------------------------------------- numerical identity with flag paths

@pytest.mark.parametrize("flags", [
    dict(use_stockham=True),
    dict(fused=True),
])
def test_config_paths_match_legacy_flag_paths_fp64(flags):
    """Planned execution is numerically identical (fp64 reference) to the
    pre-refactor flag-equivalent path (acceptance criterion)."""
    n = 32
    d = lb_partition(n, 3).d
    m64 = random_signal(n, seed=3, dtype=np.complex128)
    cfg = PlanConfig.from_flags(**flags)
    via_config = _pfft_limb(m64, d, config=cfg)
    with pytest.warns(DeprecationWarning):
        via_flags = _pfft_limb(m64, d, **flags)
    np.testing.assert_allclose(np.asarray(via_config), np.asarray(via_flags),
                               rtol=1e-12, atol=1e-9)
    # Oracle check at the precision actually in effect (the tier-1 driver
    # runs without JAX_ENABLE_X64, demoting complex128 to complex64).
    fp64 = via_config.dtype == jnp.complex128
    np.testing.assert_allclose(np.asarray(via_config),
                               np.asarray(jnp.fft.fft2(m64)),
                               rtol=1e-6 if fp64 else 2e-3,
                               atol=1e-6 if fp64 else 2e-2)


def test_segment_config_matches_legacy_batched_flag_fp64():
    n = 32
    d = lb_partition(n, 3).d
    m64 = random_signal(n, seed=4, dtype=np.complex128)
    pads = np.array([n, 2 * n, n], dtype=np.int64)
    for batched in (True, False):
        via_config = segment_row_ffts(
            m64, d, pad_lengths=pads, config=PlanConfig(batched=batched))
        with pytest.warns(DeprecationWarning):
            via_flag = segment_row_ffts(m64, d, pad_lengths=pads,
                                        batched=batched)
        np.testing.assert_allclose(np.asarray(via_config),
                                   np.asarray(via_flag),
                                   rtol=1e-12, atol=1e-9)


def test_planned_fpm_pad_matches_legacy_flag_path():
    n = 32
    fpms = fpms_for(n)
    m = random_signal(n, seed=5, dtype=np.complex128)
    plan = plan_pfft(n, fpms=fpms, method="fpm-pad", tune="estimate")
    with pytest.warns(DeprecationWarning):
        legacy = plan_pfft(n, fpms=fpms, method="fpm-pad",
                           use_stockham=plan.config.use_stockham)
    np.testing.assert_allclose(np.asarray(plan.execute(m)),
                               np.asarray(legacy.execute(m)),
                               rtol=1e-10, atol=1e-8)


# ------------------------------------------------------------ batched execute

def test_plan_execute_accepts_leading_batch_dims():
    n = 32
    plan = plan_pfft(n, p=2, method="lb")
    rng = np.random.default_rng(9)
    batch = jnp.asarray((rng.standard_normal((2, 3, n, n))
                         + 1j * rng.standard_normal((2, 3, n, n))
                         ).astype(np.complex64))
    out = plan.execute(batch)
    assert out.shape == (2, 3, n, n)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft2(batch)), atol=2e-2)
    # The vmapped wrapper is built once per batch rank and cached.
    plan.execute(batch)
    plan.execute(batch[0])
    assert sorted(plan._batched_fns) == [3, 4]


def test_plan_execute_shape_error_names_planned_size():
    n = 32
    plan = plan_pfft(n, p=2, method="lb")
    with pytest.raises(ValueError, match=r"\(32, 32\)"):
        plan.execute(jnp.ones((n + 1, n + 1), jnp.complex64))
    with pytest.raises(ValueError, match=r"\(32, 32\)"):
        plan.execute(jnp.ones((n,), jnp.complex64))


def test_plan_execute_czt_accepts_batch():
    """Batched czt execute used to be rejected with a named error; since
    the schedule executor took over the per-segment slicing it vmaps
    like every other method (satellite acceptance)."""
    n = 16
    plan = plan_pfft(n, fpms=fpms_for(n), method="fpm-czt")
    m = random_signal(n)
    np.testing.assert_allclose(np.asarray(plan.execute(m)),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)
    batch = jnp.stack([m, 2.0 * m])
    out = plan.execute(batch)
    assert out.shape == (2, n, n)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft2(batch)), atol=4e-2)


# -------------------------------------------------------------- shim hygiene

def test_fused_shim_ignored_on_padded_methods_like_pre_refactor():
    """The pre-refactor API silently ignored fused= for fpm-pad/fpm-czt
    (pad semantics are per-processor); the deprecation shim must not turn
    that into a crash."""
    n = 16
    fpms = fpms_for(n)
    m = random_signal(n)
    for method in ("fpm-pad", "fpm-czt"):
        with pytest.warns(DeprecationWarning):
            plan = plan_pfft(n, fpms=fpms, method=method, fused=True)
        assert not plan.config.fused
        assert plan.execute(m).shape == (n, n)


def test_measure_mode_respects_plan_dtype(tmp_path):
    """plan_pfft's dtype reaches the measurement (and the wisdom key), so
    a complex128 plan is not silently tuned on complex64 timings."""
    path = str(tmp_path / "wisdom.json")
    plan = plan_pfft(16, p=2, method="lb", tune="measure", wisdom=path,
                     dtype="complex128")
    assert "dtype=complex128" in plan.tuning["wisdom_key"]
    assert plan.tuning["source"] == "measure"
    # a complex64 plan misses the complex128 entry
    plan2 = plan_pfft(16, p=2, method="lb", tune="measure", wisdom=path)
    assert plan2.tuning["source"] == "measure"


def test_deprecated_shims_warn_and_conflict():
    n = 16
    m = random_signal(n)
    d = lb_partition(n, 2).d
    with pytest.warns(DeprecationWarning):
        segment_row_ffts(m, d, batched=False)
    with pytest.warns(DeprecationWarning):
        plan_pfft(n, p=2, method="lb", fused=False)
    with pytest.raises(ValueError):
        segment_row_ffts(m, d, config=PlanConfig(), batched=True)
    with pytest.raises(ValueError):
        plan_pfft(n, p=2, method="lb", config=PlanConfig(), fused=True)


def test_public_wrappers_share_the_shim_contract():
    """pfft_lb/pfft_fpm/pfft_fpm_pad warn on legacy flags and reject
    config + flags conflicts exactly like the inner layers."""
    from repro.core import pfft_fpm, pfft_fpm_pad, pfft_lb
    n = 16
    m = random_signal(n)
    fpms = fpms_for(n)
    with pytest.warns(DeprecationWarning):
        pfft_lb(m, 2, use_stockham=True)
    with pytest.warns(DeprecationWarning):
        pfft_fpm(m, fpms, fused=True)
    with pytest.warns(DeprecationWarning):
        pfft_fpm_pad(m, fpms, use_stockham=True)
    with pytest.raises(ValueError):
        pfft_lb(m, 2, use_stockham=True, config=PlanConfig(radix=4))
    with pytest.raises(ValueError):
        pfft_fpm_pad(m, fpms, use_stockham=False, config=PlanConfig())
    # config-only calls stay silent
    out = pfft_lb(m, 2, config=PlanConfig(batched=False))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.fft.fft2(m)), atol=2e-2)


def test_pfft2_distributed_config_and_shims():
    from repro.core.pfft_dist import pfft2_distributed
    mesh = jax.make_mesh((1,), ("fft",))
    n = 16
    m = random_signal(n)
    out = pfft2_distributed(m, mesh, "fft",
                            config=PlanConfig(pipeline_panels=4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.fft.fft2(m)),
                               atol=2e-2)
    with pytest.warns(DeprecationWarning):
        pfft2_distributed(m, mesh, "fft", pipeline_panels=2)
    with pytest.raises(ValueError):
        pfft2_distributed(m, mesh, "fft", config=PlanConfig(),
                          pipeline_panels=2)
    with pytest.raises(ValueError):  # config.pad conflicts with padded=
        pfft2_distributed(m, mesh, "fft", config=PlanConfig(), padded="czt")
