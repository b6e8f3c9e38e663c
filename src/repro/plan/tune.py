"""Estimate/measure tuner — FFTW's planner loop over ``PlanConfig`` space.

Every family tuner (``tune_config``, ``tune_schedule``,
``tune_dist_config``, ``tune_dist_schedule``, ``tune_pfft3``,
``tune_pfft1_large``, ``tune_rfft``, ``tune_rfft_dist``) builds a *pot* —
its candidates, a price for each, the key of the program each runs and a
way to time a list of them — and hands it to one loop,
``_rank_and_race``:

* ``mode="estimate"`` — cost model only (``plan.cost``), no device work.
  FFTW's ESTIMATE: instant, right whenever the model's ranking is.
* ``mode="measure"`` — time the ``top_k`` cheapest distinct programs on
  device (``_time_programs``: interleaved shuffled rounds, per-program
  min) and take the winner.  FFTW's MEASURE: pays seconds once so every
  later execute is served by the best plan.

The caller (``plan_pfft`` / the microbenchmark) persists the result via
``plan.wisdom`` so measurement happens once per (n, dtype, p, method,
backend) per machine.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, NamedTuple, Sequence

import numpy as np

from repro.core.fpm import FPMSet
from repro.plan.config import PlanConfig
from repro.plan.cost import (CostParams, _compute_multiplier, _is_pow2,
                             _segment_work, comm_phase_time, dist_comm_bytes,
                             dist_comm_time, estimate_cost,
                             estimate_grouped_cost, estimate_pfft3_cost,
                             estimate_schedule_cost, halfspec_cols,
                             pfft3_comm_bytes)
from repro.plan.schedule import SegmentSchedule

__all__ = ["candidate_configs", "kernel_exclusions",
           "segment_candidate_configs",
           "measure_configs", "measure_dist_configs", "tune_config",
           "tune_schedule", "tune_dist_config", "tune_dist_schedule",
           "grouped_dist_schedule", "dist_panel_space",
           "measure_rfft_configs", "measure_rfft_dist_configs",
           "tune_rfft", "tune_rfft_dist",
           "pfft3_panel_space", "measure_pfft3_configs", "tune_pfft3",
           "tune_pfft1_large"]


def _measure_with_retry(thunk, retries: int, failures: dict, key: str,
                        prefix: str = "", base_s: float = 0.05):
    """Run a measurement thunk, retrying transient failures with
    exponential backoff.

    Device measurement is the tuner's only fallible step (a transiently
    wedged device, an allocator hiccup mid-chaos).  With ``retries > 0``
    a failure that outlasts the retries is recorded as
    ``failures[key] = prefix + repr(err)`` and None is returned, so a
    flaky measurement degrades a *plan choice* (the tuner loop falls back
    to the estimate ranking, or loses one comm sample), never the caller.
    ``retries=0`` (the default everywhere) keeps the historical
    raise-through behavior.
    """
    delay = float(base_s)
    for attempt in range(int(retries) + 1):
        try:
            return thunk()
        except Exception as err:
            if retries <= 0:
                raise
            if attempt >= retries:
                failures[key] = prefix + repr(err)
                return None
            time.sleep(delay)
            delay *= 2.0


def kernel_exclusions(n: int) -> dict[str, str]:
    """Kernel families the chip cannot run at length ``n``, with why.

    Keys are ``"fused"`` (the fused complex phase) and ``"fused-real"``
    (the fused real phase, which also runs a fused complex phase).  Off
    the TPU the kernels run in interpret mode at every length, so the
    map is empty there.
    """
    import jax
    if jax.default_backend() != "tpu" or not _is_pow2(n):
        return {}
    from repro.kernels.fft.ops import tpu_unsupported
    out = {}
    for family, kinds in (("fused", ("fused",)),
                          ("fused-real", ("rfused", "fused"))):
        reasons = [r for r in (tpu_unsupported(k, n) for k in kinds) if r]
        if reasons:
            out[family] = "; ".join(reasons)
    return out


def _drop_excluded(cands: Sequence[PlanConfig], n: int) -> list[PlanConfig]:
    """``cands`` minus the fused configs ``kernel_exclusions`` names."""
    excl = kernel_exclusions(n)
    return [c for c in cands
            if not (c.fused and ("fused-real" if c.real else "fused") in excl)]


def candidate_configs(n: int, *, pad: str = "none", d=None,
                      panels: Sequence[int] = (1,)) -> list[PlanConfig]:
    """Valid ``PlanConfig`` candidates for an n x n problem.

    ``pad`` is fixed by the method (it is semantics, not a tunable);
    ``fused`` requires a power-of-two N and no per-segment padding;
    the kernel radices require a power-of-two N (and the czt path runs
    library FFTs inside ``czt_dft`` whatever the radix says, so czt
    enumerates only the dispatch structure); ``batched`` only matters
    when the partition has more than one non-empty segment.
    """
    radices: list[int | None] = [None]
    if pad != "czt" and _is_pow2(n):
        radices += [2, 4]
    multi_segment = d is None or int((np.asarray(d) > 0).sum()) > 1
    batch_opts = (True, False) if multi_segment else (True,)

    out: list[PlanConfig] = []
    for k in panels:
        for radix in radices:
            for batched in batch_opts:
                out.append(PlanConfig(radix=radix, batched=batched, pad=pad,
                                      pipeline_panels=k))
        if pad == "none" and _is_pow2(n):
            # Fused collapses each phase to one dispatch; segmentation (and
            # therefore batched) is moot, and the kernel is radix-4.
            out.append(PlanConfig(radix=4, fused=True, pipeline_panels=k))
    return _drop_excluded(out, n)


def segment_candidate_configs(length: int, *, pad: str = "none"
                              ) -> list[PlanConfig]:
    """Per-segment variants for one effective FFT length.

    A segment entry tunes only what is segment-local: the row-FFT backend
    (``radix``).  Phase-global knobs stay out of the per-segment space —
    ``fused`` collapses the whole matrix into one dispatch, ``batched``
    and ``pipeline_panels`` shape the phase, and they are all covered by
    the homogeneous envelope ``tune_schedule`` compares against.  The czt
    path has a single per-segment shape (``czt_dft`` at the entry's
    length), so it contributes exactly one candidate.
    """
    if pad == "czt":
        return [PlanConfig(pad="czt")]
    radices: list[int | None] = [None]
    if _is_pow2(length):
        radices += [2, 4]
    return [PlanConfig(radix=r, pad=pad) for r in radices]


def _length_backend(cfg: PlanConfig, length: int) -> tuple[str, int | None]:
    """Effective (backend, radix) for one length: kernel backends fall
    back to XLA on non-pow2 lengths (``fft_rows``); the one home of that
    rule for behavior keys and Pareto dedup."""
    kw = cfg.row_fft_kwargs()
    if kw["backend"] != "xla" and not _is_pow2(length):
        return "xla", None
    return kw["backend"], cfg.radix if kw["backend"] == "pallas" else None


def _behavior_key(cfg: PlanConfig, n: int, d, pad_lengths) -> tuple:
    """What program actually runs under ``cfg`` for this problem.

    Kernel backends fall back to XLA for non-power-of-two effective
    lengths (``fft_rows``), so e.g. radix=None/2/4 are one and the same
    program when every padded length is non-pow2 — measuring more than
    one of them wastes the measure budget on rubber-stamping.
    """
    lengths = sorted({length for _, length in _segment_work(n, d, pad_lengths)})
    if cfg.fused:
        return ("fused", cfg.real, cfg.exchange, tuple(lengths))
    per_len = [(length,) + _length_backend(cfg, length) for length in lengths]
    return (cfg.batched, cfg.pipeline_panels, cfg.real, cfg.exchange,
            tuple(per_len))


def _segment_time(cfg: PlanConfig, fpms: FPMSet | None, i: int, rows: int,
                  length: int, params: CostParams) -> float:
    """Estimated seconds of processor ``i``'s ``rows`` rows at ``length``
    under ``cfg``: its FPM's ``time_at`` (or the nominal flop rate) times
    the backend multiplier."""
    if fpms is not None:
        t = fpms[i].time_at(rows, length)
    else:
        from repro.core.fpm import fft_flops
        t = float(fft_flops(rows, length)) / params.nominal_flops
    return t * _compute_multiplier(cfg, length, params)


# ------------------------------------------------------------ the one loop

def _time_programs(build, items, shape, dtype, rounds: int, *,
                   mesh=None, spec=None) -> dict:
    """{item: best seconds} of ``jax.jit(build(item))`` on one input.

    The measure harness of every tuner.  The input is ``default_rng(0)``
    normal draws of ``shape`` (complex when ``dtype`` is), laid out over
    ``mesh`` by the partition ``spec`` first when a mesh is given, so
    placement is not billed to whichever item runs first.  Every program
    is compiled before any is timed.  Then ``rounds`` episodes visit the
    items in a *shuffled* order, each timed run preceded by an untimed
    same-item warm run, and each item keeps its best: close variants
    (batched vs looped) differ by far less than the episode-to-episode
    jitter, and a fixed visiting order would tax each item by whatever
    allocator/cache state its fixed neighbour leaves behind (one warm run
    does not fully neutralise an interpret-mode Pallas predecessor).
    Shuffling varies the predecessor; min keeps each item's best-context
    episode.
    """
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = rng.standard_normal(shape)
    if np.dtype(dtype).kind == "c":
        x = x + 1j * rng.standard_normal(shape)
    x = jnp.asarray(x.astype(dtype))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jax.device_put(x, NamedSharding(mesh, P(*spec)))
    fns = []
    for item in items:
        fn = jax.jit(build(item))
        jax.block_until_ready(fn(x))  # compile
        fns.append((item, fn))

    order = np.random.default_rng(1)
    times = {item: float("inf") for item, _ in fns}
    for _ in range(max(rounds, 1)):
        for i in order.permutation(len(fns)):
            item, fn = fns[int(i)]
            jax.block_until_ready(fn(x))  # warm: evict neighbour's state
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            times[item] = min(times[item], time.perf_counter() - t0)
    return times


class _CommProbe(NamedTuple):
    """How a mesh tuner turns its winner's end-to-end time into a comm
    sample: ``local_s(winner)`` times the local program(s) alone, and
    ``stats["comm_time_meas_s"] = total − phases·local_s``, clamped at 0
    (pipelined panels can hide comm below the subtraction's noise)."""
    stats: dict           # the tuner's topology facts, info["dist"] etc.
    local: str            # the key local_s is recorded under
    phases: float         # local programs in one transform
    local_s: Callable     # winner -> seconds


def _shown(cfg: PlanConfig, t: float) -> tuple:
    return (cfg.to_dict(), t)


def _rank_and_race(rec: dict, cands, price, *, mode: str,
                   params: CostParams | None = None, top_k: int = 3,
                   key=lambda c: c, measure=None, retries: int = 0,
                   family=None, probe: _CommProbe | None = None,
                   shown=_shown):
    """The tuner loop every family shares; returns the winning candidate.

    Ranks the hashable ``cands`` by ``price(c, params)`` (``params``
    defaults to the backend's constants; a stable sort: candidate order
    breaks ties) into ``rec["ranked"]`` and, in estimate mode,
    returns the cheapest.  In measure mode the finalists are the
    ``top_k`` cheapest candidates of distinct ``key`` (one per *program*:
    ranking ties are often configs whose differences runtime fallbacks
    erase), plus the cheapest of each ``family`` value not yet among them;
    ``measure(finalists) -> {candidate: seconds}`` times them and the
    fastest wins (``rec["measured"]``, ``rec["time_s"]``).  ``measure=None``
    means a 1-device mesh: there is nothing distributed to time.  With
    ``retries > 0`` a measurement that still fails falls back to the
    estimate winner (``rec["measure_fallback"]``) and a failed ``probe``
    is recorded as ``probe.stats["comm_sample_error"]``; with 0 both
    raise.
    """
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    if params is None:
        params = CostParams.for_backend()
    ranked = sorted(((c, float(price(c, params))) for c in cands),
                    key=lambda kv: kv[1])
    rec["mode"] = mode
    rec["ranked"] = [shown(c, t) for c, t in ranked]
    best = ranked[0][0]
    if mode == "estimate":
        return best
    if measure is None:
        rec["measure_fallback"] = "1-device mesh: measure == estimate"
        return best

    finalists, seen = [], set()
    for c, _ in ranked:
        program = key(c)
        if program not in seen:
            seen.add(program)
            finalists.append(c)
        if len(finalists) >= max(top_k, 1):
            break
    if family is not None:
        have = {family(c) for c in finalists}
        for c, _ in ranked:
            if family(c) not in have:
                have.add(family(c))
                finalists.append(c)
    measured = _measure_with_retry(
        lambda: measure(finalists), retries, rec, "measure_fallback",
        f"measurement failed after {retries} retries: ")
    if measured is None:
        # Serve the estimate ranking rather than fail the caller (the
        # self-healing re-planner must always get a plan).
        return best
    winner = min(measured, key=measured.get)
    rec["measured"] = [shown(c, float(t)) for c, t in measured.items()]
    rec["time_s"] = float(measured[winner])

    if probe is None:
        return winner
    # A failed probe loses only this round's comm sample; the winner stands.
    local_s = _measure_with_retry(lambda: probe.local_s(winner), retries,
                                  probe.stats, "comm_sample_error")
    if local_s is not None:
        probe.stats[probe.local] = float(local_s)
        probe.stats["comm_time_meas_s"] = float(
            max(rec["time_s"] - probe.phases * local_s, 0.0))
    return winner


def _settle(info: dict, schedule: SegmentSchedule
            ) -> tuple[SegmentSchedule, dict]:
    info["chosen"] = ("heterogeneous" if len(schedule.configs) > 1
                      else "homogeneous")
    info["schedule"] = schedule.to_dict()
    return schedule, info


def _race_schedules(info: dict, hetero: SegmentSchedule, est_hetero: float,
                    homo: SegmentSchedule, est_homo: float,
                    homo_cfg: PlanConfig, *, tie: SegmentSchedule, mode: str,
                    measure, retries: int = 0, measured_as: str = "measured"
                    ) -> tuple[SegmentSchedule, dict]:
    """The heterogeneous-vs-homogeneous race: a two-candidate pot whose
    order makes ``tie`` win an estimate tie."""
    info["heterogeneous"] = {"schedule": hetero.to_dict(),
                             "est_s": float(est_hetero)}
    info["homogeneous"] = {"config": homo_cfg.to_dict(),
                           "est_s": float(est_homo)}
    race: dict = {}
    winner = _rank_and_race(
        race, [hetero, homo] if tie is hetero else [homo, hetero],
        lambda s, _: est_hetero if s is hetero else est_homo, mode=mode,
        top_k=2, retries=retries, measure=measure,
        shown=lambda s, t: (s.describe(), t))
    if "measured" in race:
        info[measured_as] = race["measured"]
        info["time_s"] = race["time_s"]
    if "measure_fallback" in race:
        info["measure_fallback"] = race["measure_fallback"]
    return _settle(info, winner)


# ------------------------------------------------------------- single host

def measure_configs(configs: Sequence[PlanConfig | SegmentSchedule], n: int,
                    *, d=None, pad_lengths=None, dtype=np.complex64,
                    rounds: int = 3
                    ) -> dict[PlanConfig | SegmentSchedule, float]:
    """On-device seconds of the jitted limb per config: {config: best_s}.

    The tuners' shuffled-interleaved per-config-min harness
    (``_time_programs``) over the single-host limb; the planner
    microbenchmark times through it too.

    ``d=None`` means one whole-matrix segment (the cost model's
    convention).  Items may be ``PlanConfig``s *or* ``SegmentSchedule``s
    (both hashable) — ``tune_schedule``'s measure mode races assembled
    heterogeneous schedules against homogeneous configs in one pot.
    """
    from repro.core.pfft import _pfft_limb  # lazy: core imports plan.config

    d_eff = np.asarray(d) if d is not None else np.array([n], dtype=np.int64)

    def build(item):
        if isinstance(item, SegmentSchedule):
            return lambda m: _pfft_limb(m, d_eff, schedule=item)
        return lambda m: _pfft_limb(m, d_eff, pad_lengths=pad_lengths,
                                    config=item)
    return _time_programs(build, configs, (n, n), dtype, rounds)


def tune_config(n: int, *, d=None, pad_lengths=None, fpms: FPMSet | None = None,
                mode: str = "estimate", pad: str = "none",
                params: CostParams | None = None, top_k: int = 3,
                panels: Sequence[int] = (1,), comm_bytes: float = 0.0,
                dtype=np.complex64, reps: int = 3
                ) -> tuple[PlanConfig, dict]:
    """Pick the best ``PlanConfig`` for the problem; returns (config, info).

    ``info`` carries the full ranking (``"ranked"``: (config dict, predicted
    seconds), cheapest first) and, in measure mode, the on-device times of
    the ``top_k`` finalists (``"measured"``) — the planner's audit trail,
    also persisted into wisdom entries.
    """
    if d is not None:
        d = np.asarray(d)
    if mode == "measure" and comm_bytes:
        raise ValueError(
            "measure mode with comm_bytes needs the mesh the bytes cross — "
            "use tune_dist_config(mesh=...) to time the distributed "
            "pipeline end to end")
    info: dict = {}
    winner = _rank_and_race(
        info, candidate_configs(n, pad=pad, d=d, panels=panels),
        lambda c, prm: estimate_cost(c, n=n, d=d, pad_lengths=pad_lengths,
                                     fpms=fpms, params=prm,
                                     comm_bytes=comm_bytes),
        mode=mode, params=params, top_k=top_k,
        key=lambda c: _behavior_key(c, n, d, pad_lengths),
        measure=lambda fins: measure_configs(
            fins, n, d=d, pad_lengths=pad_lengths, dtype=dtype, rounds=reps))
    return winner, info


def _measure_length_group(configs: Sequence[PlanConfig], rows: int,
                          length: int, n: int, dtype, rounds: int
                          ) -> dict[PlanConfig, float]:
    """On-device seconds of one dispatch group's row-FFT program per config.

    The program is exactly what the schedule executor runs for a
    ``(length, config)`` group: gather ``rows`` rows of the N-wide
    matrix, pad to ``length`` (or chirp-Z at it), transform, crop.
    """
    import jax.numpy as jnp

    def build(cfg: PlanConfig):
        if cfg.pad == "czt":
            from repro.core.pfft import czt_dft
            return lambda m: czt_dft(m, length)
        from repro.fft.fft2d import fft_rows
        kw = cfg.row_fft_kwargs()
        if length > n:
            return lambda m: fft_rows(
                jnp.pad(m, ((0, 0), (0, length - n))), **kw)[:, :n]
        return lambda m: fft_rows(m, **kw)
    return _time_programs(build, configs, (rows, n), dtype, rounds)


def tune_schedule(n: int, *, d=None, pad_lengths=None,
                  fpms: FPMSet | None = None, mode: str = "estimate",
                  pad: str = "none", params: CostParams | None = None,
                  top_k: int = 3, panels: Sequence[int] = (1,),
                  comm_bytes: float = 0.0, dtype=np.complex64, reps: int = 3
                  ) -> tuple[SegmentSchedule, dict]:
    """Pick the best per-segment execution schedule; returns (schedule, info).

    The heterogeneous generalisation of ``tune_config``: candidate
    configs are priced *per distinct effective FFT length*, each segment
    with its own FPM ``time_at``, so a slow processor can keep the
    library FFT while pow2-padded fast processors take the kernel in the
    same phase.

    * Single-length problems are exactly the PR-2 homogeneous problem and
      delegate to ``tune_config`` (whose candidate space also covers
      ``fused``/``batched=False``/``pipeline_panels``).
    * Otherwise each length group is a pot of its own: estimate mode
      picks the per-group argmin under the makespan objective, then keeps
      the heterogeneous schedule only if it beats the best *homogeneous*
      config's estimate (dispatch counts included; a tie keeps the
      heterogeneous one) — the makespan can only improve, but extra
      dispatch groups are not free.
    * Measure mode times only the Pareto top-``top_k`` candidates per
      length group (distinct behaviors, cheapest-estimate first), then
      races the assembled schedule against the homogeneous winner end to
      end; ``info["time_s"]`` is the winner's limb time.
    """
    if d is not None:
        d = np.asarray(d)

    # (processor index, rows, effective length) of each non-empty segment.
    idx = [i for i, rows in enumerate(np.asarray(d))
           if rows > 0] if d is not None else [0]
    segments = [(i, rows, length) for i, (rows, length)
                in zip(idx, _segment_work(n, d, pad_lengths))]
    groups: dict[int, list[tuple[int, int]]] = {}
    for i, rows, length in segments:
        groups.setdefault(length, []).append((i, rows))

    if len(groups) <= 1:
        cfg, info = tune_config(n, d=d, pad_lengths=pad_lengths, fpms=fpms,
                                mode=mode, pad=pad, params=params,
                                top_k=top_k, panels=panels,
                                comm_bytes=comm_bytes, dtype=dtype, reps=reps)
        return _settle(info, SegmentSchedule.homogeneous(cfg, n, d,
                                                         pad_lengths))

    if mode == "measure" and comm_bytes:
        raise ValueError(
            "measure mode with comm_bytes needs the mesh the bytes cross — "
            "use tune_dist_schedule(mesh=...) to time the distributed "
            "pipeline end to end")

    info: dict = {"groups": {}}
    picks: dict[int, PlanConfig] = {}
    for length, members in groups.items():
        group: dict = {}
        picks[length] = _rank_and_race(
            group, segment_candidate_configs(length, pad=pad),
            # A group's estimate is its makespan contribution.
            lambda c, prm: max(_segment_time(c, fpms, i, rows, length, prm)
                               for i, rows in members),
            mode=mode, params=params, top_k=top_k,
            # Pow2 fallbacks erase radix differences.
            key=lambda c: (c.pad,) + _length_backend(c, length),
            measure=lambda fins: _measure_length_group(
                fins, rows=sum(r for _, r in members), length=length, n=n,
                dtype=dtype, rounds=reps))
        info["groups"][str(length)] = group["ranked"]
        if "measured" in group:
            info.setdefault("group_measured", {})[str(length)] = \
                group["measured"]

    p = len(d) if d is not None else 1
    default = PlanConfig(pad=pad)
    # Per-processor config: its length group's pick (idle processors get
    # the default; they have no schedule entry anyway).
    eff = {i: length for i, _, length in segments}
    cfg_list = [picks.get(eff.get(i, n), default) for i in range(p)]
    hetero = SegmentSchedule.from_parts(n, d, pad_lengths, cfg_list)
    est_hetero = estimate_schedule_cost(hetero, fpms=fpms, params=params,
                                        comm_bytes=comm_bytes)

    # Homogeneous envelope: the full PR-2 candidate space under one config.
    homo_cfg = _rank_and_race(
        info, candidate_configs(n, pad=pad, d=d, panels=panels),
        lambda c, prm: estimate_cost(c, n=n, d=d, pad_lengths=pad_lengths,
                                     fpms=fpms, params=prm,
                                     comm_bytes=comm_bytes),
        mode="estimate", params=params)
    info["mode"] = mode  # the envelope is only ranked; the race times it
    return _race_schedules(
        info, hetero, est_hetero,
        SegmentSchedule.homogeneous(homo_cfg, n, d, pad_lengths),
        info["ranked"][0][1], homo_cfg, tie=hetero, mode=mode,
        measure=lambda fins: measure_configs(
            fins, n, d=d, pad_lengths=pad_lengths, dtype=dtype, rounds=reps))


# --------------------------------------------------------------- distributed

def dist_panel_space(n: int, p: int, max_panels: int = 8) -> tuple[int, ...]:
    """Candidate ``pipeline_panels`` for an n x n problem on p devices:
    the powers of two up to ``max_panels`` that divide the local row count
    (``pfft2_distributed`` requires k | N/p).  The one home of the rule —
    the tuner, ``plan_pfft(mesh=...)``, and the microbench all enumerate
    (and digest) the same space.

    ``max_panels`` defaults to 8 so the full ``(1, 2, 4, 8)`` literal is
    reachable (it used to be silently capped at 4, making the 8-panel
    candidate dead code).  The panel space is part of the topology
    digest, so stores tuned under the old cap simply re-tune: a
    different candidate space is a different tuning experiment.
    """
    if p <= 0 or n % p:
        return (1,)
    n_loc = n // p
    ks = [k for k in (1, 2, 4, 8) if k <= max_panels and n_loc % k == 0]
    return tuple(ks) or (1,)


def _slab_candidates(n: int, mesh, axis_name: str, pad: str, panels
                     ) -> tuple[int, list[PlanConfig]]:
    """(p, candidates) of the slab pipeline over ``mesh``'s ``axis_name``.

    ``batched`` shapes the segment dispatch plan; the slab has one
    whole-block segment per device, so the knob is meaningless here and
    would only burn finalist slots on identical programs.
    """
    p = int(mesh.shape[axis_name])
    if n % p:
        raise ValueError(f"N={n} must be divisible by mesh axis "
                         f"{axis_name}={p}")
    if panels is None:
        panels = dist_panel_space(n, p)
    return p, [c for c in candidate_configs(n, pad=pad, d=None,
                                            panels=panels) if c.batched]


def _with_hier(cands: list[PlanConfig]) -> list[PlanConfig]:
    """``cands`` plus the hierarchical-exchange twin of each complex one:
    on a host-major axis that exchange is a real program alternative,
    raced as its own config dimension.  (The real path exchanges padded
    half-spectrum panels flat-only.)"""
    import dataclasses
    return cands + [dataclasses.replace(c, exchange="hier")
                    for c in cands if not c.real]


def _measure_local_phase(cfg: PlanConfig, n: int, rows: int,
                         pad_len: int | None, dtype, rounds: int) -> float:
    """Seconds of one *local* row-FFT pass of a distributed pipeline: the
    program one device runs on its (rows, N) block, without the
    ``all_to_all``.  Subtracting these from the end-to-end time is what
    turns a distributed measurement into a *comm* sample.  ``pad_len``
    None is the executor's own default, so the probe runs the program
    the end-to-end race ran."""
    from repro.core.pfft_dist import _local_fft, default_dist_pad_len  # lazy

    if pad_len is None:
        pad_len = default_dist_pad_len(n, cfg.dist_padded)
    return _time_programs(
        lambda c: lambda b: _local_fft(b, n, padded=c.dist_padded,
                                       pad_len=pad_len, config=c,
                                       backend=None),
        [cfg], (rows, n), dtype, rounds)[cfg]


def _measure_tier_exchange(mesh, axis_name: str, n: int, hosts: int,
                           local: int, tier: str, dtype,
                           rounds: int) -> float:
    """Seconds of ONE grouped ``all_to_all`` over only ``tier``'s groups.

    Times exactly one stage of the hierarchical exchange on the caller's
    mesh — intra-host groups (the fast tier) or inter-host groups (the
    slow tier) — on the full row-sharded N x N matrix, so the sample's
    byte count is the per-exchange tier volume ``dist_comm_bytes(...,
    hosts=, exchange="hier")`` predicts.  These tier-tagged samples are
    what ``plan/calibrate.py`` fits the two-tier comm params from.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.core.pfft_dist import _hier_groups  # lazy: core imports plan

    intra, inter = _hier_groups(hosts, local)
    exchange = jax.shard_map(
        functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                          split_axis=1, concat_axis=0, tiled=True,
                          axis_index_groups=intra if tier == "intra"
                          else inter),
        mesh=mesh, in_specs=(P(axis_name, None),),
        out_specs=P(axis_name, None), check_vma=False)
    return _time_programs(lambda _: exchange, [tier], (n, n), dtype, rounds,
                          mesh=mesh, spec=(axis_name, None))[tier]


def measure_dist_configs(configs: Sequence[PlanConfig | SegmentSchedule],
                         n: int, mesh, axis_name: str = "fft", *,
                         pad_len: int | None = None, dtype=np.complex64,
                         rounds: int = 3
                         ) -> dict[PlanConfig | SegmentSchedule, float]:
    """End-to-end on-device seconds of ``pfft2_distributed`` per config.

    Unlike ``measure_configs`` (which times the single-host limb and so
    prices ``comm_bytes`` candidates by model alone), this times the full
    pipeline — both all_to_all exchanges, pipelined panels, fused local
    phases — on the caller's actual ``Mesh``.  Same shuffled-interleaved
    per-config-min harness (``_time_programs``); the input is laid out
    row-sharded over ``axis_name`` first so placement cost is not billed
    to whichever config runs first.

    Items may be ``PlanConfig``s *or* ``SegmentSchedule``s —
    ``tune_dist_schedule`` races assembled heterogeneous (device-group)
    schedules against homogeneous finalists in one pot; a schedule runs
    with its own entry lengths (the uniform-length rule), so ``pad_len``
    applies only to bare configs.
    """
    from repro.core.pfft_dist import pfft2_distributed  # lazy

    def build(item):
        if isinstance(item, SegmentSchedule):
            kw = {"schedule": item}
        else:
            kw = {"config": item, "pad_len": pad_len}
        return functools.partial(pfft2_distributed, mesh=mesh,
                                 axis_name=axis_name, **kw)
    return _time_programs(build, configs, (n, n), dtype, rounds, mesh=mesh,
                          spec=(axis_name, None))


def tune_dist_config(n: int, mesh, axis_name: str = "fft", *,
                     mode: str = "estimate", pad: str = "none",
                     pad_len: int | None = None, fpms: FPMSet | None = None,
                     params: CostParams | None = None, top_k: int = 3,
                     panels: Sequence[int] | None = None,
                     dtype=np.complex64, reps: int = 3,
                     measure_retries: int = 0
                     ) -> tuple[PlanConfig, dict]:
    """Pick the best ``PlanConfig`` for ``pfft2_distributed`` on ``mesh``.

    The distributed sibling of ``tune_config``: candidates are ranked with
    the comm term filled in from the mesh (``dist_comm_bytes``), and
    ``mode="measure"`` races the ``top_k`` distinct finalists through the
    *full* pipeline on the mesh — both all_to_all phases included — via
    ``measure_dist_configs``, instead of pricing comm by model alone.

    On a 1-device mesh measure falls back to estimate (there is no
    interconnect to measure; the degenerate all_to_all is a reshuffle) and
    ``info["measure_fallback"]`` says so.

    ``info["dist"]`` carries the topology facts and, after a measured run,
    the comm sample: ``comm_time_meas_s = total − 2·local_phase``
    (clamped at 0), the number ``plan/calibrate.py`` fits
    ``interconnect_bytes_per_s``/``comm_latency_s`` from.  Both
    ``comm_time_est_s`` and ``comm_time_meas_s`` cover the transform's
    *two* all_to_all phases, so they compare directly.
    """
    p, cands = _slab_candidates(n, mesh, axis_name, pad, panels)
    if params is None:
        params = CostParams.for_backend()
    comm_bytes = dist_comm_bytes(n, p)
    from repro.launch.mesh import mesh_host_shape  # lazy: launch is thin
    hosts, local = mesh_host_shape(mesh, axis_name)
    if hosts > 1 and local > 1:
        cands = _with_hier(cands)
    stats = {
        "devices": p,
        "hosts": int(hosts),
        "axis_name": axis_name,
        "comm_bytes": float(comm_bytes),
        # Both phases, like the measured sample it is judged against.
        "comm_time_est_s": float(2.0 * comm_phase_time(
            comm_bytes, params.interconnect_bytes_per_s,
            params.comm_latency_s)),
    }
    info: dict = {"dist": stats}
    winner = _rank_and_race(
        info, cands,
        lambda c, prm: estimate_cost(
            c, n=n, fpms=fpms, params=prm, comm_bytes=comm_bytes,
            comm_time_s=dist_comm_time(n, p, params=prm, hosts=hosts,
                                       exchange=c.exchange)),
        mode=mode, params=params, top_k=top_k, retries=measure_retries,
        # The panel count changes the collective structure even when the
        # local program is identical.
        key=lambda c: (_behavior_key(c, n, None, None), c.pipeline_panels),
        measure=None if p <= 1 else lambda fins: measure_dist_configs(
            fins, n, mesh, axis_name, pad_len=pad_len, dtype=dtype,
            rounds=reps),
        probe=_CommProbe(stats, "local_phase_s", 2.0,
                         lambda w: _measure_local_phase(
                             w, n, n // p, pad_len, dtype, reps)))
    if "comm_time_meas_s" not in stats:
        return winner, info
    stats["exchange"] = winner.exchange
    if hosts > 1 and local > 1:
        # Per-tier samples: one grouped all_to_all per tier, so calibrate
        # can fit the intra- and inter-host comm params separately.  The
        # byte counts are the hierarchical per-exchange tier volumes the
        # same microbench actually moves; ``msgs`` is the slow-tier
        # message count of the timed launch (the latency multiplier).
        tiers = dist_comm_bytes(n, p, hosts=hosts, exchange="hier")
        samples = []
        for tier, tier_bytes, msgs in (("intra", tiers.intra, 1),
                                       ("inter", tiers.inter, hosts - 1)):
            if not tier_bytes:
                continue
            t = _measure_with_retry(
                lambda tier=tier: _measure_tier_exchange(
                    mesh, axis_name, n, hosts, local, tier, dtype, reps),
                measure_retries, stats, "tier_sample_error")
            if t is None:
                break
            samples.append({"tier": tier, "bytes": float(tier_bytes),
                            "msgs": int(msgs), "time_s": float(t)})
        if samples:
            stats["comm_samples"] = samples
    return winner, info


# ------------------------------------------------------------------ pfft3

def pfft3_panel_space(n: int, r: int, c: int, max_panels: int = 8
                      ) -> tuple[int, ...]:
    """Candidate ``pipeline_panels`` for an N^3 problem on an r x c pencil
    mesh: the powers of two up to ``max_panels`` dividing *both* local
    extents (``pfft3_pencil`` splits panels along whichever block axis the
    current exchange leaves alone, so k must divide N/r and N/c alike).
    The one home of the rule — the tuner, ``plan_pfft3(mesh=...)``, and
    the microbench all enumerate (and digest) the same space.
    """
    import math

    r, c = int(r), int(c)
    if r <= 0 or c <= 0 or n % r or n % c:
        return (1,)
    g = math.gcd(n // r, n // c)
    ks = [k for k in (1, 2, 4, 8) if k <= max_panels and g % k == 0]
    return tuple(ks) or (1,)


def measure_pfft3_configs(configs: Sequence[PlanConfig], n: int, mesh,
                          axis_names: Sequence[str] = ("fft_r", "fft_c"), *,
                          pad_len: int | None = None, dtype=np.complex64,
                          rounds: int = 3) -> dict[PlanConfig, float]:
    """End-to-end on-device seconds of ``pfft3_pencil`` per config.

    The 3-D sibling of ``measure_dist_configs``: times the full pencil
    pipeline — three local passes, both all_to_all rounds, pipelined
    panels, the final global transpose — on the caller's actual 2-D
    ``Mesh``.  Same shuffled-interleaved per-config-min harness
    (``_time_programs``); the cube is laid out pencil-sharded over
    ``axis_names`` first so placement cost is not billed to whichever
    config runs first.  One call races one *orientation* — callers
    (``tune_pfft3``) merge per-orientation races themselves.
    """
    from repro.core.pfft3d import pfft3_pencil  # lazy

    axes = tuple(axis_names)
    return _time_programs(
        lambda cfg: functools.partial(pfft3_pencil, mesh=mesh,
                                      axis_names=axes, config=cfg,
                                      pad_len=pad_len),
        configs, (n, n, n), dtype, rounds, mesh=mesh,
        spec=(axes[0], axes[1], None))


def tune_pfft3(n: int, mesh=None,
               axis_names: Sequence[str] = ("fft_r", "fft_c"), *,
               mode: str = "estimate", pad: str = "none",
               pad_len: int | None = None,
               params: CostParams | None = None, top_k: int = 3,
               panels: Sequence[int] | None = None, dtype=np.complex64,
               reps: int = 3, measure_retries: int = 0
               ) -> tuple[PlanConfig, tuple[str, str] | None, dict]:
    """Pick the best (config, pencil orientation) for the 3-D transform.

    Returns ``(config, axes, info)`` where ``axes`` is the winning
    ``(row_axis, col_axis)`` orientation of ``pfft3_pencil`` — the extra
    degree of freedom the 2-D mesh adds over ``tune_dist_config``: on a
    rectangular r x c mesh the first exchange crosses the *column* axis,
    so swapping which mesh axis plays row changes which round moves the
    bigger fraction of the cube.  Both orientations enter the estimate
    ranking (priced via ``estimate_pfft3_cost``), and measure mode races
    the distinct finalists of each through the full pencil pipeline.

    ``mesh=None`` is the single-host problem (r = c = 1, ``axes=None``):
    the ranking degenerates to the compute terms, and measure mode times
    the jitted single-host ``pfft3_lb`` instead of the pencil program.
    ``info["pfft3"]`` carries the topology facts and, after a measured
    run, the comm sample ``comm_time_meas_s = total − 3·local_pass``
    (clamped at 0) covering both exchange rounds.
    """
    axes0 = tuple(axis_names)
    if mesh is not None:
        r = int(mesh.shape[axes0[0]])
        c = int(mesh.shape[axes0[1]])
        if n % r or n % c:
            raise ValueError(f"N={n} must be divisible by both mesh axes "
                             f"({axes0[0]}={r}, {axes0[1]}={c})")
    else:
        r = c = 1
    if panels is None:
        panels = pfft3_panel_space(n, r, c)
    if params is None:
        params = CostParams.for_backend()
    comm_bytes = pfft3_comm_bytes(n, c) + pfft3_comm_bytes(n, r)
    if mesh is not None:
        from repro.launch.mesh import mesh_host_shape  # lazy: launch is thin
        host_shapes = {a: mesh_host_shape(mesh, a) for a in axes0}
    else:
        host_shapes = {}

    # ``batched`` shapes segment dispatch (one whole-pencil segment here)
    # and the pencil pipeline is unfused by construction — both knobs
    # would only burn finalist slots on identical or invalid programs.
    cands = [cfg for cfg in candidate_configs(n, pad=pad, d=None,
                                              panels=panels)
             if cfg.batched and not cfg.fused]
    if any(h > 1 and l > 1 for h, l in host_shapes.values()):
        # Some orientation puts a host-major axis under the row exchange.
        cands = _with_hier(cands)
    # Orientation space: which mesh axis plays "row".  On a square mesh
    # (or single host) the transposed program is identical.
    if mesh is not None and r != c:
        orientations = [axes0, (axes0[1], axes0[0])]
    elif mesh is not None:
        orientations = [axes0]
    else:
        orientations = [None]

    def est(cand, prm: CostParams) -> float:
        cfg, waxes = cand
        if waxes is None:
            r_o, c_o, h_o = 1, 1, 1
        else:
            r_o = int(mesh.shape[waxes[0]])
            c_o = int(mesh.shape[waxes[1]])
            # Hosts ride the orientation's row axis (the only exchange
            # the hierarchical form applies to); a non-host-major row
            # axis prices — and runs — as flat.
            h_o = host_shapes[waxes[0]][0]
        return estimate_pfft3_cost(cfg, n=n, r=r_o, c=c_o, params=prm,
                                   pad_len=pad_len, hosts=h_o)

    def measure(finalists) -> dict:
        if mesh is None:
            # Single host: time the production single-host program.
            from repro.core.pfft3d import pfft3_lb  # lazy
            return _time_programs(
                lambda cand: lambda m: pfft3_lb(m, 1, config=cand[0]),
                finalists, (n, n, n), dtype, reps)
        merged: dict = {}
        for waxes in orientations:
            group = [cfg for cfg, wa in finalists if wa == waxes]
            if group:
                times = measure_pfft3_configs(group, n, mesh, waxes,
                                              pad_len=pad_len, dtype=dtype,
                                              rounds=reps)
                merged.update(((cfg, waxes), t) for cfg, t in times.items())
        return merged

    stats = {
        "r": r, "c": c,
        "hosts": int(host_shapes.get(axes0[0], (1, 1))[0]),
        "axis_names": list(axes0) if mesh is not None else None,
        "comm_bytes": float(comm_bytes),
        "comm_time_est_s": float(
            sum(comm_phase_time(b, params.interconnect_bytes_per_s,
                                params.comm_latency_s)
                for b in (pfft3_comm_bytes(n, c),
                          pfft3_comm_bytes(n, r)))),
    }
    info: dict = {"pfft3": stats}
    cfg, waxes = _rank_and_race(
        info, [(cfg, waxes) for cfg in cands for waxes in orientations], est,
        mode=mode, params=params, top_k=top_k, retries=measure_retries,
        # Orientation changes which round crosses which communicator even
        # when the local program is the same.
        key=lambda cand: (_behavior_key(cand[0], n, None, None),
                          cand[0].pipeline_panels, cand[1]),
        measure=None if mesh is not None and r * c <= 1 else measure,
        probe=_CommProbe(stats, "local_pass_s", 3.0,
                         lambda w: _measure_local_phase(
                             w[0], n, (n // r) * (n // c), pad_len, dtype,
                             reps)),
        shown=lambda cand, t: (cand[0].to_dict(),
                               list(cand[1]) if cand[1] is not None
                               else None, t))
    info["orientation"] = list(waxes) if waxes is not None else None
    if "comm_time_meas_s" in stats:
        stats["exchange"] = cfg.exchange
    return cfg, waxes, info


def tune_pfft1_large(n: int, *, n1: int | None = None, n2: int | None = None,
                     mode: str = "estimate",
                     params: CostParams | None = None, top_k: int = 3,
                     dtype=np.complex64, reps: int = 3
                     ) -> tuple[PlanConfig, dict]:
    """Tune the four-step huge-1-D transform; returns (config, info).

    The four-step decomposition runs two row-FFT phases at lengths n2 and
    n1 (``core.pfft_large``), so the estimate prices each phase at its
    own length with the config's backend multiplier — a radix kernel that
    helps the pow2 side may be a fallback no-op on the other.  Measure
    mode times the jitted production ``pfft1_large_apply`` end to end.
    """
    from repro.core.pfft_large import four_step_factors  # lazy

    n1, n2 = four_step_factors(n, n1=n1, n2=n2)

    radices: list[int | None] = [None]
    if _is_pow2(n1) or _is_pow2(n2):
        radices += [2, 4]

    def est(cfg: PlanConfig, prm: CostParams) -> float:
        compute = (_segment_time(cfg, None, 0, n1, n2, prm)
                   + _segment_time(cfg, None, 0, n2, n1, prm))
        itemsize = np.dtype(dtype).itemsize
        traffic = 4.0 * n * itemsize / prm.hbm_bytes_per_s
        return compute + traffic + 2.0 * prm.dispatch_overhead_s

    def measure(finalists) -> dict:
        from repro.core.pfft_large import pfft1_large_apply  # lazy
        return _time_programs(
            lambda c: lambda v: pfft1_large_apply(v, config=c, n1=n1, n2=n2),
            finalists, (n,), dtype, reps)

    info: dict = {"four_step": {"n1": int(n1), "n2": int(n2)}}
    winner = _rank_and_race(
        info, [PlanConfig(radix=rad) for rad in radices], est, mode=mode,
        params=params, top_k=top_k,
        key=lambda c: (_length_backend(c, n1), _length_backend(c, n2)),
        measure=measure)
    return winner, info


# ------------------------------------------------------------------- real

def _require_real_dtype(dtype, pad: str = "none") -> np.dtype:
    """Validate a real-pipeline input dtype and pad; returns the np.dtype."""
    dt = np.dtype(dtype)
    if dt not in (np.dtype(np.float32), np.dtype(np.float64)):
        raise ValueError(
            f"the real pipeline tunes float32/float64 inputs, got {dt.name}")
    if pad == "czt":
        raise ValueError("the real pipeline has no Bluestein form")
    return dt


def _real_candidates(cands: Sequence[PlanConfig], n: int
                     ) -> list[PlanConfig]:
    """The real-flagged twins of a complex candidate list (czt dropped —
    the real pipeline has no Bluestein form)."""
    import dataclasses
    return _drop_excluded([dataclasses.replace(c, real=True) for c in cands
                           if c.pad != "czt"], n)


def _is_real(cfg: PlanConfig) -> bool:
    """The family of a real pot's candidate: finalists always include the
    best of each, so measure mode genuinely races real-vs-complex rather
    than burning every slot on one side."""
    return cfg.real


def measure_rfft_configs(configs: Sequence[PlanConfig], n: int, *, d=None,
                         pad_lengths=None, dtype=np.float32, rounds: int = 3
                         ) -> dict[PlanConfig, float]:
    """On-device seconds of the half-spectrum limb per config.

    ``real`` configs run ``_rpfft_limb`` on the real input; complex
    fallback configs run ``_pfft_limb`` on the upcast input and crop to
    the half spectrum — the *same* (N, N//2+1) deliverable with the same
    partition and pad lengths, so the race is apples-to-apples (the
    padded real phase equals the padded complex phase's half spectrum
    bin for bin — see ``core.pfft.halfspec_distribution``).
    """
    from repro.core.pfft import _pfft_limb, _rpfft_limb  # lazy

    dt = _require_real_dtype(dtype)
    ctype = np.result_type(dt, np.complex64)
    nh = n // 2 + 1
    d_eff = np.asarray(d) if d is not None else np.array([n], dtype=np.int64)

    def build(cfg: PlanConfig):
        if cfg.real:
            return lambda m: _rpfft_limb(m, d_eff, pad_lengths=pad_lengths,
                                         config=cfg)
        return lambda m: _pfft_limb(m.astype(ctype), d_eff,
                                    pad_lengths=pad_lengths,
                                    config=cfg)[:, :nh]
    return _time_programs(build, configs, (n, n), dt, rounds)


def tune_rfft(n: int, *, d=None, pad_lengths=None, fpms: FPMSet | None = None,
              mode: str = "estimate", pad: str = "none",
              params: CostParams | None = None, top_k: int = 3,
              dtype=np.float32, reps: int = 3
              ) -> tuple[SegmentSchedule, dict]:
    """Tune a real-input half-spectrum problem; returns (schedule, info).

    The candidate pot holds *both families*: real-flagged configs (the
    rfft pipeline) and their complex twins (upcast + crop fallback), so
    the planner picks real-vs-complex per (n, dtype) on the cost model —
    or, in measure mode, on an on-device race whose finalists always
    include the best of each family.  ``info["chosen_path"]`` says which
    side won; the returned schedule's configs carry the ``real`` flag the
    executor routes on.
    """
    _require_real_dtype(dtype, pad)
    if d is not None:
        d = np.asarray(d)

    complex_cands = candidate_configs(n, pad=pad, d=d)
    info: dict = {}
    winner = _rank_and_race(
        info, _real_candidates(complex_cands, n) + complex_cands,
        lambda c, prm: estimate_cost(c, n=n, d=d, pad_lengths=pad_lengths,
                                     fpms=fpms, params=prm),
        mode=mode, params=params, top_k=top_k, family=_is_real,
        key=lambda c: _behavior_key(c, n, d, pad_lengths),
        measure=lambda fins: measure_rfft_configs(
            fins, n, d=d, pad_lengths=pad_lengths, dtype=dtype, rounds=reps))
    info["chosen_path"] = "real" if winner.real else "complex"
    schedule = SegmentSchedule.homogeneous(winner, n, d, pad_lengths)
    info["schedule"] = schedule.to_dict()
    return schedule, info


def measure_rfft_dist_configs(configs: Sequence[PlanConfig], n: int, mesh,
                              axis_name: str = "fft", *,
                              pad_len: int | None = None, dtype=np.float32,
                              rounds: int = 3) -> dict[PlanConfig, float]:
    """End-to-end on-device seconds of the distributed half-spectrum
    transform per config: ``real`` configs run ``rpfft2_distributed``
    (half-width all_to_all panels), complex fallbacks run the upcast
    ``pfft2_distributed`` cropped to the half spectrum — same deliverable
    on the same mesh, same sharded-input discipline as
    ``measure_dist_configs``.
    """
    from repro.core.pfft_dist import (pfft2_distributed,  # lazy
                                      rpfft2_distributed)

    dt = _require_real_dtype(dtype)
    ctype = np.result_type(dt, np.complex64)
    nh = n // 2 + 1

    def build(cfg: PlanConfig):
        if cfg.real:
            return functools.partial(rpfft2_distributed, mesh=mesh,
                                     axis_name=axis_name, config=cfg,
                                     pad_len=pad_len)
        return lambda m: pfft2_distributed(
            m.astype(ctype), mesh=mesh, axis_name=axis_name, config=cfg,
            pad_len=pad_len)[:, :nh]
    return _time_programs(build, configs, (n, n), dt, rounds, mesh=mesh,
                          spec=(axis_name, None))


def _measure_local_real_phases(cfg: PlanConfig, n: int, p: int,
                               pad_len: int | None, dtype,
                               rounds: int) -> float:
    """Combined seconds of the real pipeline's two *local* phase programs
    (rfft on the (N/p, N) row block + complex FFT on the (hc/p, N)
    spectral block) — the subtraction term that turns an end-to-end real
    measurement into a comm sample, mirroring ``_measure_local_phase``.
    """
    from repro.core.pfft import _group_row_rffts, _group_row_ffts  # lazy
    from repro.core.pfft_dist import default_dist_pad_len

    dt = _require_real_dtype(dtype)
    if pad_len is None:
        pad_len = default_dist_pad_len(n, cfg.dist_padded)
    length = pad_len if cfg.pad == "fpm" else n
    rfft = _time_programs(
        lambda c: lambda b: _group_row_rffts(b, length, n, c, None),
        [cfg], (max(n // p, 1), n), dt, rounds)
    fft = _time_programs(
        lambda c: lambda b: _group_row_ffts(b, length, n, c, None),
        [cfg], (max(halfspec_cols(n, p) // p, 1), n),
        np.result_type(dt, np.complex64), rounds)
    return rfft[cfg] + fft[cfg]


def tune_rfft_dist(n: int, mesh, axis_name: str = "fft", *,
                   mode: str = "estimate", pad: str = "none",
                   pad_len: int | None = None, fpms: FPMSet | None = None,
                   params: CostParams | None = None, top_k: int = 3,
                   panels: Sequence[int] | None = None, dtype=np.float32,
                   reps: int = 3, measure_retries: int = 0
                   ) -> tuple[SegmentSchedule, dict]:
    """Tune the distributed real-input transform on ``mesh``.

    Real candidates are priced with the *half-spectrum* comm term
    (``dist_comm_bytes(real=True)`` — ~half the complex bytes) and their
    complex twins with the full-panel term, so estimate mode already sees
    the comm saving; measure mode races both families end to end through
    their actual distributed programs.  The real path's program shape is
    homogeneous/unfused/monolithic (``rpfft2_distributed``), so real
    candidates enumerate only the row-FFT backend; complex fallbacks keep
    the full panel/fused space.  ``info["dist"]`` carries both byte
    counts, their ratio, and (measured) the winner's comm sample.
    """
    _require_real_dtype(dtype, pad)
    p, complex_cands = _slab_candidates(n, mesh, axis_name, pad, panels)
    comm_complex = dist_comm_bytes(n, p)
    comm_real = dist_comm_bytes(n, p, real=True)
    real_cands = [c for c in _real_candidates(complex_cands, n)
                  if not c.fused and c.pipeline_panels == 1]

    def local_s(winner: PlanConfig) -> float:
        if winner.real:
            return _measure_local_real_phases(winner, n, p, pad_len, dtype,
                                              reps)
        return 2.0 * _measure_local_phase(
            winner, n, n // p, pad_len,
            np.result_type(np.dtype(dtype), np.complex64), reps)

    stats = {
        "devices": p,
        "axis_name": axis_name,
        "comm_bytes_complex": float(comm_complex),
        "comm_bytes_real": float(comm_real),
        "comm_ratio_real": (float(comm_real / comm_complex)
                            if comm_complex else 0.0),
    }
    info: dict = {"dist": stats}
    winner = _rank_and_race(
        info, real_cands + complex_cands,
        lambda c, prm: estimate_cost(c, n=n, fpms=fpms, params=prm,
                                     comm_bytes=comm_real if c.real
                                     else comm_complex),
        mode=mode, params=params, top_k=top_k, retries=measure_retries, family=_is_real,
        key=lambda c: _behavior_key(c, n, None, None),
        measure=None if p <= 1 else lambda fins: measure_rfft_dist_configs(
            fins, n, mesh, axis_name, pad_len=pad_len, dtype=dtype,
            rounds=reps),
        probe=_CommProbe(stats, "local_phase_s", 1.0, local_s))
    info["chosen_path"] = "real" if winner.real else "complex"
    stats["comm_bytes"] = float(comm_real if winner.real else comm_complex)
    d = np.full(p, n // p, dtype=np.int64) if p > 0 else None
    schedule = SegmentSchedule.homogeneous(winner, n, d)
    info["schedule"] = schedule.to_dict()
    return schedule, info


def grouped_dist_schedule(n: int, p: int, *, pad_lengths=None,
                          fpms: FPMSet | None = None, pad: str = "none",
                          params: CostParams | None = None
                          ) -> SegmentSchedule | None:
    """The model-driven heterogeneous candidate for a p-device mesh.

    One entry per device (N/p rows — the SPMD shard), each assigned the
    ``segment_candidate_configs`` argmin of *its own* predicted time:
    its FPM's ``time_at`` (or the nominal flop rate) at its own declared
    effective length, times the candidate's backend multiplier.  Mixed
    per-device pad lengths are what make the assignment genuinely mixed
    — a pow2-padded device's kernel candidates survive ``_factor_term``
    while a non-pow2 neighbour falls back to the library FFT — exactly
    how the single-host ``tune_schedule`` grows heterogeneity.  Returns
    ``None`` when the assembly degenerates to a single config (nothing
    to group) or p <= 1; the caller prices the survivor with
    ``estimate_grouped_cost`` (the lowering runs every branch at the max
    length — the declared-length estimate is the model's view of *why*
    each device picked its variant, not of the padded flops).
    """
    if p <= 1 or n % p:
        return None
    if params is None:
        params = CostParams.for_backend()
    if fpms is not None and fpms.p != p:
        fpms = None  # one abstract processor per device or no FPM at all
    n_loc = n // p
    d = np.full(p, n_loc, dtype=np.int64)
    cfgs = []
    for i in range(p):
        length = n
        if pad_lengths is not None and int(pad_lengths[i]) > n:
            length = int(pad_lengths[i])
        cands = segment_candidate_configs(length, pad=pad)
        cfgs.append(min(cands, key=lambda c: _segment_time(
            c, fpms, i, n_loc, length, params)))
    schedule = SegmentSchedule.from_parts(n, d, pad_lengths, cfgs)
    return schedule if len(schedule.configs) > 1 else None


def tune_dist_schedule(n: int, mesh, axis_name: str = "fft", *,
                       pad_lengths=None, mode: str = "estimate",
                       pad: str = "none", pad_len: int | None = None,
                       fpms: FPMSet | None = None,
                       params: CostParams | None = None, top_k: int = 3,
                       panels: Sequence[int] | None = None,
                       dtype=np.complex64, reps: int = 3,
                       measure_retries: int = 0
                       ) -> tuple[SegmentSchedule, dict]:
    """Schedule-shaped distributed tuner; returns (schedule, info).

    The homogeneous candidate space is ``tune_dist_config``'s (comm term
    from the mesh, measure mode racing finalists end to end).  On top of
    it the tuner *grows heterogeneous candidates*: the per-device
    assembly of ``grouped_dist_schedule`` — lowered by the executor as a
    device-group program (``repro.plan.groups``) — priced with
    ``estimate_grouped_cost`` (per-group makespan + switch-dispatch
    overhead) against the homogeneous winner; a tie keeps the
    homogeneous one.  ``mode="measure"`` races
    the grouped finalist against the homogeneous winner end to end
    through the *actual* grouped ``pfft2_distributed`` program on the
    caller's mesh (``info["grouped_measured"]``), so a genuinely
    heterogeneous pod's mixed pick is chosen on evidence, not model
    faith.  This is what ``plan_pfft(mesh=...)`` resolves through, so
    grouped picks persist under the same v3 topology keys.
    """
    p = int(mesh.shape[axis_name])
    if pad_len is None and pad_lengths is not None:
        # The returned schedule executes at the uniform max effective
        # length (pfft2_distributed's uniform-length rule), so the
        # homogeneous finalists must be raced — and the comm sample
        # taken — at that very length, not the unpadded/smooth default:
        # a measured time for a program the plan never runs would poison
        # the wisdom entry and the interconnect calibration.
        lengths = [int(x) for x in pad_lengths if int(x) > n]
        if lengths:
            pad_len = max(lengths)
    cfg, info = tune_dist_config(n, mesh, axis_name, mode=mode, pad=pad,
                                 pad_len=pad_len, fpms=fpms, params=params,
                                 top_k=top_k, panels=panels, dtype=dtype,
                                 reps=reps, measure_retries=measure_retries)
    d = np.full(p, n // p, dtype=np.int64) if p > 0 else None
    homo = SegmentSchedule.homogeneous(cfg, n, d, pad_lengths)
    hetero = grouped_dist_schedule(n, p, pad_lengths=pad_lengths, fpms=fpms,
                                   pad=pad, params=params)
    if hetero is None:
        return _settle(info, homo)

    fpms_dev = fpms if fpms is not None and fpms.p == p else None
    comm_bytes = dist_comm_bytes(n, p)
    est_hetero = estimate_grouped_cost(hetero, fpms=fpms_dev, params=params,
                                       comm_bytes=comm_bytes)
    est_homo = estimate_grouped_cost(homo, fpms=fpms_dev, params=params,
                                     comm_bytes=comm_bytes)
    return _race_schedules(
        info, hetero, est_hetero, homo, est_homo, cfg, tie=homo,
        mode="estimate" if "measure_fallback" in info else mode,
        retries=measure_retries, measured_as="grouped_measured",
        measure=lambda fins: measure_dist_configs(
            fins, n, mesh, axis_name, dtype=dtype, rounds=reps))
