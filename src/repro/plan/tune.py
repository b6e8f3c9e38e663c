"""Estimate/measure tuner — FFTW's planner loop over ``PlanConfig`` space.

``candidate_configs`` enumerates the valid variant space for a problem
(radix x fused x batched x pipeline_panels, pruned by structural
constraints); ``tune_config`` ranks it:

* ``mode="estimate"`` — cost model only (``plan.cost``), no device work.
  FFTW's ESTIMATE: instant, right whenever the model's ranking is.
* ``mode="measure"`` — time the ``top_k`` cheapest candidates on device
  (``measure_configs``: interleaved round-robin, per-config min) and take
  the winner.  FFTW's MEASURE: pays seconds once so every later execute
  is served by the best plan.

The caller (``plan_pfft`` / the microbenchmark) persists the result via
``plan.wisdom`` so measurement happens once per (n, dtype, p, method,
backend) per machine.
"""

from __future__ import annotations

import functools
import time
from typing import Sequence

import numpy as np

from repro.core.fpm import FPMSet
from repro.plan.config import PlanConfig
from repro.plan.cost import (CostParams, _compute_multiplier, _segment_work,
                             comm_phase_time, dist_comm_bytes, dist_comm_time,
                             estimate_cost, estimate_grouped_cost,
                             estimate_pfft3_cost, estimate_schedule_cost,
                             exchange_time, pfft3_comm_bytes)
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


def _is_pow2(n: int) -> bool:
    return n > 0 and not (n & (n - 1))


def _measure_with_retry(thunk, retries: int, base_s: float = 0.05):
    """Run a measurement thunk, retrying transient failures with
    exponential backoff; re-raises after the budget is exhausted.

    Device measurement is the tuner's only fallible step (a transiently
    wedged device, an allocator hiccup mid-chaos) — the distributed
    tuners call this when ``measure_retries > 0`` and fall back to the
    estimate ranking (``info["measure_fallback"]``) if even the retries
    fail, so a flaky measurement degrades a *plan choice*, never the
    caller.  ``retries=0`` (the default everywhere) keeps the historical
    raise-through behavior.
    """
    delay = float(base_s)
    for attempt in range(int(retries) + 1):
        try:
            return thunk()
        except Exception:
            if attempt >= retries:
                raise
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


def _timed_min(pairs, x, rounds: int) -> dict:
    """{item: best seconds} over ``rounds`` shuffled-interleaved episodes.

    The shared timing discipline of every measure harness here: an
    untimed same-fn warm run before each timed one (evict the shuffled
    neighbour's allocator/cache state), per-item min across rounds.
    ``pairs``: [(item, compiled fn)].
    """
    import jax

    rng = np.random.default_rng(1)
    times = {item: float("inf") for item, _ in pairs}
    for _ in range(max(rounds, 1)):
        for i in rng.permutation(len(pairs)):
            item, fn = pairs[int(i)]
            jax.block_until_ready(fn(x))  # warm: evict neighbour's state
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            times[item] = min(times[item], time.perf_counter() - t0)
    return times


def measure_configs(configs: Sequence[PlanConfig | SegmentSchedule], n: int,
                    *, d=None, pad_lengths=None, dtype=np.complex64,
                    rounds: int = 3
                    ) -> dict[PlanConfig | SegmentSchedule, float]:
    """On-device seconds of the jitted limb per config: {config: best_s}.

    Interleaved in a per-round *shuffled* order, per-config min over
    ``rounds``, with an untimed same-config warm run before every timed
    one: close variants (batched vs looped) differ by far less than the
    episode-to-episode jitter, and a fixed visiting order would tax each
    config by whatever allocator/cache state its fixed neighbour leaves
    behind (one warm run does not fully neutralise an interpret-mode
    Pallas predecessor).  Shuffling varies the predecessor; min keeps
    each config's best-context episode.  This is the shared harness of
    measure-mode tuning and the planner microbenchmark.

    ``d=None`` means one whole-matrix segment (the cost model's
    convention).  Items may be ``PlanConfig``s *or* ``SegmentSchedule``s
    (both hashable) — ``tune_schedule``'s measure mode races assembled
    heterogeneous schedules against homogeneous configs in one pot.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.pfft import _pfft_limb  # lazy: core imports plan.config

    d_eff = np.asarray(d) if d is not None else np.array([n], dtype=np.int64)
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((n, n))
                     + 1j * rng.standard_normal((n, n))).astype(dtype))
    pairs = []
    for item in configs:
        if isinstance(item, SegmentSchedule):
            kw = {"schedule": item}
        else:
            kw = {"pad_lengths": pad_lengths, "config": item}
        fn = jax.jit(lambda m, kw=kw: _pfft_limb(m, d_eff, **kw))
        jax.block_until_ready(fn(x))  # compile
        pairs.append((item, fn))
    return _timed_min(pairs, x, rounds)


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
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    if d is not None:
        d = np.asarray(d)

    cands = candidate_configs(n, pad=pad, d=d, panels=panels)
    if params is None:
        params = CostParams.for_backend()
    ranked = sorted(
        ((cfg, estimate_cost(cfg, n=n, d=d, pad_lengths=pad_lengths,
                             fpms=fpms, params=params, comm_bytes=comm_bytes))
         for cfg in cands),
        key=lambda kv: kv[1])
    info: dict = {
        "mode": mode,
        "ranked": [(cfg.to_dict(), float(c)) for cfg, c in ranked],
    }

    if mode == "estimate":
        return ranked[0][0], info

    if comm_bytes:
        raise ValueError(
            "measure mode with comm_bytes needs the mesh the bytes cross — "
            "use tune_dist_config(mesh=...) to time the distributed "
            "pipeline end to end")
    # One finalist per distinct *program*: ties in the ranking are often
    # configs whose differences are erased by runtime fallbacks.
    finalists, seen = [], set()
    for cfg, _ in ranked:
        key = _behavior_key(cfg, n, d, pad_lengths)
        if key not in seen:
            seen.add(key)
            finalists.append(cfg)
        if len(finalists) >= max(top_k, 1):
            break
    measured = measure_configs(finalists, n, d=d, pad_lengths=pad_lengths,
                               dtype=dtype, rounds=reps)
    winner = min(measured, key=measured.get)
    info["measured"] = [(cfg.to_dict(), float(t)) for cfg, t in measured.items()]
    info["time_s"] = float(measured[winner])
    return winner, info


def _measure_length_group(configs: Sequence[PlanConfig], rows: int,
                          length: int, n: int, dtype, rounds: int
                          ) -> dict[PlanConfig, float]:
    """On-device seconds of one dispatch group's row-FFT program per config.

    The program is exactly what the schedule executor runs for a
    ``(length, config)`` group: gather ``rows`` rows of the N-wide
    matrix, pad to ``length`` (or chirp-Z at it), transform, crop.  Same
    shuffled-interleaved-min discipline as ``measure_configs``.
    """
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((rows, n))
                     + 1j * rng.standard_normal((rows, n))).astype(dtype))

    def group_fn(cfg: PlanConfig):
        if cfg.pad == "czt":
            from repro.core.pfft import czt_dft
            return lambda m: czt_dft(m, length)
        from repro.fft.fft2d import fft_rows
        kw = cfg.row_fft_kwargs()
        if length > n:
            return lambda m: fft_rows(
                jnp.pad(m, ((0, 0), (0, length - n))), **kw)[:, :n]
        return lambda m: fft_rows(m, **kw)

    pairs = []
    for cfg in configs:
        fn = jax.jit(group_fn(cfg))
        jax.block_until_ready(fn(x))  # compile
        pairs.append((cfg, fn))
    return _timed_min(pairs, x, rounds)


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
    * Otherwise, estimate mode picks the per-group argmin under the
      makespan objective, then keeps the heterogeneous schedule only if
      it beats the best *homogeneous* config's estimate (dispatch counts
      included) — the makespan can only improve, but extra dispatch
      groups are not free.
    * Measure mode times only the Pareto top-``top_k`` candidates per
      length group (distinct behaviors, cheapest-estimate first), then
      races the assembled schedule against the homogeneous winner end to
      end; ``info["time_s"]`` is the winner's limb time.
    """
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    if d is not None:
        d = np.asarray(d)
    if params is None:
        params = CostParams.for_backend()

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
        schedule = SegmentSchedule.homogeneous(cfg, n, d, pad_lengths)
        info["chosen"] = "homogeneous"
        info["schedule"] = schedule.to_dict()
        return schedule, info

    if mode == "measure" and comm_bytes:
        raise ValueError(
            "measure mode with comm_bytes needs the mesh the bytes cross — "
            "use tune_dist_schedule(mesh=...) to time the distributed "
            "pipeline end to end")

    def group_time(cfg: PlanConfig, members, length: int) -> float:
        """Estimated makespan contribution of one length group under cfg."""
        def seg_t(i: int, rows: int) -> float:
            if fpms is not None:
                t = fpms[i].time_at(rows, length)
            else:
                from repro.core.fpm import fft_flops
                t = float(fft_flops(rows, length)) / params.nominal_flops
            return t * _compute_multiplier(cfg, length, params)
        return max(seg_t(i, rows) for i, rows in members)

    info: dict = {"mode": mode, "groups": {}}
    picks: dict[int, PlanConfig] = {}
    for length, members in groups.items():
        cands = segment_candidate_configs(length, pad=pad)
        ranked = sorted(((cfg, group_time(cfg, members, length))
                         for cfg in cands), key=lambda kv: kv[1])
        info["groups"][str(length)] = [(c.to_dict(), float(t))
                                       for c, t in ranked]
        if mode == "estimate":
            picks[length] = ranked[0][0]
            continue
        # Pareto finalists: one per distinct program (pow2 fallbacks erase
        # radix differences), cheapest-estimate first, at most top_k.
        finalists, seen = [], set()
        for cfg, _ in ranked:
            key = (cfg.pad,) + _length_backend(cfg, length)
            if key not in seen:
                seen.add(key)
                finalists.append(cfg)
            if len(finalists) >= max(top_k, 1):
                break
        measured = _measure_length_group(
            finalists, rows=sum(r for _, r in members), length=length,
            n=n, dtype=dtype, rounds=reps)
        picks[length] = min(measured, key=measured.get)
        info.setdefault("group_measured", {})[str(length)] = [
            (c.to_dict(), float(t)) for c, t in measured.items()]

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
    homo_ranked = sorted(
        ((cfg, estimate_cost(cfg, n=n, d=d, pad_lengths=pad_lengths,
                             fpms=fpms, params=params, comm_bytes=comm_bytes))
         for cfg in candidate_configs(n, pad=pad, d=d, panels=panels)),
        key=lambda kv: kv[1])
    homo_cfg, est_homo = homo_ranked[0]
    homo = SegmentSchedule.homogeneous(homo_cfg, n, d, pad_lengths)
    info["ranked"] = [(c.to_dict(), float(t)) for c, t in homo_ranked]
    info["heterogeneous"] = {"schedule": hetero.to_dict(),
                             "est_s": float(est_hetero)}
    info["homogeneous"] = {"config": homo_cfg.to_dict(),
                           "est_s": float(est_homo)}

    if mode == "estimate":
        winner = homo if est_homo < est_hetero else hetero
    else:
        raced = measure_configs([hetero, homo], n, d=d,
                                pad_lengths=pad_lengths, dtype=dtype,
                                rounds=reps)
        winner = min(raced, key=raced.get)
        info["measured"] = [(s.describe(), float(t)) for s, t in raced.items()]
        info["time_s"] = float(raced[winner])
    info["chosen"] = ("heterogeneous" if len(winner.configs) > 1
                      else "homogeneous")
    info["schedule"] = winner.to_dict()
    return winner, info


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


def _measure_local_phase(cfg: PlanConfig, n: int, p: int, pad_len: int,
                         dtype, rounds: int) -> float:
    """Seconds of one *local* phase limb of the distributed pipeline: the
    row-FFT program one device runs on its (N/p, N) block, without the
    ``all_to_all``.  Subtracting two of these from the end-to-end time is
    what turns a distributed measurement into a *comm* sample."""
    import jax
    import jax.numpy as jnp
    from repro.core.pfft_dist import _local_fft  # lazy: core imports plan

    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((max(n // p, 1), n))
                     + 1j * rng.standard_normal((max(n // p, 1), n))
                     ).astype(dtype))
    fn = jax.jit(lambda b: _local_fft(b, n, padded=cfg.dist_padded,
                                      pad_len=pad_len, config=cfg,
                                      backend=None))
    jax.block_until_ready(fn(x))  # compile
    return min(_timed_min([(cfg, fn)], x, rounds).values())


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
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.pfft_dist import _hier_groups  # lazy: core imports plan

    intra, inter = _hier_groups(hosts, local)
    groups = intra if tier == "intra" else inter
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((n, n))
                     + 1j * rng.standard_normal((n, n))).astype(dtype))
    x = jax.device_put(x, NamedSharding(mesh, P(axis_name, None)))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis_name, None),),
                       out_specs=P(axis_name, None), check_vma=False)
    def ex(block):
        return jax.lax.all_to_all(block, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True,
                                  axis_index_groups=groups)

    jax.block_until_ready(ex(x))  # compile
    return min(_timed_min([(tier, ex)], x, rounds).values())


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
    per-config-min harness (``_timed_min``); the input is laid out
    row-sharded over ``axis_name`` first so placement cost is not billed
    to whichever config runs first.

    Items may be ``PlanConfig``s *or* ``SegmentSchedule``s —
    ``tune_dist_schedule`` races assembled heterogeneous (device-group)
    schedules against homogeneous finalists in one pot; a schedule runs
    with its own entry lengths (the uniform-length rule), so ``pad_len``
    applies only to bare configs.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.pfft_dist import pfft2_distributed  # lazy

    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((n, n))
                     + 1j * rng.standard_normal((n, n))).astype(dtype))
    x = jax.device_put(x, NamedSharding(mesh, P(axis_name, None)))
    pairs = []
    for item in configs:
        if isinstance(item, SegmentSchedule):
            kw = {"schedule": item}
        else:
            kw = {"config": item, "pad_len": pad_len}
        fn = jax.jit(functools.partial(pfft2_distributed, mesh=mesh,
                                       axis_name=axis_name, **kw))
        jax.block_until_ready(fn(x))  # compile
        pairs.append((item, fn))
    return _timed_min(pairs, x, rounds)


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
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    p = int(mesh.shape[axis_name])
    if n % p:
        raise ValueError(f"N={n} must be divisible by mesh axis "
                         f"{axis_name}={p}")
    if panels is None:
        panels = dist_panel_space(n, p)
    if params is None:
        params = CostParams.for_backend()
    comm_bytes = dist_comm_bytes(n, p)
    from repro.launch.mesh import mesh_host_shape  # lazy: launch is thin
    hosts, local = mesh_host_shape(mesh, axis_name)

    # ``batched`` shapes the segment dispatch plan; the dist pipeline has
    # one whole-block segment per device, so the knob is meaningless here
    # and would only burn finalist slots on identical programs.
    cands = [c for c in candidate_configs(n, pad=pad, d=None, panels=panels)
             if c.batched]
    if hosts > 1 and local > 1:
        # Host-major axis: the hierarchical exchange is a real program
        # alternative — race it as its own config dimension.  (The real
        # path exchanges padded half-spectrum panels flat-only.)
        import dataclasses
        cands += [dataclasses.replace(c, exchange="hier")
                  for c in cands if not c.real]
    ranked = sorted(
        ((cfg, estimate_cost(
            cfg, n=n, fpms=fpms, params=params, comm_bytes=comm_bytes,
            comm_time_s=dist_comm_time(n, p, params=params, hosts=hosts,
                                       exchange=cfg.exchange)))
         for cfg in cands),
        key=lambda kv: kv[1])
    info: dict = {
        "mode": mode,
        "ranked": [(cfg.to_dict(), float(c)) for cfg, c in ranked],
        "dist": {
            "devices": p,
            "hosts": int(hosts),
            "axis_name": axis_name,
            "comm_bytes": float(comm_bytes),
            # Both phases, like the measured sample it is judged against.
            "comm_time_est_s": float(2.0 * comm_phase_time(
                comm_bytes, params.interconnect_bytes_per_s,
                params.comm_latency_s)),
        },
    }

    if mode == "estimate":
        return ranked[0][0], info
    if p <= 1:
        # Nothing distributed to time: the 1-device all_to_all is a local
        # reshuffle and an end-to-end race would just re-measure the limb.
        info["measure_fallback"] = "1-device mesh: measure == estimate"
        return ranked[0][0], info

    # One finalist per distinct *distributed* program: the single-host
    # behavior key plus the panel count (panels change the collective
    # structure even when the local program is identical).
    finalists, seen = [], set()
    for cfg, _ in ranked:
        key = (_behavior_key(cfg, n, None, None), cfg.pipeline_panels)
        if key not in seen:
            seen.add(key)
            finalists.append(cfg)
        if len(finalists) >= max(top_k, 1):
            break
    try:
        measured = _measure_with_retry(
            lambda: measure_dist_configs(finalists, n, mesh, axis_name,
                                         pad_len=pad_len, dtype=dtype,
                                         rounds=reps),
            measure_retries)
    except Exception as err:
        if measure_retries <= 0:
            raise
        # Retries exhausted: serve the estimate ranking rather than fail
        # the caller (the self-healing re-planner must always get a plan).
        info["measure_fallback"] = (
            f"measurement failed after {measure_retries} retries: {err!r}")
        return ranked[0][0], info
    winner = min(measured, key=measured.get)
    info["measured"] = [(cfg.to_dict(), float(t)) for cfg, t in measured.items()]
    info["time_s"] = float(measured[winner])

    # Comm sample: end-to-end minus the two measured local phases of the
    # winning config.  Clamped at 0 — overlap (pipelined panels) can
    # legitimately hide comm below the subtraction's noise floor.
    eff_len = pad_len
    if eff_len is None:
        # The executor's own default, so the local probe runs the same
        # program the end-to-end measurement ran.
        from repro.core.pfft_dist import default_dist_pad_len
        eff_len = default_dist_pad_len(n, winner.dist_padded)
    try:
        local_s = _measure_with_retry(
            lambda: _measure_local_phase(winner, n, p, eff_len, dtype, reps),
            measure_retries)
    except Exception as err:
        if measure_retries <= 0:
            raise
        # The winner stands; only the comm sample is lost this round.
        info["dist"]["comm_sample_error"] = repr(err)
        return winner, info
    info["dist"]["local_phase_s"] = float(local_s)
    info["dist"]["comm_time_meas_s"] = float(
        max(measured[winner] - 2.0 * local_s, 0.0))
    info["dist"]["exchange"] = winner.exchange
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
            try:
                t = _measure_with_retry(
                    lambda tier=tier: _measure_tier_exchange(
                        mesh, axis_name, n, hosts, local, tier, dtype, reps),
                    measure_retries)
            except Exception as err:
                if measure_retries <= 0:
                    raise
                info["dist"]["tier_sample_error"] = repr(err)
                break
            samples.append({"tier": tier, "bytes": float(tier_bytes),
                            "msgs": int(msgs), "time_s": float(t)})
        if samples:
            info["dist"]["comm_samples"] = samples
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


def _measure_pfft3_local_pass(cfg: PlanConfig, n: int, r: int, c: int,
                              pad_len: int, dtype, rounds: int) -> float:
    """Seconds of one *local* axis pass of the pencil pipeline: the
    row-FFT program one device runs on its (N/r · N/c, N) pencil rows,
    without either ``all_to_all``.  Subtracting three of these from the
    end-to-end time turns a pencil measurement into a *comm* sample
    covering the transform's two exchange rounds."""
    import jax
    import jax.numpy as jnp
    from repro.core.pfft_dist import _local_fft  # lazy: core imports plan

    rows = max((n // max(r, 1)) * (n // max(c, 1)), 1)
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((rows, n))
                     + 1j * rng.standard_normal((rows, n))).astype(dtype))
    fn = jax.jit(lambda b: _local_fft(b, n, padded=cfg.dist_padded,
                                      pad_len=pad_len, config=cfg,
                                      backend=None))
    jax.block_until_ready(fn(x))  # compile
    return min(_timed_min([(cfg, fn)], x, rounds).values())


def measure_pfft3_configs(configs: Sequence[PlanConfig], n: int, mesh,
                          axis_names: Sequence[str] = ("fft_r", "fft_c"), *,
                          pad_len: int | None = None, dtype=np.complex64,
                          rounds: int = 3) -> dict[PlanConfig, float]:
    """End-to-end on-device seconds of ``pfft3_pencil`` per config.

    The 3-D sibling of ``measure_dist_configs``: times the full pencil
    pipeline — three local passes, both all_to_all rounds, pipelined
    panels, the final global transpose — on the caller's actual 2-D
    ``Mesh``.  Same shuffled-interleaved per-config-min harness
    (``_timed_min``); the cube is laid out pencil-sharded over
    ``axis_names`` first so placement cost is not billed to whichever
    config runs first.  One call races one *orientation* — callers
    (``tune_pfft3``) merge per-orientation races themselves.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.pfft3d import pfft3_pencil  # lazy

    axes = tuple(axis_names)
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((n, n, n))
                     + 1j * rng.standard_normal((n, n, n))).astype(dtype))
    x = jax.device_put(x, NamedSharding(mesh, P(axes[0], axes[1], None)))
    pairs = []
    for cfg in configs:
        fn = jax.jit(functools.partial(pfft3_pencil, mesh=mesh,
                                       axis_names=axes, config=cfg,
                                       pad_len=pad_len))
        jax.block_until_ready(fn(x))  # compile
        pairs.append((cfg, fn))
    return _timed_min(pairs, x, rounds)


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
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
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
        # Some orientation puts a host-major axis under the row exchange:
        # race the hierarchical form as its own config dimension.
        import dataclasses
        cands += [dataclasses.replace(cfg, exchange="hier")
                  for cfg in cands if not cfg.real]
    # Orientation space: which mesh axis plays "row".  On a square mesh
    # (or single host) the transposed program is identical.
    if mesh is not None and r != c:
        orientations = [axes0, (axes0[1], axes0[0])]
    elif mesh is not None:
        orientations = [axes0]
    else:
        orientations = [None]

    def est(cfg: PlanConfig, waxes) -> float:
        if waxes is None:
            r_o, c_o, h_o = 1, 1, 1
        else:
            r_o = int(mesh.shape[waxes[0]])
            c_o = int(mesh.shape[waxes[1]])
            # Hosts ride the orientation's row axis (the only exchange
            # the hierarchical form applies to); a non-host-major row
            # axis prices — and runs — as flat.
            h_o = host_shapes[waxes[0]][0]
        return estimate_pfft3_cost(cfg, n=n, r=r_o, c=c_o, params=params,
                                   pad_len=pad_len, hosts=h_o)

    ranked = sorted(((cfg, waxes, est(cfg, waxes))
                     for cfg in cands for waxes in orientations),
                    key=lambda kv: kv[2])
    info: dict = {
        "mode": mode,
        "ranked": [(cfg.to_dict(),
                    list(waxes) if waxes is not None else None, float(t))
                   for cfg, waxes, t in ranked],
        "pfft3": {
            "r": r, "c": c,
            "hosts": int(host_shapes.get(axes0[0], (1, 1))[0]),
            "axis_names": list(axes0) if mesh is not None else None,
            "comm_bytes": float(comm_bytes),
            "comm_time_est_s": float(
                sum(comm_phase_time(b, params.interconnect_bytes_per_s,
                                    params.comm_latency_s)
                    for b in (pfft3_comm_bytes(n, c),
                              pfft3_comm_bytes(n, r)))),
        },
    }

    if mode == "estimate":
        cfg, waxes, _ = ranked[0]
        info["orientation"] = list(waxes) if waxes is not None else None
        return cfg, waxes, info
    if r * c <= 1 and mesh is not None:
        info["measure_fallback"] = "1-device mesh: measure == estimate"
        cfg, waxes, _ = ranked[0]
        info["orientation"] = list(waxes) if waxes is not None else None
        return cfg, waxes, info

    # One finalist per distinct *pencil* program: single-host behavior key
    # plus panel count plus orientation (orientation changes which round
    # crosses which communicator even when the local program is the same).
    finalists, seen = [], set()
    for cfg, waxes, _ in ranked:
        key = (_behavior_key(cfg, n, None, None), cfg.pipeline_panels, waxes)
        if key not in seen:
            seen.add(key)
            finalists.append((cfg, waxes))
        if len(finalists) >= max(top_k, 1):
            break

    def run_races() -> dict:
        merged: dict = {}
        if mesh is None:
            # Single host: time the production single-host program.
            import jax
            import jax.numpy as jnp
            from repro.core.pfft3d import pfft3_lb  # lazy

            rng = np.random.default_rng(0)
            x = jnp.asarray((rng.standard_normal((n, n, n))
                             + 1j * rng.standard_normal((n, n, n))
                             ).astype(dtype))
            pairs = []
            for cfg, _ in finalists:
                fn = jax.jit(lambda m, c=cfg: pfft3_lb(m, 1, config=c))
                jax.block_until_ready(fn(x))  # compile
                pairs.append((cfg, fn))
            for cfg, t in _timed_min(pairs, x, reps).items():
                merged[(cfg, None)] = t
            return merged
        for waxes in orientations:
            group = [cfg for cfg, wa in finalists if wa == waxes]
            if not group:
                continue
            times = measure_pfft3_configs(group, n, mesh, waxes,
                                          pad_len=pad_len, dtype=dtype,
                                          rounds=reps)
            for cfg, t in times.items():
                merged[(cfg, waxes)] = t
        return merged

    try:
        measured = _measure_with_retry(run_races, measure_retries)
    except Exception as err:
        if measure_retries <= 0:
            raise
        info["measure_fallback"] = (
            f"measurement failed after {measure_retries} retries: {err!r}")
        cfg, waxes, _ = ranked[0]
        info["orientation"] = list(waxes) if waxes is not None else None
        return cfg, waxes, info
    wcfg, waxes = min(measured, key=measured.get)
    info["measured"] = [(cfg.to_dict(),
                         list(wa) if wa is not None else None, float(t))
                        for (cfg, wa), t in measured.items()]
    info["time_s"] = float(measured[(wcfg, waxes)])
    info["orientation"] = list(waxes) if waxes is not None else None

    # Comm sample: end-to-end minus the three measured local passes of
    # the winning program.  Clamped at 0 — pipelined panels can hide comm
    # below the subtraction's noise floor.
    eff_len = pad_len
    if eff_len is None:
        from repro.core.pfft_dist import default_dist_pad_len
        eff_len = default_dist_pad_len(n, wcfg.dist_padded)
    try:
        local_s = _measure_with_retry(
            lambda: _measure_pfft3_local_pass(wcfg, n, r, c, eff_len, dtype,
                                              reps),
            measure_retries)
    except Exception as err:
        if measure_retries <= 0:
            raise
        info["pfft3"]["comm_sample_error"] = repr(err)
        return wcfg, waxes, info
    info["pfft3"]["local_pass_s"] = float(local_s)
    info["pfft3"]["comm_time_meas_s"] = float(
        max(measured[(wcfg, waxes)] - 3.0 * local_s, 0.0))
    info["pfft3"]["exchange"] = wcfg.exchange
    return wcfg, waxes, info


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
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    from repro.core.fpm import fft_flops
    from repro.core.pfft_large import four_step_factors  # lazy

    n1, n2 = four_step_factors(n, n1=n1, n2=n2)
    if params is None:
        params = CostParams.for_backend()

    radices: list[int | None] = [None]
    if _is_pow2(n1) or _is_pow2(n2):
        radices += [2, 4]
    cands = [PlanConfig(radix=rad) for rad in radices]

    def est(cfg: PlanConfig) -> float:
        compute = (
            float(fft_flops(n1, n2)) / params.nominal_flops
            * _compute_multiplier(cfg, n2, params)
            + float(fft_flops(n2, n1)) / params.nominal_flops
            * _compute_multiplier(cfg, n1, params))
        itemsize = np.dtype(dtype).itemsize
        traffic = 4.0 * n * itemsize / params.hbm_bytes_per_s
        return compute + traffic + 2.0 * params.dispatch_overhead_s

    ranked = sorted(((cfg, est(cfg)) for cfg in cands), key=lambda kv: kv[1])
    info: dict = {
        "mode": mode,
        "ranked": [(cfg.to_dict(), float(t)) for cfg, t in ranked],
        "four_step": {"n1": int(n1), "n2": int(n2)},
    }
    if mode == "estimate":
        return ranked[0][0], info

    import jax
    import jax.numpy as jnp
    from repro.core.pfft_large import pfft1_large_apply  # lazy

    finalists, seen = [], set()
    for cfg, _ in ranked:
        key = (_length_backend(cfg, n1), _length_backend(cfg, n2))
        if key not in seen:
            seen.add(key)
            finalists.append(cfg)
        if len(finalists) >= max(top_k, 1):
            break
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal(n)
                     + 1j * rng.standard_normal(n)).astype(dtype))
    pairs = []
    for cfg in finalists:
        fn = jax.jit(lambda v, c=cfg: pfft1_large_apply(v, config=c, n1=n1,
                                                        n2=n2))
        jax.block_until_ready(fn(x))  # compile
        pairs.append((cfg, fn))
    measured = _timed_min(pairs, x, reps)
    winner = min(measured, key=measured.get)
    info["measured"] = [(cfg.to_dict(), float(t))
                        for cfg, t in measured.items()]
    info["time_s"] = float(measured[winner])
    return winner, info


# ------------------------------------------------------------------- real

def _require_real_dtype(dtype) -> np.dtype:
    """Validate a real-pipeline input dtype; returns the np.dtype."""
    dt = np.dtype(dtype)
    if dt not in (np.dtype(np.float32), np.dtype(np.float64)):
        raise ValueError(
            f"the real pipeline tunes float32/float64 inputs, got {dt.name}")
    return dt


def _real_candidates(cands: Sequence[PlanConfig], n: int
                     ) -> list[PlanConfig]:
    """The real-flagged twins of a complex candidate list (czt dropped —
    the real pipeline has no Bluestein form)."""
    import dataclasses
    return _drop_excluded([dataclasses.replace(c, real=True) for c in cands
                           if c.pad != "czt"], n)


def _family_finalists(ranked, n: int, d, pad_lengths, top_k: int
                      ) -> list[PlanConfig]:
    """Distinct-program finalists that always include the best candidate
    of *each* family (real and complex), so measure mode genuinely races
    real-vs-complex rather than burning every slot on one side."""
    finalists, seen = [], set()
    for cfg, _ in ranked:
        key = _behavior_key(cfg, n, d, pad_lengths)
        if key not in seen:
            seen.add(key)
            finalists.append(cfg)
        if len(finalists) >= max(top_k, 1):
            break
    for want_real in (True, False):
        if not any(c.real == want_real for c in finalists):
            best = next((c for c, _ in ranked if c.real == want_real), None)
            if best is not None:
                finalists.append(best)
    return finalists


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
    import jax
    import jax.numpy as jnp
    from repro.core.pfft import _pfft_limb, _rpfft_limb  # lazy

    dt = _require_real_dtype(dtype)
    ctype = np.complex64 if dt == np.dtype(np.float32) else np.complex128
    nh = n // 2 + 1
    d_eff = np.asarray(d) if d is not None else np.array([n], dtype=np.int64)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, n)).astype(dt))
    pairs = []
    for cfg in configs:
        if cfg.real:
            fn = jax.jit(lambda m, c=cfg: _rpfft_limb(
                m, d_eff, pad_lengths=pad_lengths, config=c))
        else:
            fn = jax.jit(lambda m, c=cfg: _pfft_limb(
                m.astype(ctype), d_eff, pad_lengths=pad_lengths,
                config=c)[:, :nh])
        jax.block_until_ready(fn(x))  # compile
        pairs.append((cfg, fn))
    return _timed_min(pairs, x, rounds)


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
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    _require_real_dtype(dtype)
    if pad == "czt":
        raise ValueError("the real pipeline has no Bluestein form")
    if d is not None:
        d = np.asarray(d)
    if params is None:
        params = CostParams.for_backend()

    complex_cands = candidate_configs(n, pad=pad, d=d)
    cands = _real_candidates(complex_cands, n) + complex_cands
    ranked = sorted(
        ((cfg, estimate_cost(cfg, n=n, d=d, pad_lengths=pad_lengths,
                             fpms=fpms, params=params))
         for cfg in cands),
        key=lambda kv: kv[1])
    info: dict = {
        "mode": mode,
        "ranked": [(cfg.to_dict(), float(c)) for cfg, c in ranked],
    }

    if mode == "estimate":
        winner = ranked[0][0]
    else:
        finalists = _family_finalists(ranked, n, d, pad_lengths, top_k)
        measured = measure_rfft_configs(finalists, n, d=d,
                                        pad_lengths=pad_lengths, dtype=dtype,
                                        rounds=reps)
        winner = min(measured, key=measured.get)
        info["measured"] = [(cfg.to_dict(), float(t))
                            for cfg, t in measured.items()]
        info["time_s"] = float(measured[winner])
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
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.pfft_dist import (pfft2_distributed,  # lazy
                                      rpfft2_distributed)

    dt = _require_real_dtype(dtype)
    ctype = np.complex64 if dt == np.dtype(np.float32) else np.complex128
    nh = n // 2 + 1
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, n)).astype(dt))
    x = jax.device_put(x, NamedSharding(mesh, P(axis_name, None)))
    pairs = []
    for cfg in configs:
        if cfg.real:
            fn = jax.jit(functools.partial(rpfft2_distributed, mesh=mesh,
                                           axis_name=axis_name, config=cfg,
                                           pad_len=pad_len))
        else:
            fn = jax.jit(lambda m, c=cfg: pfft2_distributed(
                m.astype(ctype), mesh=mesh, axis_name=axis_name, config=c,
                pad_len=pad_len)[:, :nh])
        jax.block_until_ready(fn(x))  # compile
        pairs.append((cfg, fn))
    return _timed_min(pairs, x, rounds)


def _measure_local_real_phases(cfg: PlanConfig, n: int, p: int, pad_len: int,
                               dtype, rounds: int) -> float:
    """Combined seconds of the real pipeline's two *local* phase programs
    (rfft on the (N/p, N) row block + complex FFT on the (hc/p, N)
    spectral block) — the subtraction term that turns an end-to-end real
    measurement into a comm sample, mirroring ``_measure_local_phase``.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.pfft import _group_row_rffts, _group_row_ffts  # lazy
    from repro.plan.cost import halfspec_cols

    dt = _require_real_dtype(dtype)
    ctype = np.complex64 if dt == np.dtype(np.float32) else np.complex128
    hc = halfspec_cols(n, p)
    rng = np.random.default_rng(0)
    x1 = jnp.asarray(rng.standard_normal((max(n // p, 1), n)).astype(dt))
    x2 = jnp.asarray((rng.standard_normal((max(hc // p, 1), n))
                      + 1j * rng.standard_normal((max(hc // p, 1), n))
                      ).astype(ctype))
    length = pad_len if cfg.pad == "fpm" else n
    fn1 = jax.jit(lambda b: _group_row_rffts(b, length, n, cfg, None))
    fn2 = jax.jit(lambda b: _group_row_ffts(b, length, n, cfg, None))
    jax.block_until_ready(fn1(x1))  # compile
    jax.block_until_ready(fn2(x2))
    t1 = min(_timed_min([(cfg, fn1)], x1, rounds).values())
    t2 = min(_timed_min([(cfg, fn2)], x2, rounds).values())
    return t1 + t2


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
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    _require_real_dtype(dtype)
    if pad == "czt":
        raise ValueError("the real pipeline has no Bluestein form")
    p = int(mesh.shape[axis_name])
    if n % p:
        raise ValueError(f"N={n} must be divisible by mesh axis "
                         f"{axis_name}={p}")
    if panels is None:
        panels = dist_panel_space(n, p)
    if params is None:
        params = CostParams.for_backend()
    comm_complex = dist_comm_bytes(n, p)
    comm_real = dist_comm_bytes(n, p, real=True)

    complex_cands = [c for c in candidate_configs(n, pad=pad, d=None,
                                                  panels=panels) if c.batched]
    real_cands = [c for c in _real_candidates(complex_cands, n)
                  if not c.fused and c.pipeline_panels == 1]
    ranked = sorted(
        ((cfg, estimate_cost(cfg, n=n, fpms=fpms, params=params,
                             comm_bytes=comm_real if cfg.real
                             else comm_complex))
         for cfg in real_cands + complex_cands),
        key=lambda kv: kv[1])
    info: dict = {
        "mode": mode,
        "ranked": [(cfg.to_dict(), float(c)) for cfg, c in ranked],
        "dist": {
            "devices": p,
            "axis_name": axis_name,
            "comm_bytes_complex": float(comm_complex),
            "comm_bytes_real": float(comm_real),
            "comm_ratio_real": (float(comm_real / comm_complex)
                                if comm_complex else 0.0),
        },
    }

    def finish(winner: PlanConfig) -> tuple[SegmentSchedule, dict]:
        info["chosen_path"] = "real" if winner.real else "complex"
        info["dist"]["comm_bytes"] = float(comm_real if winner.real
                                           else comm_complex)
        d = np.full(p, n // p, dtype=np.int64) if p > 0 else None
        schedule = SegmentSchedule.homogeneous(winner, n, d)
        info["schedule"] = schedule.to_dict()
        return schedule, info

    if mode == "estimate":
        return finish(ranked[0][0])
    if p <= 1:
        info["measure_fallback"] = "1-device mesh: measure == estimate"
        return finish(ranked[0][0])

    finalists = _family_finalists(ranked, n, None, None, top_k)
    try:
        measured = _measure_with_retry(
            lambda: measure_rfft_dist_configs(finalists, n, mesh, axis_name,
                                              pad_len=pad_len, dtype=dtype,
                                              rounds=reps),
            measure_retries)
    except Exception as err:
        if measure_retries <= 0:
            raise
        info["measure_fallback"] = (
            f"measurement failed after {measure_retries} retries: {err!r}")
        return finish(ranked[0][0])
    winner = min(measured, key=measured.get)
    info["measured"] = [(cfg.to_dict(), float(t))
                        for cfg, t in measured.items()]
    info["time_s"] = float(measured[winner])

    eff_len = pad_len
    if eff_len is None:
        from repro.core.pfft_dist import default_dist_pad_len
        eff_len = default_dist_pad_len(n, winner.dist_padded)
    try:
        if winner.real:
            local_s = _measure_with_retry(
                lambda: _measure_local_real_phases(winner, n, p, eff_len,
                                                   dtype, reps),
                measure_retries)
        else:
            ctype = (np.complex64 if np.dtype(dtype) == np.dtype(np.float32)
                     else np.complex128)
            local_s = 2.0 * _measure_with_retry(
                lambda: _measure_local_phase(winner, n, p, eff_len, ctype,
                                             reps),
                measure_retries)
    except Exception as err:
        if measure_retries <= 0:
            raise
        info["dist"]["comm_sample_error"] = repr(err)
        return finish(winner)
    info["dist"]["local_phase_s"] = float(local_s)
    info["dist"]["comm_time_meas_s"] = float(
        max(measured[winner] - local_s, 0.0))
    return finish(winner)


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

    def seg_time(i: int, cfg: PlanConfig, length: int) -> float:
        if fpms is not None:
            t = fpms[i].time_at(n_loc, length)
        else:
            from repro.core.fpm import fft_flops
            t = float(fft_flops(n_loc, length)) / params.nominal_flops
        return t * _compute_multiplier(cfg, length, params)

    cfgs = []
    for i in range(p):
        length = n
        if pad_lengths is not None and int(pad_lengths[i]) > n:
            length = int(pad_lengths[i])
        cands = segment_candidate_configs(length, pad=pad)
        cfgs.append(min(cands, key=lambda c: seg_time(i, c, length)))
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
    overhead) against the homogeneous winner.  ``mode="measure"`` races
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
    if params is None:
        params = CostParams.for_backend()
    d = np.full(p, n // p, dtype=np.int64) if p > 0 else None
    homo = SegmentSchedule.homogeneous(cfg, n, d, pad_lengths)
    hetero = grouped_dist_schedule(n, p, pad_lengths=pad_lengths, fpms=fpms,
                                   pad=pad, params=params)
    if hetero is None:
        info["chosen"] = "homogeneous"
        info["schedule"] = homo.to_dict()
        return homo, info

    fpms_dev = fpms if fpms is not None and fpms.p == p else None
    comm_bytes = dist_comm_bytes(n, p)
    est_hetero = estimate_grouped_cost(hetero, fpms=fpms_dev, params=params,
                                       comm_bytes=comm_bytes)
    est_homo = estimate_grouped_cost(homo, fpms=fpms_dev, params=params,
                                     comm_bytes=comm_bytes)
    info["heterogeneous"] = {"schedule": hetero.to_dict(),
                             "est_s": float(est_hetero)}
    info["homogeneous"] = {"config": cfg.to_dict(), "est_s": float(est_homo)}

    if mode == "estimate" or "measure_fallback" in info:
        winner = hetero if est_hetero < est_homo else homo
    else:
        try:
            raced = _measure_with_retry(
                lambda: measure_dist_configs([homo, hetero], n, mesh,
                                             axis_name, dtype=dtype,
                                             rounds=reps),
                measure_retries)
        except Exception as err:
            if measure_retries <= 0:
                raise
            info["measure_fallback"] = (
                f"grouped race failed after {measure_retries} retries: "
                f"{err!r}")
            winner = hetero if est_hetero < est_homo else homo
        else:
            winner = min(raced, key=raced.get)
            info["grouped_measured"] = [(s.describe(), float(t))
                                        for s, t in raced.items()]
            info["time_s"] = float(raced[winner])
    info["chosen"] = ("heterogeneous" if len(winner.configs) > 1
                      else "homogeneous")
    info["schedule"] = winner.to_dict()
    return winner, info
