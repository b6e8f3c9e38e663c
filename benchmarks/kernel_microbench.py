"""Kernel-layer microbenchmarks -> BENCH_kernels.json (and wisdom).

    PYTHONPATH=src python -m benchmarks.kernel_microbench \\
        [--quick] [--out F] [--wisdom W]

Four comparisons, one JSON record each (plus structural facts the
acceptance checks assert on):

  rowfft       XLA's library FFT vs the Pallas row kernel on the same
               rows; records the kernel's four-step split
               (``split_length``).
  fused        unfused (fft_rows_op + transpose_op, intermediate matrix)
               vs fused ``fft_rows_transpose_op`` (one dispatch).
  segments     looped per-segment ``segment_row_ffts`` vs the batched
               one-dispatch-per-distinct-pad-length path; records the
               dispatch counts from ``plan_segment_batches``.
  planner      the full ``PlanConfig`` sweep (every variant the tuner can
               pick) vs the estimate-planned config — records whether the
               cost model's pick lands within the measured envelope
               (``within_best_pct`` / ``not_worst``).
  schedule     heterogeneous per-segment planning (one slow + p-1 fast
               FPMs): the per-segment ``tune_schedule`` pick vs the best
               homogeneous config — records the distinct config count,
               the makespan-estimate delta, and the measured limb times
               of both (hetero schedule wisdom is recorded under the
               same key ``plan_pfft`` would look up).
  dist         distributed measure tuning on a mesh over every visible
               device: ``tune_dist_config`` races finalists through the
               full ``pfft2_distributed`` pipeline and the record carries
               the *measured-vs-estimated comm delta* (the number the
               cost model's interconnect constants are judged — and
               calibrated — by).  On a 1-device host the sweep records
               the estimate-fallback facts; run under
               ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
               (the CI dist job does) for a real comm sample.
  hetero-dist  grouped-vs-homogeneous device-group programs: a synthetic
               mixed-pad fleet drives ``grouped_dist_schedule`` and both
               programs race end-to-end through ``pfft2_distributed``;
               the record carries the grouped-vs-homogeneous makespan
               delta and the measured winner warms the same v3 topology
               key ``plan_pfft(mesh=..., method="fpm-pad")`` consults.
  rfft         the real-input half-spectrum pipeline vs the upcast-and-
               crop complex fallback: interleaved wall-time race of both
               limbs, the structural p=4 comm-bytes delta (half-spectrum
               panels vs full panels), and the measure-tuned family pick
               (wisdom-warmed under the ``rfft-lb`` keys ``plan_pfft``
               looks up).  On a multi-device host an ``rfft-dist`` record
               races both families end to end through the distributed
               pipelines and carries the measured comm sample.
  pfft3        pencil-vs-slab 3-D decomposition on an r x c mesh over
               every visible device: ``tune_pfft3(mode="measure")`` races
               config x panel x *orientation* finalists through the
               two-exchange pencil pipeline, then the winner races the
               one-axis slab program (three exchanges) end to end on the
               same devices — the record carries the pencil-vs-slab
               delta and the measured comm sample, and the winner
               (orientation included) warms the same v3 2-D-topology key
               ``plan_pfft3(mesh=...)`` looks up.  A 1-device host
               records the estimate-fallback facts.
  multihost    hierarchical-vs-flat exchange on an emulated hosts x local
               host-major mesh (``make_fft_mesh(hosts=...)`` over the
               forced CPU devices): ``tune_dist_config(mode="measure")``
               races both exchange forms end to end, the record carries
               the explicit hier-vs-flat delta, the per-tier comm
               samples (one grouped all_to_all per tier), and — when a
               wisdom store is being warmed — the two interconnect
               tiers ``fit_cost_params`` recovers from those samples.
               The winner lands under the host-count topology digest
               (``2hx4x...``), so a warmed store serves later multi-host
               plans with zero re-measurement (CI asserts it).  A
               sub-4-device host records the structural two-tier
               byte-accounting facts instead.

Every record is labeled with the backend it was measured on and whether
the Pallas kernels ran in interpret mode.  A ``--sweeps`` subset merges:
records of benches not being rerun are carried into the new file intact,
and a cpu (interpret-mode) run refuses to replace accelerator-tagged
records of the benches it *is* rerunning (``--force`` overrides —
interpreter numbers say nothing about hardware and must not masquerade
as it).

``--wisdom W`` writes each benched size's best *measured* config into the
wisdom store ``W`` (keyed exactly as ``plan_pfft`` keys its lookups), so a
measured benchmark run warms every later planning session — FFTW's
wisdom lifecycle; CI asserts the round trip.

On this CPU container the Pallas kernels run in interpret mode, so the
absolute times are not TPU times — the JSON exists to start the perf
trajectory and to pin the structural wins (pass counts, dispatch counts)
that carry to hardware.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np
import jax.numpy as jnp

from benchmarks.common import signal, time_fn
from repro.core.fpm import FPMSet, SpeedFunction
from repro.core.pfft import _pfft_limb, plan_segment_batches, segment_row_ffts
from repro.core.partition import lb_partition
from repro.kernels.fft.kernel import split_length
from repro.kernels.fft.ops import fft_rows_op
from repro.kernels.fused.ops import fft_rows_transpose_op
from repro.kernels.transpose.ops import transpose_op
from repro.plan import (CostParams, PlanConfig, SegmentSchedule,
                        candidate_configs, dist_comm_bytes, dist_panel_space,
                        estimate_cost, estimate_grouped_cost,
                        estimate_schedule_cost, exchange_time,
                        grouped_dist_schedule,
                        measure_configs, measure_dist_configs,
                        partition_digest, record_wisdom, topology_digest,
                        tune_config, tune_dist_config, tune_schedule,
                        wisdom_key)
from repro.plan.cost import V5E_KIND

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "BENCH_kernels.json")


def _rows_signal(rows: int, n: int, seed: int = 1) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.standard_normal((rows, n))
                        + 1j * rng.standard_normal((rows, n))
                        ).astype(np.complex64))


def bench_rowfft(sizes, rows: int) -> list[dict]:
    recs = []
    for n in sizes:
        x = _rows_signal(rows, n)
        n1, n2 = split_length(n)
        for name, fn in (("xla", lambda x: jnp.fft.fft(x, axis=-1)),
                         ("pallas", fft_rows_op)):
            recs.append({
                "bench": "rowfft",
                "n": int(n),
                "rows": int(rows),
                "impl": name,
                "n1": n1,
                "n2": n2,
                "time_s": time_fn(fn, x),
            })
    return recs


def bench_fused(sizes) -> list[dict]:
    recs = []
    for n in sizes:
        m = signal(n, seed=2)

        def unfused(m):
            return transpose_op(fft_rows_op(m))

        for name, fn in (("unfused", unfused), ("fused", fft_rows_transpose_op)):
            t = time_fn(fn, m)
            recs.append({
                "bench": "fused",
                "n": int(n),
                "variant": name,
                "dispatches_per_phase": 2 if name == "unfused" else 1,
                "time_s": t,
            })
    return recs


def bench_segments(n: int, p: int, pad_to: int) -> list[dict]:
    m = signal(n, seed=3)
    d = lb_partition(n, p).d
    pads = np.array([pad_to if i % 2 else n for i in range(p)], dtype=np.int64)
    plan = plan_segment_batches(d, pads, n)
    recs = []
    for name, batched in (("looped", False), ("batched", True)):
        cfg = PlanConfig(batched=batched, pad="fpm")
        t = time_fn(lambda m=m, c=cfg: segment_row_ffts(
            m, d, pad_lengths=pads, config=c))
        recs.append({
            "bench": "segments",
            "n": int(n),
            "p": int(p),
            "distinct_pad_lengths": len(plan),
            "dispatches": len(plan) if batched else int((np.asarray(d) > 0).sum()),
            "variant": name,
            "time_s": t,
        })
    return recs


def bench_planner(sizes, p: int, wisdom_path: str | None = None) -> list[dict]:
    """Time the full PlanConfig sweep, compare the estimate-planned pick
    against the measured envelope, and (optionally) warm the wisdom store
    with each size's best measured config."""
    import jax
    backend = jax.default_backend()
    recs = []
    for n in sizes:
        d = lb_partition(n, p).d
        # measure_configs is the tuner's own interleaved-min harness (a
        # per-config timing block would rank this host's jitter instead);
        # 40 rounds so per-config mins converge below the few-percent gap
        # the acceptance comparison cares about.
        times = measure_configs(candidate_configs(n, d=d), n, d=d, rounds=40)
        for cfg, t in times.items():
            recs.append({"bench": "planner", "n": int(n), "p": int(p),
                         "role": "sweep", "config": cfg.describe(),
                         "time_s": t})
        est_cfg, _ = tune_config(n, d=d, mode="estimate")
        t_est = times[est_cfg]
        best_cfg = min(times, key=times.get)
        t_best, t_worst = times[best_cfg], max(times.values())
        recs.append({
            "bench": "planner", "n": int(n), "p": int(p),
            "role": "estimate-planned", "config": est_cfg.describe(),
            "time_s": t_est,
            "best_config": best_cfg.describe(), "best_s": t_best,
            "worst_s": t_worst,
            "within_best_pct": 100.0 * (t_est / t_best - 1.0),
            "not_worst": bool(t_est <= t_worst),
        })
        if wisdom_path:
            key = wisdom_key(n=n, dtype="complex64", p=p, method="lb",
                             backend=backend)
            record_wisdom(wisdom_path, key, best_cfg, mode="measure",
                          time_s=t_best,
                          extra={"origin": "kernel_microbench"})
    return recs


def bench_schedule(n: int, p: int, wisdom_path: str | None = None
                   ) -> list[dict]:
    """Heterogeneous per-segment planning vs the best homogeneous config.

    A synthetic one-slow/(p-1)-fast FPM set — the ISSUE-3 acceptance
    scenario — whose partition *and* pad lengths are derived exactly the
    way ``plan_pfft(method="fpm-pad")`` derives them (``partition_rows``
    + ``fpm_pad_lengths``), so the recorded wisdom key is the one a
    ``plan_pfft`` call with the same FPMSet looks up.  The fast
    processors' speed peaks at the next pow2 (padding wins for them);
    the slow processor's is flat (padding only adds flops), yielding
    mixed effective lengths.  Estimates use the accelerator cost
    constants (the per-segment choice is about *which* variants differ,
    which interpret-mode CPU constants collapse); measured limb times
    use this host.  The makespan-estimate delta and the distinct-config
    count are the structural facts CI pins.
    """
    from repro.core.partition import partition_rows
    from repro.plan.pads import fpm_pad_lengths

    npow2 = 1 << int(np.ceil(np.log2(n + 1)))
    xs = np.array(sorted({1, max(n // 2, 1), n}))
    ys = np.array(sorted({n, npow2, 2 * npow2}))
    fast = np.tile([1e9, 4e9, 1e9], (len(xs), 1))
    slow = np.full((len(xs), len(ys)), 2.5e8)
    fpms = FPMSet([SpeedFunction(xs, ys, slow if i == 0 else fast,
                                 name=f"P{i}") for i in range(p)])
    part = partition_rows(n, fpms, 0.05)
    d = part.d
    pads = fpm_pad_lengths(fpms, d, n)
    params = CostParams.for_backend("tpu", device_kind=V5E_KIND)

    sched, info = tune_schedule(n, d=d, pad_lengths=pads, fpms=fpms,
                                mode="estimate", pad="fpm", params=params)
    # The *assembled* heterogeneous estimate, not the winner's (the winner
    # is already the argmin of this very comparison — recording it would
    # make hetero_not_worse_est tautologically true).
    est_hetero = (info["heterogeneous"]["est_s"] if "heterogeneous" in info
                  else estimate_schedule_cost(sched, fpms=fpms, params=params))
    homo_cfg, est_homo = min(
        ((c, estimate_cost(c, n=n, d=d, pad_lengths=pads, fpms=fpms,
                           params=params))
         for c in candidate_configs(n, pad="fpm", d=d)),
        key=lambda kv: kv[1])

    m = signal(n, seed=4)
    t_hetero = time_fn(lambda m=m: _pfft_limb(m, d, schedule=sched))
    t_homo = time_fn(lambda m=m, c=homo_cfg: _pfft_limb(
        m, d, pad_lengths=pads, config=c))
    rec = {
        "bench": "schedule", "n": int(n), "p": int(p),
        "schedule": sched.describe(),
        "distinct_configs": len(sched.configs),
        "dispatch_groups": len(sched.batch_groups()),
        "homogeneous_config": homo_cfg.describe(),
        "makespan_est_hetero_s": float(est_hetero),
        "makespan_est_homo_s": float(est_homo),
        "makespan_est_delta_s": float(est_homo - est_hetero),
        "hetero_not_worse_est": bool(est_hetero <= est_homo),
        "time_hetero_s": t_hetero,
        "time_homo_s": t_homo,
        "chosen": info["chosen"],
    }
    if wisdom_path:
        # Record what this host actually measured fastest — the estimate
        # deliberately used accelerator constants, so on CPU the
        # homogeneous library config can beat the kernel-bearing
        # schedule; wisdom must never serve a measured-slower plan.
        import jax
        from repro.plan import SegmentSchedule
        winner, t_best = ((sched, t_hetero) if t_hetero <= t_homo else
                          (SegmentSchedule.homogeneous(homo_cfg, n, d, pads),
                           t_homo))
        key = wisdom_key(n=n, dtype="complex64", p=p, method="fpm-pad",
                         backend=jax.default_backend(),
                         detail=partition_digest(d, pads))
        record_wisdom(wisdom_path, key, winner, mode="measure",
                      time_s=t_best, extra={"origin": "kernel_microbench"})
    return [rec]


def bench_dist(sizes, wisdom_path: str | None = None) -> list[dict]:
    """Distributed measure tuning over every visible device.

    For each size, ``tune_dist_config(mode="measure")`` races the top
    finalists through the full ``pfft2_distributed`` pipeline (both
    all_to_all phases) on a 1-D mesh over all local devices, and the
    record pins the measured-vs-estimated comm delta — the evidence the
    interconnect constants are calibrated from.  Wisdom entries land
    under the same per-topology v3 key ``plan_pfft(mesh=...)`` looks up,
    comm sample included, so a benchmark run warms distributed planning
    exactly like it warms the single-host kinds.
    """
    import jax
    from repro.launch.mesh import make_fft_mesh

    p = jax.device_count()
    mesh = make_fft_mesh(p)
    backend = jax.default_backend()
    recs = []
    for n in sizes:
        if n % p:
            continue
        panels = dist_panel_space(n, p)
        cfg, info = tune_dist_config(n, mesh, "fft", mode="measure",
                                     panels=panels)
        dist = info["dist"]
        measured = "measure_fallback" not in info
        rec = {
            "bench": "dist", "n": int(n), "devices": p,
            "topology": topology_digest(mesh, "fft", panels=panels),
            "config": cfg.describe(),
            "comm_bytes": dist["comm_bytes"],
            "comm_time_est_s": dist["comm_time_est_s"],
            "measured": measured,
        }
        if measured:
            rec.update({
                "time_s": info["time_s"],
                "local_phase_s": dist["local_phase_s"],
                "comm_time_meas_s": dist["comm_time_meas_s"],
                "comm_delta_s": dist["comm_time_meas_s"]
                - dist["comm_time_est_s"],
            })
        else:
            rec["fallback"] = info["measure_fallback"]
        recs.append(rec)
        if wisdom_path and measured:
            key = wisdom_key(n=n, dtype="complex64", p=p, method="lb",
                             backend=backend, topology=rec["topology"])
            record_wisdom(wisdom_path, key, cfg, mode="measure",
                          time_s=info["time_s"],
                          extra={"origin": "kernel_microbench",
                                 "topology": rec["topology"],
                                 "comm_bytes": dist["comm_bytes"],
                                 "comm_time_s": dist["comm_time_meas_s"]})
    return recs


def bench_hetero_dist(sizes, wisdom_path: str | None = None) -> list[dict]:
    """Grouped-vs-homogeneous distributed makespan (device-group programs).

    Synthetic per-device pad lengths — half the devices pow2-padded, the
    rest unpadded — make ``grouped_dist_schedule``'s per-device argmin
    genuinely mixed, and the cost constants favor the *pure-jnp* radix-2
    kernel on pow2 lengths so the raced branches stay cheap on this CPU
    container (the point is the grouped-vs-homogeneous structure and the
    makespan delta, not which backend wins interpret mode).  On a
    multi-device host both programs run end to end through
    ``pfft2_distributed`` (the grouped one through its ``lax.switch``
    lowering) and the record carries the measured delta; the measured
    winner lands in wisdom under the same per-topology v3 key
    ``plan_pfft(mesh=..., method="fpm-pad")`` looks up.
    """
    import dataclasses

    import jax
    from repro.launch.mesh import make_fft_mesh

    p = jax.device_count()
    mesh = make_fft_mesh(p)
    backend = jax.default_backend()
    params = dataclasses.replace(
        CostParams.for_backend("cpu"),
        backend_factor={"xla": 1.0, "stockham": 0.5, "pallas": 300.0})
    recs = []
    for n in sizes:
        if n % p:
            continue
        pow2 = 1 << int(np.ceil(np.log2(n + 1)))
        pads = np.array([pow2 if i >= p // 2 else n for i in range(p)],
                        dtype=np.int64)
        d = np.full(p, n // p, dtype=np.int64)
        grouped = grouped_dist_schedule(n, p, pad_lengths=pads, pad="fpm",
                                        params=params)
        homo = SegmentSchedule.homogeneous(PlanConfig(pad="fpm"), n, d, pads)
        comm = dist_comm_bytes(n, p)
        est_g = (estimate_grouped_cost(grouped, params=params,
                                       comm_bytes=comm)
                 if grouped is not None else None)
        est_h = estimate_grouped_cost(homo, params=params, comm_bytes=comm)
        rec = {
            "bench": "hetero-dist", "n": int(n), "devices": p,
            "grouped": grouped.describe() if grouped is not None else None,
            "distinct_configs": (len(grouped.configs)
                                 if grouped is not None else 1),
            "makespan_est_grouped_s": est_g,
            "makespan_est_homo_s": float(est_h),
            "measured": bool(p > 1 and grouped is not None),
        }
        if rec["measured"]:
            times = measure_dist_configs([homo, grouped], n, mesh, "fft",
                                         rounds=3)
            t_h, t_g = times[homo], times[grouped]
            rec.update({
                "time_grouped_s": float(t_g),
                "time_homo_s": float(t_h),
                "grouped_vs_homo_delta_s": float(t_h - t_g),
            })
            if wisdom_path:
                winner, t_best = ((grouped, t_g) if t_g <= t_h
                                  else (homo, t_h))
                topo = topology_digest(mesh, "fft",
                                       panels=dist_panel_space(n, p))
                key = wisdom_key(n=n, dtype="complex64", p=p,
                                 method="fpm-pad", backend=backend,
                                 detail=partition_digest(d, pads),
                                 topology=topo)
                record_wisdom(wisdom_path, key, winner, mode="measure",
                              time_s=float(t_best),
                              extra={"origin": "kernel_microbench",
                                     "topology": topo})
        recs.append(rec)
    return recs


def bench_rfft(sizes, wisdom_path: str | None = None) -> list[dict]:
    """Real-input pipeline vs the upcast-and-crop complex fallback.

    Both limbs deliver the same (N, N//2+1) half spectrum, so the race is
    apples-to-apples: ``measure_rfft_configs`` interleaves them through
    the tuner's own min-of-rounds harness.  The comm-bytes columns are
    structural (``dist_comm_bytes`` at p=4 — the half-spectrum panel is
    ~half the full panel regardless of host), so the record pins the
    comm win even on a 1-device container; on a multi-device host an
    ``rfft-dist`` record adds the *measured* end-to-end race and comm
    sample.  The measure-tuned pick warms wisdom under the same
    ``method="rfft-lb"`` keys ``plan_pfft`` consults.
    """
    import jax
    from repro.plan import measure_rfft_configs, tune_rfft

    backend = jax.default_backend()
    recs = []
    for n in sizes:
        real_cfg = PlanConfig(real=True)
        cplx_cfg = PlanConfig()
        times = measure_rfft_configs([real_cfg, cplx_cfg], n, rounds=20)
        t_real, t_cplx = times[real_cfg], times[cplx_cfg]
        cb_c = dist_comm_bytes(n, 4)
        cb_r = dist_comm_bytes(n, 4, real=True)
        sched, info = tune_rfft(n, mode="measure", top_k=2, reps=5)
        recs.append({
            "bench": "rfft", "n": int(n),
            "time_real_s": float(t_real),
            "time_complex_s": float(t_cplx),
            "speedup_real": float(t_cplx / t_real),
            "comm_bytes_real_p4": float(cb_r),
            "comm_bytes_complex_p4": float(cb_c),
            "comm_ratio_p4": float(cb_r / cb_c),
            "tuned_path": info["chosen_path"],
            "tuned_time_s": float(info["time_s"]),
        })
        if wisdom_path:
            key = wisdom_key(n=n, dtype="float32", p=1, method="rfft-lb",
                             backend=backend)
            record_wisdom(wisdom_path, key, sched, mode="measure",
                          time_s=float(info["time_s"]),
                          extra={"origin": "kernel_microbench"})

    p = jax.device_count()
    if p > 1:
        from repro.launch.mesh import make_fft_mesh
        from repro.plan import tune_rfft_dist

        mesh = make_fft_mesh(p)
        for n in sizes:
            if n % p:
                continue
            sched, info = tune_rfft_dist(n, mesh, "fft", mode="measure",
                                         top_k=2, reps=3)
            dist = info["dist"]
            topo = topology_digest(mesh, "fft", panels=dist_panel_space(n, p))
            recs.append({
                "bench": "rfft-dist", "n": int(n), "devices": p,
                "topology": topo,
                "tuned_path": info["chosen_path"],
                "comm_bytes_real": dist["comm_bytes_real"],
                "comm_bytes_complex": dist["comm_bytes_complex"],
                "comm_ratio_real": dist["comm_ratio_real"],
                "comm_time_meas_s": dist.get("comm_time_meas_s"),
                "time_s": float(info["time_s"]),
            })
            if wisdom_path:
                key = wisdom_key(n=n, dtype="float32", p=p,
                                 method="rfft-lb", backend=backend,
                                 topology=topo)
                record_wisdom(wisdom_path, key, sched, mode="measure",
                              time_s=float(info["time_s"]),
                              extra={"origin": "kernel_microbench",
                                     "topology": topo,
                                     "comm_bytes": dist["comm_bytes"],
                                     "comm_time_s":
                                         dist.get("comm_time_meas_s")})
    return recs


def bench_pfft3(sizes, wisdom_path: str | None = None) -> list[dict]:
    """Pencil-vs-slab 3-D decomposition race on this host's devices.

    The mesh is the squarest r x c factorization of the visible device
    count (rectangular when p is not a perfect square — exactly the case
    where ``tune_pfft3``'s orientation racing matters, since swapping
    which axis plays row changes which exchange round moves more data).
    ``tune_pfft3(mode="measure")`` races config x panel x orientation
    finalists through the full two-exchange pencil pipeline, then the
    winning program races the one-axis *slab* pipeline (three exchange
    rounds) end to end over the same devices: the record carries the
    pencil-vs-slab delta — the decomposition's headline claim — plus the
    measured-vs-estimated comm delta the 3-D makespan constants are
    calibrated by.  The measured winner lands in wisdom, orientation
    included, under the same v3 2-D-topology key ``plan_pfft3(mesh=...)``
    looks up, so a benchmark run warms 3-D planning like every other
    sweep warms its family.  On a 1-device host the sweep records the
    estimate-fallback facts.
    """
    import functools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.pfft3d import pfft3_slab
    from repro.launch.mesh import make_fft_mesh, make_pfft3_mesh
    from repro.plan import pfft3_panel_space, tune_pfft3

    p = jax.device_count()
    backend = jax.default_backend()
    c = max(k for k in range(1, int(p ** 0.5) + 1) if p % k == 0)
    r = p // c
    recs = []
    for n in sizes:
        if n % r or n % c or n % p:
            continue
        mesh = make_pfft3_mesh(r, c)
        panels = pfft3_panel_space(n, r, c)
        topo = topology_digest(mesh, ("fft_r", "fft_c"), panels=panels)
        cfg, waxes, info = tune_pfft3(n, mesh, mode="measure",
                                      panels=panels)
        stats = info["pfft3"]
        measured = "measure_fallback" not in info
        rec = {
            "bench": "pfft3", "n": int(n), "devices": p,
            "mesh": f"{r}x{c}",
            "topology": topo,
            "config": cfg.describe(),
            "orientation": info.get("orientation"),
            "comm_bytes": stats["comm_bytes"],
            "comm_time_est_s": stats["comm_time_est_s"],
            "measured": measured,
        }
        if measured:
            # Slab baseline: same cube, same local config, one mesh axis,
            # three exchange rounds instead of the pencil's two.
            slab_mesh = make_fft_mesh(p)
            rng = np.random.default_rng(0)
            x = jnp.asarray((rng.standard_normal((n, n, n))
                             + 1j * rng.standard_normal((n, n, n))
                             ).astype(np.complex64))
            x = jax.device_put(x, NamedSharding(slab_mesh,
                                                P("fft", None, None)))
            t_slab = time_fn(jax.jit(functools.partial(
                pfft3_slab, mesh=slab_mesh, axis_name="fft", config=cfg)), x)
            rec.update({
                "time_pencil_s": float(info["time_s"]),
                "time_slab_s": float(t_slab),
                "pencil_vs_slab_delta_s": float(t_slab - info["time_s"]),
                "local_pass_s": stats.get("local_pass_s"),
                "comm_time_meas_s": stats.get("comm_time_meas_s"),
            })
            if stats.get("comm_time_meas_s") is not None:
                rec["comm_delta_s"] = float(
                    stats["comm_time_meas_s"] - stats["comm_time_est_s"])
        else:
            rec["fallback"] = info["measure_fallback"]
        recs.append(rec)
        if wisdom_path and measured:
            key = wisdom_key(n=n, dtype="complex64", p=p, method="pfft3-lb",
                             backend=backend, topology=topo)
            extra = {"origin": "kernel_microbench", "topology": topo}
            if waxes is not None:
                extra["pfft3_orientation"] = list(waxes)
            if stats.get("comm_time_meas_s") is not None:
                extra["comm_bytes"] = stats["comm_bytes"]
                extra["comm_time_s"] = stats["comm_time_meas_s"]
            record_wisdom(wisdom_path, key, cfg, mode="measure",
                          time_s=info.get("time_s"), extra=extra)
    return recs


def bench_multihost(sizes, wisdom_path: str | None = None) -> list[dict]:
    """Hierarchical-vs-flat exchange race on an emulated hosts x local mesh.

    ``make_fft_mesh(hosts=h, local=l)`` splits the forced CPU devices
    into ``h`` host-major groups (the single-process stand-in for real
    ``process_index`` structure), so ``tune_dist_config(mode="measure")``
    races hierarchical-exchange candidates against flat ones through the
    full ``pfft2_distributed`` pipeline.  The record pins three things:

    * the *explicit* hier-vs-flat end-to-end delta (both forms of the
      winner's config, interleaved through ``measure_dist_configs``);
    * the per-tier comm samples the tuner times — one grouped
      all_to_all per tier, byte volumes matching
      ``dist_comm_bytes(hosts=..., exchange="hier")`` exactly;
    * the two interconnect tiers ``fit_cost_params`` recovers from the
      warmed store (fast intra-host vs slow inter-host constants) —
      degenerate on a localhost rig where both tiers are shared memory,
      but the fit *path* is the one real clusters calibrate through.

    The measured winner warms wisdom under the host-count topology
    digest (``{h}hx{p}x...``), the key ``plan_pfft(mesh=...)`` looks up
    when handed the same emulated-host mesh — so a warmed store serves
    the multi-host plan with zero re-measurement.  Sub-4-device hosts
    record the structural two-tier byte split instead (flat keeps
    ``M(l-1)/p`` on the fast tier, hier aggregates to ``M(l-1)/l`` fast
    bytes but only ``h-1`` slow-tier messages).
    """
    import dataclasses

    import jax
    from repro.launch.mesh import make_fft_mesh, mesh_host_shape
    from repro.plan import fit_cost_params

    p = jax.device_count()
    backend = jax.default_backend()
    hosts = 2 if p >= 4 and p % 2 == 0 else 1
    local = p // hosts
    recs = []
    if hosts < 2 or local < 2:
        # Structural fallback: the tier byte accounting at a reference
        # 2-host x 2-device topology, priced by the default constants.
        params = CostParams.for_backend(backend)
        for n in sizes:
            flat = dist_comm_bytes(n, 4, hosts=2, exchange="flat")
            hier = dist_comm_bytes(n, 4, hosts=2, exchange="hier")
            total = dist_comm_bytes(n, 4)
            recs.append({
                "bench": "multihost", "n": int(n), "devices": p,
                "hosts": 2, "local": 2, "measured": False,
                "fallback": "needs >= 4 devices with an even split",
                "flat_intra_bytes": float(flat.intra),
                "flat_inter_bytes": float(flat.inter),
                "hier_intra_bytes": float(hier.intra),
                "hier_inter_bytes": float(hier.inter),
                "inter_msgs_flat": 2, "inter_msgs_hier": 1,
                "exchange_time_flat_s": exchange_time(
                    total, 4, params=params, hosts=2, exchange="flat"),
                "exchange_time_hier_s": exchange_time(
                    total, 4, params=params, hosts=2, exchange="hier"),
            })
        return recs

    mesh = make_fft_mesh(hosts=hosts, local=local)
    assert mesh_host_shape(mesh, "fft") == (hosts, local)
    for n in sizes:
        if n % p:
            continue
        panels = dist_panel_space(n, p)
        topo = topology_digest(mesh, "fft", panels=panels)
        cfg, info = tune_dist_config(n, mesh, "fft", mode="measure",
                                     panels=panels)
        dist = info["dist"]
        tiers = dist_comm_bytes(n, p, hosts=hosts, exchange=cfg.exchange)
        measured = "measure_fallback" not in info
        rec = {
            "bench": "multihost", "n": int(n), "devices": p,
            "hosts": hosts, "local": local,
            "topology": topo,
            "config": cfg.describe(),
            "exchange": cfg.exchange,
            "intra_bytes": float(tiers.intra),
            "inter_bytes": float(tiers.inter),
            "comm_time_est_s": dist["comm_time_est_s"],
            "measured": measured,
        }
        if measured:
            # Explicit hier-vs-flat: the same winning config under both
            # exchange forms, interleaved through the tuner's harness.
            flat_cfg = dataclasses.replace(cfg, exchange="flat")
            hier_cfg = dataclasses.replace(cfg, exchange="hier")
            times = measure_dist_configs([flat_cfg, hier_cfg], n, mesh,
                                         "fft", rounds=3)
            rec.update({
                "time_s": info["time_s"],
                "time_flat_s": float(times[flat_cfg]),
                "time_hier_s": float(times[hier_cfg]),
                "hier_vs_flat_delta_s": float(times[flat_cfg]
                                              - times[hier_cfg]),
                "comm_time_meas_s": dist.get("comm_time_meas_s"),
                "comm_samples": dist.get("comm_samples"),
            })
        else:
            rec["fallback"] = info["measure_fallback"]
        recs.append(rec)
        if wisdom_path and measured:
            key = wisdom_key(n=n, dtype="complex64", p=p, method="lb",
                             backend=backend, topology=topo)
            extra = {"origin": "kernel_microbench", "topology": topo,
                     "hosts": hosts,
                     "comm_bytes": dist["comm_bytes"],
                     "comm_time_s": dist.get("comm_time_meas_s")}
            if dist.get("comm_samples"):
                extra["comm_samples"] = dist["comm_samples"]
            record_wisdom(wisdom_path, key, cfg, mode="measure",
                          time_s=info["time_s"], extra=extra)
    if wisdom_path and any(r.get("measured") for r in recs):
        fitted = fit_cost_params(wisdom_path, backend=backend)
        for r in recs:
            if r.get("measured"):
                r["fit_intra_bytes_per_s"] = fitted.interconnect_bytes_per_s
                r["fit_intra_latency_s"] = fitted.comm_latency_s
                r["fit_inter_bytes_per_s"] = fitted.inter_bytes_per_s
                r["fit_inter_latency_s"] = fitted.inter_latency_s
    return recs


# Which record ``bench`` tags each sweep (re)writes — the unit of the
# overwrite guard and of partial-sweep merging below.
_SWEEP_BENCHES = {
    "rowfft": ("rowfft",), "fused": ("fused",), "segments": ("segments",),
    "planner": ("planner",), "schedule": ("schedule",),
    "dist": ("dist",), "hetero-dist": ("hetero-dist",),
    "rfft": ("rfft", "rfft-dist"), "pfft3": ("pfft3",),
    "multihost": ("multihost",),
}


def _merge_existing_records(out: str, rerun_benches: set, backend: str,
                            force: bool) -> list:
    """Record-level overwrite protection + partial-sweep merge.

    Returns the existing records whose bench is *not* being rerun (they
    are carried into the new file unchanged, so a ``--sweeps`` subset
    refreshes only its own rows).  For the benches that *are* rerun: if
    this run is cpu (interpret-mode Pallas) and any record it would
    replace is tagged with an accelerator backend, refuse — interpreter
    timings say nothing about hardware and must never silently replace
    measured numbers.  ``--force`` overrides.  Records predating the
    per-record tags inherit the file's top-level backend.
    """
    if not os.path.exists(out):
        return []
    try:
        with open(out) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        return []  # unreadable/legacy file: nothing trustworthy to protect
    if not isinstance(existing, dict):
        return []
    file_backend = existing.get("backend")
    records = [r for r in existing.get("records", []) if isinstance(r, dict)]
    replaced = [r for r in records if r.get("bench") in rerun_benches]
    if backend == "cpu" and not force:
        accel = sorted({r.get("backend") or file_backend or "?"
                        for r in replaced
                        if (r.get("backend") or file_backend or "cpu")
                        != "cpu"})
        if accel:
            raise SystemExit(
                f"{out} holds {'/'.join(accel)}-measured records for "
                f"benches being rerun; refusing to replace them with cpu "
                f"interpret-mode timings (--force to override)")
    kept = [r for r in records if r.get("bench") not in rerun_benches]
    for r in kept:
        # Tags travel with the record once it outlives its original file
        # header (the merged file's header describes *this* run).
        r.setdefault("backend", file_backend)
        r.setdefault("interpret", bool(existing.get("interpret_mode")))
    return kept


def run(quick: bool = False, out: str = DEFAULT_OUT,
        wisdom: str | None = None, sweeps: str | None = None,
        force: bool = False) -> dict:
    rowfft_sizes = [64, 256] if quick else [64, 256, 1024]
    fused_sizes = [64, 128] if quick else [64, 128, 256]
    planner_sizes = [128] if quick else [128, 256]
    all_sweeps = {
        "rowfft": lambda: bench_rowfft(rowfft_sizes,
                                       rows=32 if quick else 64),
        "fused": lambda: bench_fused(fused_sizes),
        "segments": lambda: bench_segments(n=128 if quick else 256, p=4,
                                           pad_to=160 if quick else 320),
        "planner": lambda: bench_planner(planner_sizes, p=4,
                                         wisdom_path=wisdom),
        "schedule": lambda: bench_schedule(n=48 if quick else 96, p=4,
                                           wisdom_path=wisdom),
        "dist": lambda: bench_dist([64] if quick else [64, 128],
                                   wisdom_path=wisdom),
        "hetero-dist": lambda: bench_hetero_dist(
            [48] if quick else [48, 96], wisdom_path=wisdom),
        "rfft": lambda: bench_rfft([64] if quick else [64, 128],
                                   wisdom_path=wisdom),
        "pfft3": lambda: bench_pfft3([8] if quick else [8, 16],
                                     wisdom_path=wisdom),
        "multihost": lambda: bench_multihost([64] if quick else [64, 128],
                                             wisdom_path=wisdom),
    }
    chosen = (list(all_sweeps) if sweeps is None
              else [s.strip() for s in sweeps.split(",") if s.strip()])
    unknown = set(chosen) - set(all_sweeps)
    if unknown:
        raise SystemExit(f"unknown sweeps {sorted(unknown)}; "
                         f"choose from {sorted(all_sweeps)}")
    import jax
    backend = jax.default_backend()
    interpret = backend == "cpu"
    rerun_benches = {b for s in chosen for b in _SWEEP_BENCHES[s]}
    kept = _merge_existing_records(out, rerun_benches, backend, force)
    records = []
    for name in chosen:
        records += all_sweeps[name]()
    for r in records:
        # Every record says where its numbers came from, so merged or
        # archived files stay interpretable record by record.
        r.setdefault("backend", backend)
        r.setdefault("interpret", interpret)
    payload = {
        "backend": backend,
        "interpret_mode": interpret,
        "records": kept + records,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    for r in records:
        print(",".join(f"{k}={v}" for k, v in r.items()))
    print(f"wrote {out} ({len(records)} records)")
    if wisdom:
        print(f"warmed wisdom store {wisdom}")
    return payload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--wisdom", default=None,
                    help="wisdom store to warm with each size's best "
                         "measured config (plan_pfft-compatible keys)")
    ap.add_argument("--sweeps", default=None,
                    help="comma-separated subset of "
                         "rowfft,fused,segments,planner,schedule,dist,"
                         "hetero-dist,rfft,pfft3,multihost (default: all)")
    ap.add_argument("--force", action="store_true",
                    help="overwrite an output file holding accelerator-"
                         "tagged records with interpret-mode timings")
    ap.add_argument("--require-accelerator", action="store_true",
                    help="exit non-zero, before any bench, when JAX finds "
                         "no accelerator")
    args = ap.parse_args()
    import jax
    from repro.launch.compile_cache import use_compile_cache
    platform = jax.devices()[0].platform
    if args.require_accelerator and platform == "cpu":
        print(f"kernel_microbench: no accelerator (JAX platform "
              f"{platform!r}); refusing to bench", file=sys.stderr)
        return 1
    use_compile_cache()
    run(quick=args.quick, out=args.out, wisdom=args.wisdom,
        sweeps=args.sweeps, force=args.force)
    return 0


if __name__ == "__main__":
    sys.exit(main())
