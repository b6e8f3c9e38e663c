"""Plan-style user API (mirrors fftw's plan/execute/wisdom lifecycle).

    plan = plan_pfft(n=4096, fpms=fpms, method="fpm-pad", tune="estimate")
    out  = plan.execute(signal)     # jit-compiled, reusable

The plan captures everything host-side once — the partition ``d``, the pad
lengths, *and* the execution schedule (``SegmentSchedule``: one
``PlanConfig`` per segment, so a slow processor can keep the library FFT
while pow2-padded fast ones take the kernel) — so ``execute`` is a pure
jitted function: the analogue of building an fftw plan once and calling
``fftw_execute`` repeatedly (the only thread-safe op, as the paper notes
in §IV).  A single explicit ``config=`` becomes the degenerate
one-entry-per-segment schedule, keeping the PR-2 API a thin shim.

``tune`` selects how the variant is chosen (fftw's ESTIMATE/MEASURE):

* ``"off"`` — the default config (library FFT, batched dispatch), or an
  explicit ``config=``/legacy flags.
* ``"estimate"`` — rank the candidate space with the cost model
  (``repro.plan.cost``), per distinct effective FFT length
  (``tune_schedule``); no device work.
* ``"measure"`` — additionally time the Pareto top-k candidates per
  length group on device.

``wisdom=path`` consults/feeds the persistent store (``repro.plan.wisdom``)
keyed by (n, dtype, p, method, backend): a hit skips tuning entirely, and
a measured choice is recorded so fresh processes are served from disk.
When the store holds enough measured entries, the estimate cost model is
re-calibrated from them (``repro.plan.calibrate``) before ranking.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Literal

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.fpm import FPMSet
from repro.core.partition import PartitionResult, lb_partition, partition_rows
from repro.core.pfft import _pfft_limb
from repro.plan.calibrate import fit_cost_params
from repro.plan.config import PlanConfig, normalize_pad
from repro.plan.schedule import SegmentSchedule
from repro.plan.tune import (dist_panel_space, kernel_exclusions,
                             tune_dist_schedule, tune_schedule)
from repro.plan.wisdom import (lookup_wisdom, partition_digest, record_wisdom,
                               topology_digest, wisdom_key)

Method = Literal["lb", "fpm", "fpm-pad", "fpm-czt",
                 "rfft-lb", "rfft-fpm", "rfft-fpm-pad"]
TuneMode = Literal["off", "estimate", "measure"]

_PAD_STRATEGY = {"lb": "none", "fpm": "none", "fpm-pad": "fpm",
                 "fpm-czt": "czt",
                 "rfft-lb": "none", "rfft-fpm": "none", "rfft-fpm-pad": "fpm"}

# The real-input half-spectrum pipeline: same partition/pad machinery as
# the base method (the name after the ``rfft-`` prefix), but the plan
# transforms a real (N, N) signal into its (N, N//2+1) half spectrum and
# the tuner races the real pipeline against the upcast-and-crop complex
# fallback — the winning family is recorded in the schedule's ``real``
# flags and the executor routes on them.  No ``rfft-fpm-czt``: the real
# pipeline has no Bluestein form.
_REAL_METHODS = frozenset({"rfft-lb", "rfft-fpm", "rfft-fpm-pad"})

__all__ = ["PfftPlan", "plan_pfft", "rfft2", "irfft2",
           "Pfft3Plan", "plan_pfft3",
           "Pfft1LargePlan", "plan_pfft1_large", "pfft1_large"]


def _base_method(method: Method) -> str:
    """The partitioning family a method uses: ``rfft-fpm-pad`` pads and
    partitions exactly like ``fpm-pad``; the prefix only changes what the
    transform delivers."""
    return method[5:] if method in _REAL_METHODS else method


def _ctype_for(dtype: str) -> str:
    return "complex128" if np.dtype(dtype) == np.dtype(np.float64) \
        else "complex64"


def _build_raw(n: int, method: Method, d: np.ndarray,
               schedule: SegmentSchedule, mesh, axis_name: str,
               dtype: str) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """The un-jitted executor for a resolved schedule.

    Shared by ``plan_pfft`` and ``PfftPlan.with_schedule`` so a hot-swap
    routes identically to the original plan.  Real methods route on the
    *winning family*: a ``real``-flagged schedule runs the half-spectrum
    pipeline, a complex-family winner upcasts and crops to the same
    (N, N//2+1) deliverable.
    """
    if method in _REAL_METHODS:
        nh = n // 2 + 1
        ctype = _ctype_for(dtype)
        if mesh is not None:
            if schedule.anchor_config.real:
                from repro.core.pfft_dist import rpfft2_distributed

                def raw(m):
                    return rpfft2_distributed(m, mesh, axis_name,
                                              schedule=schedule)
            else:
                from repro.core.pfft_dist import pfft2_distributed

                def raw(m):
                    return pfft2_distributed(m.astype(ctype), mesh,
                                             axis_name,
                                             schedule=schedule)[:, :nh]
        elif schedule.anchor_config.real:
            from repro.core.pfft import _rpfft_limb

            def raw(m):
                return _rpfft_limb(m, d, schedule=schedule)
        else:
            def raw(m):
                return _pfft_limb(m.astype(ctype), d,
                                  schedule=schedule)[:, :nh]
        return raw
    if mesh is not None:
        from repro.core.pfft_dist import pfft2_distributed

        def raw(m):
            # The full schedule, not just its anchor config: this is what
            # routes heterogeneous picks to the device-group program (and
            # per-device FPM pad lengths to the uniform-length rule).
            return pfft2_distributed(m, mesh, axis_name, schedule=schedule)
    else:
        def raw(m):
            return _pfft_limb(m, d, schedule=schedule)
    return raw


class _Scoped:
    """What every plan type shares with ``repro.obs``: a plan is tracked
    while it lives, and names and counts the instructions of its own
    executable."""

    def __post_init__(self) -> None:
        obs.register(self)

    def _compiled(self) -> tuple[dict[str, str | None], dict[str, int]]:
        """``(scope_map, counters)`` of the executable ``execute`` runs on
        one planned input.

        Lowers the plan's function on ``input_spec()`` (one planned
        input, laid out as planned) and compiles it, which the compile
        cache serves once the plan has run; computed once per plan,
        never per call."""
        found = getattr(self, "_analysis", None)
        if found is None:
            text = obs.compiled_text(self._fn, self.input_spec())
            scopes = obs.scope_map(text)
            found = self._analysis = (
                scopes, {**obs.exchange_counts(text),
                         **obs.sparse_tile_counts(text, scopes),
                         **obs.rowfft_counts(text)})
        return found

    def scope_map(self) -> dict[str, str | None]:
        """``{instruction name: pfft.* scope or None}`` of the plan's
        executable (``repro.obs.scope_map``).  The names are those a
        profiler trace gives the device ops, so the map reads an xprof
        trace of this plan by program phase."""
        return self._compiled()[0]

    def counters(self) -> dict[str, int]:
        """The counters of one transform of the plan's executable:
        ``repro.obs.exchange_counts`` (none on one chip),
        ``repro.obs.sparse_tile_counts`` and ``repro.obs.rowfft_counts``
        (none on the CPU)."""
        return self._compiled()[1]


def _sharded(shape, dtype, mesh, spec) -> jax.ShapeDtypeStruct:
    """An input of ``shape`` laid out by ``spec`` over ``mesh``, or on
    the default device where there is no mesh."""
    from jax.sharding import (NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    sharding = (SingleDeviceSharding(jax.devices()[0]) if mesh is None
                else NamedSharding(mesh, PartitionSpec(*spec)))
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


@dataclasses.dataclass
class PfftPlan(_Scoped):
    n: int
    method: Method
    partition: PartitionResult
    pad_lengths: np.ndarray | None
    config: PlanConfig
    schedule: SegmentSchedule
    tuning: dict[str, Any]
    _fn: Callable[[jnp.ndarray], jnp.ndarray]

    # Distributed plans carry their mesh so the plan can be *rebuilt*
    # against the same topology (the self-healing hot-swap path).
    mesh: Any = None
    axis_name: str = "fft"
    # The planned input dtype; real methods need it to rebuild the
    # upcast-and-crop fallback executor on a hot-swap.
    dtype: str = "complex64"
    _batched_fns: dict[int, Callable] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def execute(self, m: jnp.ndarray) -> jnp.ndarray:
        """Run the planned transform; leading batch dims are vmapped.

        ``m``: ``(..., n, n)``.  Batched wrappers are built (and jitted)
        once per batch rank and cached — execute stays the
        plan-once/run-many hot path.  Every method vmaps, czt included
        (its phases are ordinary jnp programs since the schedule
        executor took over the per-segment slicing).
        """
        if m.ndim < 2 or m.shape[-2:] != (self.n, self.n):
            raise ValueError(
                f"plan is for ({self.n}, {self.n}) signals "
                f"(optionally with leading batch dims), got {m.shape}")
        with obs.span("pfft.execute"):
            if m.ndim == 2:
                return self._fn(m)
            fn = self._batched_fns.get(m.ndim)
            if fn is None:
                fn = self._fn
                for _ in range(m.ndim - 2):
                    fn = jax.vmap(fn)
                fn = jax.jit(fn)
                self._batched_fns[m.ndim] = fn
            return fn(m)

    def input_spec(self) -> jax.ShapeDtypeStruct:
        return _sharded((self.n, self.n), self.dtype, self.mesh,
                        (self.axis_name, None))

    def execute_many(self, ms, *, pad_to: int | None = None) -> list:
        """Serve a cohort: stack same-size signals into ONE batched dispatch.

        The serving layer's execution surface — ``ms`` is a sequence of
        ``(n, n)`` signals (many users' concurrent requests for the same
        transform), stacked onto a leading batch axis and run through
        ``execute``'s vmapped program, so the whole cohort costs one
        dispatch instead of ``len(ms)``.  Returns the per-request
        results in order.

        ``pad_to`` rounds the stacked batch up with zero signals before
        dispatch (the extras are computed and dropped): a serving loop
        that buckets its batch sizes to powers of two compiles one
        program per (plan, bucket) instead of one per distinct cohort
        size — jit specialises on shapes, and an unbucketed mixed
        stream would otherwise retrace on nearly every tick.

        Stacking, padding, and unstacking happen on the host (numpy),
        so the device sees exactly one transfer in and one out; the
        returned results are numpy views into the fetched batch.
        Per-item device slicing would cost a dispatch per request —
        the very overhead coalescing exists to amortise.
        """
        return _execute_many(self, ms, (self.n, self.n), pad_to)

    @property
    def d(self) -> np.ndarray:
        return self.partition.d

    def with_schedule(self, schedule: SegmentSchedule,
                      tuning: dict[str, Any] | None = None) -> "PfftPlan":
        """Same problem, new execution schedule: rebuild the jitted
        executor around ``schedule`` and return a fresh plan.

        This is the hot-swap primitive of the self-healing runtime
        (``repro.runtime.resilient``): an online re-plan produces a new
        ``SegmentSchedule`` (typically a device-group program that gives
        a degraded device different work) and the wrapper swaps it in at
        the next call boundary.  The swapped program lowers exactly like
        ``plan_pfft`` lowers — distributed plans re-enter
        ``pfft2_distributed`` on the captured mesh, single-host plans
        re-enter the limb on the captured partition (and real-method
        plans re-route on the swapped schedule's winning family).
        """
        raw = _build_raw(self.n, self.method, self.partition.d, schedule,
                         self.mesh, self.axis_name, self.dtype)
        return dataclasses.replace(
            self, schedule=schedule, config=schedule.anchor_config,
            tuning=dict(tuning) if tuning is not None else dict(self.tuning),
            _fn=jax.jit(raw), _batched_fns={})


def _resolve_schedule(n: int, method: Method, part: PartitionResult,
                      pads: np.ndarray | None, fpms: FPMSet | None,
                      tune: TuneMode, wisdom: str | None,
                      config: PlanConfig | None, dtype: str,
                      mesh=None, axis_name: str = "fft"
                      ) -> tuple[SegmentSchedule, dict[str, Any]]:
    """Pick the plan's execution schedule and say where it came from.

    Resolution order: explicit config > wisdom hit > tuner > default.
    A wisdom hit applies even at ``tune="off"`` — passing ``wisdom=path``
    *is* the request to use stored plans (FFTW reads wisdom regardless of
    planner rigor) — but only when the stored schedule still describes
    the current partition (a stale structure is a miss, never an error).
    ``tuning["source"]`` records which branch won — the CI smoke test
    asserts a warm wisdom file yields ``"wisdom"`` (no re-measure).

    With a ``mesh``, the plan is for ``pfft2_distributed``: the wisdom
    key gains the mesh's ``topology_digest`` (schema v3 — a plan measured
    on one topology is never served to another), the tuner is the
    distributed one (``tune_dist_schedule``: measure races finalists
    through the full all_to_all pipeline end to end on this mesh), and a
    measured pick is recorded with its comm sample so calibration can fit
    the interconnect constants.
    """
    pad_strategy = _PAD_STRATEGY[method]
    real = method in _REAL_METHODS

    def normalize(cfg: PlanConfig) -> PlanConfig:
        """The method owns the pad semantics: ``plan.config.normalize_pad``
        (shared with the algorithm entry points in ``core.pfft``, so an
        explicit ``PlanConfig()`` on fpm-czt still runs Bluestein and a
        drifted ``pad="czt"`` on fpm-pad still runs the paper's crop).
        Real methods also own the transform: an explicit config is
        real-flagged so the executor runs the half-spectrum pipeline
        (a tuner-chosen complex fallback keeps its own flag — that flag
        *is* the race's verdict)."""
        cfg = normalize_pad(cfg, pad_strategy)
        if real and not cfg.real:
            cfg = dataclasses.replace(cfg, real=True)
        return cfg

    tuning: dict[str, Any] = {"mode": tune}
    if config is not None:
        tuning["source"] = "explicit"
        return SegmentSchedule.homogeneous(normalize(config), n, part.d,
                                           pads), tuning

    # The lb partition is a function of (n, p); the FPM partitions (and
    # pad lengths) depend on the FPMSet and eps, so they digest into the
    # key — a different model must not be served another model's plan.
    # A mesh additionally digests its topology: a measured distributed
    # plan is a property of the pod it was timed on.
    detail = (partition_digest(part.d, pads)
              if _base_method(method) != "lb" else None)
    topo = panels = None
    if mesh is not None:
        panels = dist_panel_space(n, int(mesh.shape[axis_name]))
        topo = topology_digest(mesh, axis_name, panels=panels)
        tuning["topology"] = topo
    key = wisdom_key(n=n, dtype=dtype, p=len(part.d), method=method,
                     backend=jax.default_backend(), detail=detail,
                     topology=topo)
    tuning["wisdom_key"] = key
    if wisdom is not None:
        hit = lookup_wisdom(wisdom, key)
        if hit is not None:
            plan, entry = hit
            if isinstance(plan, SegmentSchedule):
                # Structure AND pad semantics must match: an entry whose
                # config pad drifted from the method's strategy would
                # execute the wrong transform (czt vs pad-and-crop), so
                # it is a miss like every other kind of drift.
                ok = (plan.matches(part.d, pads)
                      and all(e.config.pad == pad_strategy for e in plan))
                schedule = plan if ok else None
            else:
                schedule = SegmentSchedule.homogeneous(normalize(plan), n,
                                                       part.d, pads)
            if schedule is not None and mesh is not None:
                # A distributed plan must lower to one SPMD program —
                # heterogeneous mixes of the row-FFT variant group fine
                # (device-group programs), but a hand-edited or drifted
                # entry mixing program-level knobs is a miss.  The rows
                # mapping is already guaranteed by matches() above
                # (the even N/p split tiles every mesh).  A real-family
                # hit must additionally satisfy the real dist program's
                # shape (homogeneous, unfused, monolithic) — anything
                # ``rpfft2_distributed`` would refuse is a miss too.
                try:
                    if schedule.anchor_config.real:
                        from repro.core.pfft_dist import _validate_real_dist
                        _validate_real_dist(None, schedule)
                    else:
                        from repro.core.pfft_dist import \
                            validate_spmd_schedule
                        validate_spmd_schedule(schedule)
                except ValueError:
                    schedule = None
            if schedule is not None:
                tuning["source"] = "wisdom"
                tuning["wisdom_entry"] = entry
                return schedule, tuning

    if tune == "off":
        tuning["source"] = "off"
        return SegmentSchedule.homogeneous(
            PlanConfig(pad=pad_strategy, real=real), n, part.d,
            pads), tuning

    params = None
    if wisdom is not None:
        # Enough measured entries on this host re-fit the cost constants
        # (falls back to the hard-coded ones below the sample threshold).
        from repro.plan.cost import CostParams
        params = fit_cost_params(wisdom)
        tuning["calibrated"] = params != CostParams.for_backend()
    if real and mesh is not None:
        from repro.plan.tune import tune_rfft_dist
        schedule, info = tune_rfft_dist(
            n, mesh, axis_name, mode=tune, pad=pad_strategy, fpms=fpms,
            params=params, panels=panels, dtype=np.dtype(dtype))
    elif real:
        from repro.plan.tune import tune_rfft
        schedule, info = tune_rfft(n, d=part.d, pad_lengths=pads,
                                   fpms=fpms, mode=tune, pad=pad_strategy,
                                   params=params, dtype=np.dtype(dtype))
    elif mesh is not None:
        schedule, info = tune_dist_schedule(
            n, mesh, axis_name, pad_lengths=pads, mode=tune,
            pad=pad_strategy, fpms=fpms, params=params, panels=panels,
            dtype=np.dtype(dtype))
    else:
        schedule, info = tune_schedule(n, d=part.d, pad_lengths=pads,
                                       fpms=fpms, mode=tune,
                                       pad=pad_strategy, params=params,
                                       dtype=np.dtype(dtype))
    tuning.update(info)
    tuning["source"] = tune
    excluded = kernel_exclusions(n)
    if excluded:
        tuning["excluded"] = excluded
    if wisdom is not None and tune == "measure":
        extra = None
        if mesh is not None:
            extra = {"topology": topo}
            dist = info.get("dist", {})
            if dist.get("comm_time_meas_s") is not None:
                extra["comm_bytes"] = dist["comm_bytes"]
                extra["comm_time_s"] = dist["comm_time_meas_s"]
            if dist.get("comm_samples"):
                extra["comm_samples"] = dist["comm_samples"]
            if int(dist.get("hosts", 1)) > 1:
                extra["hosts"] = int(dist["hosts"])
        record_wisdom(wisdom, key, schedule, mode="measure",
                      time_s=info.get("time_s"), extra=extra)
    return schedule, tuning


def plan_pfft(n: int, *, p: int | None = None, fpms: FPMSet | None = None,
              method: Method = "fpm", eps: float = 0.05,
              tune: TuneMode = "off", wisdom: str | None = None,
              config: PlanConfig | None = None, dtype: str = "complex64",
              mesh=None, axis_name: str = "fft",
              use_stockham: bool | None = None,
              fused: bool | None = None) -> PfftPlan:
    """Build a reusable plan; see the module docstring for the lifecycle.

    ``mesh=`` plans for ``pfft2_distributed`` over the given ``Mesh``
    instead of the single-host limb: the wisdom key gains the mesh's
    ``topology_digest``, ``tune="measure"`` times finalists through the
    full all_to_all pipeline end to end on that mesh, and ``execute``
    runs the distributed transform.  N must divide by the mesh axis
    size.  The padded FPM methods are planned too: SPMD shards rows
    evenly (one abstract processor per device, N/p rows each), so the
    FPMs drive *per-device pad lengths and execution variants* instead
    of row counts — plain ``method="fpm"`` is rejected (on an even
    split it would be byte-identical to ``"lb"``); heterogeneous picks
    lower as device-group programs
    (``repro.plan.groups``: per-shard ``lax.switch`` branches at the
    schedule's max effective length, the program-level analog of the
    ragged row layout) and persist under the same v3 topology keys.
    ``method="fpm-pad"``/``"fpm-czt"`` require ``fpms`` covering
    exactly the mesh axis (``fpms.p == p``).

    The ``rfft-*`` methods plan the *real-input* transform: ``execute``
    takes a real (N, N) signal (``dtype='float32'|'float64'`` required)
    and returns the (N, N//2+1) half spectrum — half the row FFTs (two
    real rows packed per complex transform) and, distributed, roughly
    half the all_to_all bytes.  The tuner races the real pipeline
    against the upcast-and-crop complex fallback and the plan routes on
    the winner; ``plan.tuning["chosen_path"]`` says which side won.

    ``use_stockham=``/``fused=`` are deprecated shims for the pre-planner
    flag API (they build an explicit config, so tuning is skipped).
    """
    if tune not in ("off", "estimate", "measure"):
        raise ValueError(f"tune must be 'off'|'estimate'|'measure', got {tune!r}")
    if method not in _PAD_STRATEGY:
        raise ValueError(f"unknown method {method!r}")
    real = method in _REAL_METHODS
    base = _base_method(method)
    kind = np.dtype(dtype).kind
    if real and kind != "f":
        raise ValueError(
            f"method={method!r} transforms real input; pass dtype='float32' "
            f"or 'float64' (got {dtype!r})")
    if not real and kind == "f":
        raise ValueError(
            f"method={method!r} transforms complex input (got dtype="
            f"{dtype!r}); use an 'rfft-*' method for real signals")
    if real and mesh is not None and base == "fpm-pad":
        raise ValueError(
            "the distributed real path runs the homogeneous unpadded "
            "program; use method='rfft-lb' with mesh=, or plan "
            "'rfft-fpm-pad' single-host")
    if mesh is not None:
        mesh_p = int(mesh.shape[axis_name])
        if p is None:
            p = mesh_p
        elif p != mesh_p:
            raise ValueError(f"p={p} conflicts with mesh axis "
                             f"{axis_name!r} size {mesh_p}")
        if n % p:
            raise ValueError(f"N={n} must be divisible by mesh axis "
                             f"{axis_name}={p}")
        if base == "fpm":
            raise ValueError(
                "plan_pfft(mesh=...) shards rows evenly, so plain "
                f"method={method!r} would run byte-identically to the 'lb' "
                "variant (its FPMs can only influence the *row* split, "
                "which SPMD fixes) — use the 'lb' variant, or "
                "'fpm-pad'/'fpm-czt' for FPM-driven per-device pads and "
                "execution variants")
        if base != "lb" and fpms is not None and fpms.p != p:
            raise ValueError(
                f"plan_pfft(mesh=...) assigns one abstract processor per "
                f"device: fpms covers {fpms.p} processors but the mesh "
                f"axis {axis_name!r} has {p} devices")
    if use_stockham is not None or fused is not None:
        if config is not None:
            raise ValueError("pass either config= or the legacy flags "
                             "(use_stockham/fused), not both")
        warnings.warn(
            "plan_pfft: use_stockham=/fused= are deprecated; pass "
            "config=PlanConfig(...) or let tune='estimate'|'measure' choose",
            DeprecationWarning, stacklevel=2)
        pad_strategy = _PAD_STRATEGY[method]
        # The pre-refactor API silently ignored fused= on the padded
        # methods (pad semantics are per-processor); the shim must too.
        config = PlanConfig.from_flags(
            use_stockham=bool(use_stockham),
            fused=bool(fused) and pad_strategy == "none",
            pad=pad_strategy)

    with obs.span("pfft.plan.partition"):
        if base == "lb":
            if p is None:
                raise ValueError(f"method={method!r} requires p")
            part = lb_partition(n, p)
            pads = None
        else:
            if fpms is None:
                raise ValueError(f"method={method!r} requires fpms")
            if mesh is not None:
                # SPMD shards rows evenly — the FPMs drive per-device pad
                # lengths and execution variants, not row counts (the
                # device-group lowering's realisation of heterogeneity).
                part = lb_partition(n, p)
            else:
                part = partition_rows(n, fpms, eps)
            if base == "fpm-pad" and real:
                # Even pads only: the packed real row FFT transforms two
                # rows per complex FFT, and the half-spectrum crop
                # identity holds for any length >= n, so the model picks
                # among even beneficial lengths.
                from repro.plan.pads import rfft_pad_lengths
                pads = rfft_pad_lengths(fpms, part.d, n)
            elif base == "fpm-pad":
                from repro.plan.pads import fpm_pad_lengths
                pads = fpm_pad_lengths(fpms, part.d, n)
            elif base == "fpm-czt":
                from repro.plan.pads import czt_fft_lengths
                pads = czt_fft_lengths(fpms, part.d, n, limit_ratio=2.0)
            else:
                pads = None
    with obs.span("pfft.plan.schedule"):
        schedule, tuning = _resolve_schedule(n, method, part, pads, fpms,
                                             tune, wisdom, config, dtype,
                                             mesh=mesh, axis_name=axis_name)
    raw = _build_raw(n, method, part.d, schedule, mesh, axis_name, dtype)
    return PfftPlan(n=n, method=method, partition=part, pad_lengths=pads,
                    config=schedule.anchor_config, schedule=schedule,
                    tuning=tuning, _fn=jax.jit(raw), mesh=mesh,
                    axis_name=axis_name, dtype=dtype)


def rfft2(m: jnp.ndarray, *, p: int = 1, tune: TuneMode = "off",
          wisdom: str | None = None, mesh=None,
          axis_name: str = "fft") -> jnp.ndarray:
    """One-shot planned real-input 2-D DFT -> (N, N//2+1) half spectrum.

    Convenience wrapper: builds an ``rfft-lb`` plan for ``m``'s size and
    dtype and executes it once.  For the plan-once/run-many lifecycle
    (or the FPM methods) use ``plan_pfft(method='rfft-...')`` directly.
    """
    if m.ndim < 2 or m.shape[-1] != m.shape[-2]:
        raise ValueError(f"rfft2 plans square (N, N) signals, got {m.shape}")
    plan = plan_pfft(m.shape[-1], p=p, method="rfft-lb", tune=tune,
                     wisdom=wisdom, dtype=str(jnp.asarray(m).dtype),
                     mesh=mesh, axis_name=axis_name)
    return plan.execute(m)


def irfft2(h: jnp.ndarray, *, n: int | None = None) -> jnp.ndarray:
    """Inverse of ``rfft2``: half spectrum back to the real signal
    (``repro.fft.irfft2``; pass ``n`` for odd original lengths)."""
    from repro.fft.fft2d import irfft2 as _irfft2
    return _irfft2(h, n=n)


# ---------------------------------------------------------------------- 3-D

def _execute_many(plan, ms, shape: tuple[int, ...],
                  pad_to: int | None) -> list:
    """The shared cohort-stacking core of every plan's ``execute_many``:
    host-side stack (+ zero-pad to the bucket), one batched ``execute``,
    host-side unstack.  See ``PfftPlan.execute_many`` for why."""
    if not ms:
        return []
    arrs = [np.asarray(m) for m in ms]
    for m in arrs:
        if m.shape != shape:
            raise ValueError(
                f"execute_many stacks {shape} signals, got {m.shape}")
    batch = np.stack(arrs)
    b = len(arrs)
    if pad_to is not None and pad_to > b:
        batch = np.concatenate(
            [batch, np.zeros((pad_to - b,) + batch.shape[1:], batch.dtype)])
    out = np.asarray(plan.execute(batch))
    return [out[i] for i in range(b)]


@dataclasses.dataclass
class Pfft3Plan(_Scoped):
    """A planned 3-D transform — same plan/execute/wisdom lifecycle as
    ``PfftPlan``, for cubic N^3 signals.

    Distributed plans run the pencil pipeline (``pfft3_pencil``) on the
    captured 2-D mesh in the *tuned orientation* (which mesh axis plays
    row is a degree of freedom on rectangular meshes — see
    ``tune_pfft3``); single-host plans run ``pfft3_lb``'s axis passes.
    """
    n: int
    method: str
    config: PlanConfig
    tuning: dict[str, Any]
    _fn: Callable[[jnp.ndarray], jnp.ndarray]
    mesh: Any = None
    axis_names: tuple[str, str] | None = None
    dtype: str = "complex64"
    _batched_fns: dict[int, Callable] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def execute(self, m: jnp.ndarray) -> jnp.ndarray:
        """Run the planned transform; leading batch dims are vmapped
        (single-host plans only — the pencil program is already SPMD)."""
        if m.ndim < 3 or m.shape[-3:] != (self.n,) * 3:
            raise ValueError(
                f"plan is for ({self.n}, {self.n}, {self.n}) signals "
                f"(optionally with leading batch dims), got {m.shape}")
        if m.ndim > 3 and self.mesh is not None:
            raise ValueError(
                "distributed pfft3 plans transform one cube per call "
                "(vmapping over shard_map is not supported); loop instead")
        with obs.span("pfft.execute"):
            if m.ndim == 3:
                return self._fn(m)
            fn = self._batched_fns.get(m.ndim)
            if fn is None:
                fn = self._fn
                for _ in range(m.ndim - 3):
                    fn = jax.vmap(fn)
                fn = jax.jit(fn)
                self._batched_fns[m.ndim] = fn
            return fn(m)

    def input_spec(self) -> jax.ShapeDtypeStruct:
        spec = (*self.axis_names, None) if self.mesh is not None else ()
        return _sharded((self.n,) * 3, self.dtype, self.mesh, spec)

    def execute_many(self, ms, *, pad_to: int | None = None) -> list:
        """Serve a cohort of cubes in ONE batched dispatch — the 3-D
        sibling of ``PfftPlan.execute_many`` (same host-side stacking,
        zero-pad bucketing, and unstacking discipline)."""
        return _execute_many(self, ms, (self.n,) * 3, pad_to)


def plan_pfft3(n: int, *, p: int | None = None, mesh=None,
               axis_names: tuple[str, str] = ("fft_r", "fft_c"),
               tune: TuneMode = "off", wisdom: str | None = None,
               config: PlanConfig | None = None,
               dtype: str = "complex64") -> Pfft3Plan:
    """Plan the 3-D transform; see ``plan_pfft`` for the lifecycle.

    ``mesh=`` plans the pencil-parallel pipeline over a 2-D r x c mesh
    (both ``axis_names`` must exist on it; N must divide by both sizes):
    the wisdom key gains the mesh's 2-D ``topology_digest`` (schema v3 —
    '+'-joined per-axis terms, injective against 1-D and transposed
    meshes), ``tune="measure"`` races config x panel x *orientation*
    finalists through the full two-exchange pipeline end to end, and a
    measured winner persists with its orientation
    (``extra["pfft3_orientation"]``) so a second plan on the same mesh
    is served from disk with zero re-measurement.  Without a mesh the
    plan runs the single-host axis passes over an lb row partition of
    ``p`` segments (default 1).
    """
    if tune not in ("off", "estimate", "measure"):
        raise ValueError(f"tune must be 'off'|'estimate'|'measure', got {tune!r}")
    if np.dtype(dtype).kind != "c":
        raise ValueError(
            f"plan_pfft3 transforms complex input, got dtype={dtype!r}")
    from repro.core.pfft_dist import require_mesh_divisible
    from repro.plan.tune import pfft3_panel_space, tune_pfft3

    method = "pfft3-lb"
    axes0 = tuple(axis_names)
    if mesh is not None:
        if len(axes0) != 2:
            raise ValueError(
                f"plan_pfft3(mesh=...) needs two axis names, got {axes0!r}")
        r = int(mesh.shape[axes0[0]])
        c = int(mesh.shape[axes0[1]])
        require_mesh_divisible(n, r, axes0[0])
        require_mesh_divisible(n, c, axes0[1])
        q = r * c
        if p is not None and p != q:
            raise ValueError(f"p={p} conflicts with mesh {axes0[0]}x"
                             f"{axes0[1]} = {r}x{c} = {q} devices")
    else:
        # Single host: lb row partitions split unevenly by design, so any
        # 1 <= p <= n works (only the SPMD mesh path needs divisibility).
        r = c = 1
        q = int(p) if p is not None else 1
        if not 1 <= q <= n:
            raise ValueError(f"need 1 <= p <= N, got p={q} for N={n}")

    tuning: dict[str, Any] = {"mode": tune}
    axes: tuple[str, str] | None = axes0 if mesh is not None else None

    def build(cfg: PlanConfig, waxes) -> Pfft3Plan:
        if mesh is not None:
            from repro.core.pfft3d import pfft3_pencil
            raw = functools.partial(pfft3_pencil, mesh=mesh,
                                    axis_names=waxes, config=cfg)
        else:
            from repro.core.pfft3d import pfft3_lb
            raw = functools.partial(pfft3_lb, p=q, config=cfg)
        return Pfft3Plan(n=n, method=method, config=cfg, tuning=tuning,
                         _fn=jax.jit(raw), mesh=mesh, axis_names=waxes,
                         dtype=dtype)

    if config is not None:
        tuning["source"] = "explicit"
        return build(normalize_pad(config, "none"), axes)

    panels = pfft3_panel_space(n, r, c) if mesh is not None else (1,)
    topo = None
    if mesh is not None:
        topo = topology_digest(mesh, axes0, panels=panels)
        tuning["topology"] = topo
    key = wisdom_key(n=n, dtype=dtype, p=q, method=method,
                     backend=jax.default_backend(), topology=topo)
    tuning["wisdom_key"] = key
    if wisdom is not None:
        hit = lookup_wisdom(wisdom, key)
        if hit is not None:
            plan, entry = hit
            ok = isinstance(plan, PlanConfig)  # pencil plans are configs
            waxes = axes
            if ok and mesh is not None:
                stored = entry.get("pfft3_orientation")
                if stored is not None:
                    waxes = tuple(stored)
                    # Drifted orientation names are a miss, not an error.
                    ok = sorted(waxes) == sorted(axes0)
            if ok:
                tuning["source"] = "wisdom"
                tuning["wisdom_entry"] = entry
                return build(normalize_pad(plan, "none"), waxes)

    if tune == "off":
        tuning["source"] = "off"
        return build(PlanConfig(), axes)

    cfg, waxes, info = tune_pfft3(
        n, mesh, axes0 if mesh is not None else ("fft_r", "fft_c"),
        mode=tune, panels=panels if mesh is not None else None,
        dtype=np.dtype(dtype))
    tuning.update(info)
    tuning["source"] = tune
    if wisdom is not None and tune == "measure":
        extra: dict[str, Any] = {}
        if topo is not None:
            extra["topology"] = topo
        if waxes is not None:
            extra["pfft3_orientation"] = list(waxes)
        stats = info.get("pfft3", {})
        if stats.get("comm_time_meas_s") is not None:
            extra["comm_bytes"] = stats["comm_bytes"]
            extra["comm_time_s"] = stats["comm_time_meas_s"]
        if int(stats.get("hosts", 1)) > 1:
            extra["hosts"] = int(stats["hosts"])
        record_wisdom(wisdom, key, cfg, mode="measure",
                      time_s=info.get("time_s"), extra=extra or None)
    return build(cfg, waxes if mesh is not None else None)


# ------------------------------------------------------------------ huge 1-D

@dataclasses.dataclass
class Pfft1LargePlan(_Scoped):
    """A planned four-step huge-1-D transform (``core.pfft_large``)."""
    n: int
    n1: int
    n2: int
    method: str
    config: PlanConfig
    tuning: dict[str, Any]
    _fn: Callable[[jnp.ndarray], jnp.ndarray]
    dtype: str = "complex64"
    _batched_fns: dict[int, Callable] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def execute(self, x: jnp.ndarray) -> jnp.ndarray:
        """Run the planned transform; leading batch dims are vmapped."""
        if x.ndim < 1 or int(x.shape[-1]) != self.n:
            raise ValueError(
                f"plan is for length-{self.n} 1-D signals "
                f"(optionally with leading batch dims), got {x.shape}")
        with obs.span("pfft.execute"):
            if x.ndim == 1:
                return self._fn(x)
            fn = self._batched_fns.get(x.ndim)
            if fn is None:
                fn = self._fn
                for _ in range(x.ndim - 1):
                    fn = jax.vmap(fn)
                fn = jax.jit(fn)
                self._batched_fns[x.ndim] = fn
            return fn(x)

    def input_spec(self) -> jax.ShapeDtypeStruct:
        return _sharded((self.n,), self.dtype, None, ())

    def execute_many(self, xs, *, pad_to: int | None = None) -> list:
        """Serve a cohort of lines in ONE batched dispatch — the 1-D
        sibling of ``PfftPlan.execute_many``."""
        return _execute_many(self, xs, (self.n,), pad_to)


def plan_pfft1_large(n: int, *, tune: TuneMode = "off",
                     wisdom: str | None = None,
                     config: PlanConfig | None = None,
                     dtype: str = "complex64", n1: int | None = None,
                     n2: int | None = None) -> Pfft1LargePlan:
    """Plan one huge 1-D line through the EFFT four-step pipeline.

    ``n1``/``n2`` pin the factorization (default: most-square split —
    ``four_step_factors``); a non-default split enters the wisdom key as
    a ``part=`` detail, since the best row-FFT variant depends on which
    lengths the two phases actually run at.
    """
    if tune not in ("off", "estimate", "measure"):
        raise ValueError(f"tune must be 'off'|'estimate'|'measure', got {tune!r}")
    if np.dtype(dtype).kind != "c":
        raise ValueError(
            f"plan_pfft1_large transforms complex input, got dtype={dtype!r}")
    from repro.core.pfft_large import four_step_factors, pfft1_large_apply
    from repro.plan.tune import tune_pfft1_large

    method = "pfft1-large"
    f1, f2 = four_step_factors(n, n1=n1, n2=n2)
    default = four_step_factors(n)
    detail = f"{f1}x{f2}" if (f1, f2) != default else None

    tuning: dict[str, Any] = {"mode": tune, "n1": f1, "n2": f2}

    def build(cfg: PlanConfig) -> Pfft1LargePlan:
        raw = functools.partial(pfft1_large_apply, config=cfg, n1=f1, n2=f2)
        return Pfft1LargePlan(n=n, n1=f1, n2=f2, method=method, config=cfg,
                              tuning=tuning, _fn=jax.jit(raw), dtype=dtype)

    if config is not None:
        tuning["source"] = "explicit"
        return build(normalize_pad(config, "none"))

    key = wisdom_key(n=n, dtype=dtype, p=1, method=method,
                     backend=jax.default_backend(), detail=detail)
    tuning["wisdom_key"] = key
    if wisdom is not None:
        hit = lookup_wisdom(wisdom, key)
        if hit is not None:
            plan, entry = hit
            if isinstance(plan, PlanConfig):
                tuning["source"] = "wisdom"
                tuning["wisdom_entry"] = entry
                return build(normalize_pad(plan, "none"))

    if tune == "off":
        tuning["source"] = "off"
        return build(PlanConfig())

    cfg, info = tune_pfft1_large(n, n1=f1, n2=f2, mode=tune,
                                 dtype=np.dtype(dtype))
    tuning.update(info)
    tuning["source"] = tune
    if wisdom is not None and tune == "measure":
        record_wisdom(wisdom, key, cfg, mode="measure",
                      time_s=info.get("time_s"))
    return build(cfg)


def pfft1_large(x: jnp.ndarray, *, tune: TuneMode = "off",
                wisdom: str | None = None, n1: int | None = None,
                n2: int | None = None) -> jnp.ndarray:
    """One-shot planned four-step 1-D DFT of a long line.

    Convenience wrapper over ``plan_pfft1_large`` for ``x``'s length and
    dtype; use the plan directly for the plan-once/run-many lifecycle.
    """
    x = jnp.asarray(x)
    if x.ndim != 1:
        raise ValueError(
            f"pfft1_large transforms one 1-D line, got shape {x.shape}")
    dt = x.dtype if jnp.issubdtype(x.dtype, jnp.complexfloating) \
        else jnp.complex64
    plan = plan_pfft1_large(int(x.shape[0]), tune=tune, wisdom=wisdom,
                            dtype=str(np.dtype(dt)), n1=n1, n2=n2)
    return plan.execute(x.astype(dt))
