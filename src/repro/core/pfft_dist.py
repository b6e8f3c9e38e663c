"""Distributed PFFT on a jax device mesh (the TPU-pod adaptation).

The paper's 4-step pipeline maps onto a 1-D pencil decomposition over a mesh
axis: each device holds a contiguous block of rows; the paper's explicit
transpose steps become ``all_to_all`` collectives (this is the dominant
roofline term at pod scale — see DESIGN.md §Distributed pipeline).

``pipeline_panels=k`` chunks each local phase into ``k`` row panels and
software-pipelines them: panel ``i``'s ``all_to_all`` is issued before panel
``i+1``'s local FFT, so the dataflow lets the compiler overlap the
distributed transpose with compute instead of serializing the full-block
FFT against the full-block exchange (see DESIGN.md §Compute/communication
overlap).

    rows sharded (N/p, N) --local row FFT-->
    --all_to_all (split cols, concat rows) + local transpose-->
    cols sharded (N/p, N) --local row FFT (== column FFT)-->
    --all_to_all back + local transpose--> rows sharded, transformed.

Padding adaptation on TPU: the *local FFT length* is padded to an FPM-chosen
fast size (smooth / lane-aligned).  Two variants:

  * ``padded='crop'``  — the paper's PFFT-FPM-PAD semantics (padded-signal
    DFT cropped to N bins; spectral interpolation);
  * ``padded='czt'``   — exact N-point DFT via Bluestein at the padded
    length (beyond-paper, exactness preserved).

Uneven (HPOPTA) distributions across *heterogeneous device groups* are
realised block-ragged: the row axis is split into ``p`` equal SPMD shards,
but the FPM distribution decides how many of each shard's rows are real
work vs. masked padding; see ``ragged_row_layout``.

Heterogeneous *execution variants* are realised as device-group programs
(``repro.plan.groups``): a schedule whose entries pick different row-FFT
variants lowers to one SPMD program whose local phase branches per shard
via ``jax.lax.switch(jax.lax.axis_index(axis_name), ...)`` — one traced
branch per distinct config, every device meeting the others at the same
collectives, with the effective FFT length made uniform at the
schedule's max entry length (see DESIGN.md §Device-group programs).
"""

from __future__ import annotations

import functools
import warnings
from typing import Literal

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.padding import pad_to_smooth
from repro.core.pfft import czt_dft
from repro.fft.fft2d import fft_rows, fft_rows_then_transpose, rfft_rows
from repro.plan.config import PlanConfig
from repro.plan.groups import (DeviceGroupProgram, device_group_program,
                               spmd_program_config)
from repro.plan.schedule import SegmentSchedule

__all__ = ["pfft2_distributed", "rpfft2_distributed", "irpfft2_distributed",
           "make_pfft2_fn", "ragged_row_layout", "hier_all_to_all",
           "validate_spmd_schedule", "default_dist_pad_len",
           "require_mesh_divisible"]

# Inverse of PlanConfig.dist_padded: the ``padded`` vocabulary of this
# module mapped back onto the planner's pad strategies.
_PAD_FROM_PADDED = {"crop": "fpm", "czt": "czt", None: "none"}


def default_dist_pad_len(n: int, padded: str | None) -> int:
    """Default local FFT length under each padding semantics: the
    model-free smooth size for 'crop', the next pow2 >= 2N-1 for 'czt'
    (Bluestein's linear-convolution length), N otherwise.  The single
    home of the rule — ``pfft2_distributed`` applies it and the dist
    tuner's local-phase probe (``plan.tune``) must time the very same
    program the end-to-end race ran."""
    if padded == "crop":
        return pad_to_smooth(n)
    if padded == "czt":
        return 1 << int(np.ceil(np.log2(2 * n - 1)))
    return n


def require_mesh_divisible(n: int, p: int, axis_name: str) -> None:
    """The shared divisibility check of every distributed entry point: the
    mesh axis size must divide N (SPMD shards are equal-sized).  One home
    for the rule — and for the message, whose wording once drifted into
    the inverted "N must divide the mesh axis" in the 3-D path."""
    if int(p) > 0 and n % int(p):
        raise ValueError(
            f"N={n} must be divisible by mesh axis {axis_name}={int(p)}")


def _hier_groups(hosts: int, local: int) -> tuple[list, list]:
    """``axis_index_groups`` of the two hierarchical-exchange stages on a
    host-major axis: intra groups are each host's contiguous run of
    ``local`` positions, inter groups collect local rank ``L`` of every
    host."""
    intra = [[H * local + L for L in range(local)] for H in range(hosts)]
    inter = [[H * local + L for H in range(hosts)] for L in range(local)]
    return intra, inter


def hier_all_to_all(x: jnp.ndarray, *, axis_name: str, hosts: int,
                    local: int, split_axis: int,
                    concat_axis: int) -> jnp.ndarray:
    """Hierarchical tiled ``all_to_all`` over a host-major mesh axis —
    bit-identical output to the flat collective, different traffic shape.

    The flat tiled all_to_all sends one split-axis panel to each of the
    ``p - 1`` peers, ``p - local`` of which cross the slow inter-host
    tier.  This form runs two grouped stages instead: a local permutation
    reorders the ``p = hosts * local`` panels host-major -> local-major,
    an *intra-host* all_to_all (each host's contiguous group of ``local``
    devices) aggregates, per device, the panels bound for local rank L of
    every host, and an *inter-host* all_to_all (the ``local`` groups
    collecting rank L across hosts) delivers them in ``hosts - 1``
    slow-tier messages per device.  Panel algebra: after the grouped
    stages the received blocks concatenate in (host, local) lexicographic
    order — exactly the flat collective's peer order — and block (H, L)
    is that sender's panel for this device, so the result matches the
    flat exchange element for element (pinned by tests on the monolithic,
    pipelined, fused-transposed, and pencil layouts).

    Works for any (split_axis, concat_axis) pair with
    ``x.shape[split_axis] % p == 0``; the fused path's transposed
    exchange and the 3-D pencil rounds reuse it unchanged.
    """
    p = hosts * local
    shape = x.shape
    w = shape[split_axis] // p
    with obs.scope(obs.EXCHANGE):
        xs = x.reshape(shape[:split_axis] + (hosts, local, w)
                       + shape[split_axis + 1:])
        xs = xs.swapaxes(split_axis, split_axis + 1)
        x = xs.reshape(shape)
        intra, inter = _hier_groups(hosts, local)
        x = jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                               concat_axis=concat_axis, tiled=True,
                               axis_index_groups=intra)
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True,
                                  axis_index_groups=inter)


def _flat_a2a(axis_name: str, split_axis: int, concat_axis: int):
    """The tiled ``all_to_all`` over ``axis_name``, traced under the
    ``pfft.exchange`` scope."""
    def a2a(x: jnp.ndarray) -> jnp.ndarray:
        with obs.scope(obs.EXCHANGE):
            return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                      concat_axis=concat_axis, tiled=True)
    return a2a


def _exchange_fns(axis_name: str, host_shape: tuple[int, int] | None):
    """(a2a, a2a_t) for one phase: the flat collectives, or the
    hierarchical pair when the phase runs on a host-major axis with a
    non-degenerate (hosts > 1, local > 1) shape — degenerate hierarchies
    are the flat program with extra steps."""
    if host_shape is not None and host_shape[0] > 1 and host_shape[1] > 1:
        hosts, local = host_shape
        a2a = functools.partial(hier_all_to_all, axis_name=axis_name,
                                hosts=hosts, local=local,
                                split_axis=1, concat_axis=0)
        a2a_t = functools.partial(hier_all_to_all, axis_name=axis_name,
                                  hosts=hosts, local=local,
                                  split_axis=0, concat_axis=1)
        return a2a, a2a_t
    return _flat_a2a(axis_name, 1, 0), _flat_a2a(axis_name, 0, 1)


def _local_fft(block: jnp.ndarray, n: int, *, padded: str | None,
               pad_len: int, config: PlanConfig,
               backend: str | None) -> jnp.ndarray:
    """Row FFTs on a local block under the selected padding semantics."""
    if padded == "czt":
        return czt_dft(block, pad_len)
    kw = config.row_fft_kwargs(backend)
    with obs.scope(obs.ROWFFT):
        if padded == "crop" and pad_len > n:
            block = jnp.pad(block, ((0, 0), (0, pad_len - n)))
            return fft_rows(block, **kw)[:, :n]
        return fft_rows(block, **kw)


def _faulted_fft(fft, axis_name: str, axis_size: int | None):
    """Apply the fault layer's per-device slowdown to a local row-FFT.

    When the process-global ``FaultInjector`` has an active slowdown, the
    FFT is wrapped per mesh position: a ``lax.switch`` on
    ``axis_index(axis_name)`` routes each device to a ``repeated``
    variant that genuinely runs its FFT ``factor`` times (bit-identical
    output via exact power-of-two rescaling — work XLA can neither CSE
    nor DCE), so an injected straggler costs real wall time exactly
    where a thermally-throttled device would.  With no active fault the
    function is returned untouched — zero overhead — and callers that
    don't thread ``axis_size`` (single-host paths) are never wrapped.

    Injection is read at *trace* time: executors that cache jitted
    programs re-trace on the injector's ``epoch`` (``ResilientPlan``
    does; a plain jitted ``pfft2_distributed`` traced before the fault
    keeps running the healthy program, exactly like real hardware drift
    under an already-compiled binary).
    """
    if axis_size is None:
        return fft
    from repro.runtime.faults import get_injector, repeated  # lazy: no cycle
    reps = get_injector().local_repeats(int(axis_size))
    if reps is None:
        return fft
    distinct = sorted(set(reps))
    branch_of = jnp.asarray([distinct.index(r) for r in reps],
                            dtype=jnp.int32)
    branches = [repeated(fft, r) for r in distinct]

    def slowed(block: jnp.ndarray) -> jnp.ndarray:
        with obs.scope(obs.ROWFFT):
            b = branch_of[jax.lax.axis_index(axis_name)]
            return jax.lax.switch(b, branches, block)

    return slowed


def _grouped_local_fft(axis_name: str, n: int, *, padded: str | None,
                       pad_len: int, program: DeviceGroupProgram,
                       backend: str | None):
    """Per-shard branching row-FFT: one ``lax.switch`` branch per distinct
    config, selected by this device's position along ``axis_name``.

    Every device traces every branch (it is still one SPMD program) and
    executes its own; collectives stay *outside* the switch, so devices
    on different branches still meet at the same ``all_to_all``.  All
    branches transform at the uniform ``pad_len`` and crop back to N
    bins, so their output shapes — and the exchanged bin semantics —
    agree (the uniform-length rule of ``repro.plan.groups``).
    """
    branches = [
        functools.partial(_local_fft, n=n, padded=padded, pad_len=pad_len,
                          config=cfg, backend=backend)
        for cfg in program.configs]
    groups = jnp.asarray(np.asarray(program.group_of_device, dtype=np.int32))

    def fft(block: jnp.ndarray) -> jnp.ndarray:
        with obs.scope(obs.ROWFFT):
            gid = groups[jax.lax.axis_index(axis_name)]
            return jax.lax.switch(gid, branches, block)

    return fft


def _local_phase(block: jnp.ndarray, axis_name: str, n: int, *,
                 padded: str | None, pad_len: int, config: PlanConfig,
                 backend: str | None = None,
                 pipeline_panels: int = 1,
                 program: DeviceGroupProgram | None = None,
                 axis_size: int | None = None,
                 host_shape: tuple[int, int] | None = None) -> jnp.ndarray:
    """One (row FFT -> distributed transpose) phase on a local block.

    block: (n_loc, N) — this device's rows.  Returns (n_loc, N): this
    device's block of the *transposed, row-transformed* matrix.

    The phase executes its schedule entry's config.  ``config.fused``
    runs the local (row FFT, transpose) as one fused Pallas dispatch
    (``fft_rows_then_transpose``) and swaps the ``all_to_all`` axes to
    match — since ``a2a(X, split=1, concat=0).T == a2a(X.T, split=0,
    concat=1)``, the exchange consumes the transposed block directly and
    the intermediate row-major matrix never exists.  This is what routes
    the planner's fused pick to pods; unfused configs keep FFT →
    exchange → local transpose.

    With ``pipeline_panels=1`` the phase is monolithic: transform the
    whole block, then one tiled ``all_to_all`` (split the column axis
    into p panels, keep panel j from every peer, concat along rows).

    With ``pipeline_panels=k > 1`` the block's rows are chunked into ``k``
    panels and software-pipelined: panel ``i``'s all_to_all is issued
    *before* panel ``i+1``'s FFT, so the two have no data dependence and
    the exchange of one panel hides behind the compute of the next (the
    paper's overlap lever, restated for collectives).  Panel results are
    re-interleaved so the output is bit-identical in layout to the
    monolithic phase.

    ``program`` (a ``DeviceGroupProgram``) makes the local row-FFT branch
    per shard — ``_grouped_local_fft``'s ``lax.switch`` over one traced
    branch per distinct config — while the collective structure stays
    uniform; heterogeneous schedules never take the fused path (the
    grouped lowering rejects fused mixes eagerly).

    ``host_shape`` (hosts, local) routes the exchange through the
    hierarchical two-stage collective (``hier_all_to_all``) — same
    output, but the slow inter-host tier carries ``hosts - 1`` aggregated
    messages per device instead of one per remote peer; the panel
    pipeline then overlaps those inter-host rounds against the next
    panel's FFT exactly as it overlaps flat exchanges.  ``None`` (or a
    degenerate shape) is the flat collective.
    """
    fused = config.fused and padded is None and program is None
    a2a, a2a_t = _exchange_fns(axis_name, host_shape)
    if fused:
        fft_t = functools.partial(fft_rows_then_transpose, backend=backend)
    if program is not None:
        fft = _grouped_local_fft(axis_name, n, padded=padded,
                                 pad_len=pad_len, program=program,
                                 backend=backend)
    else:
        fft = functools.partial(_local_fft, n=n, padded=padded,
                                pad_len=pad_len, config=config,
                                backend=backend)
    fft = _faulted_fft(fft, axis_name, axis_size)
    if fused:
        fft_t = _faulted_fft(fft_t, axis_name, axis_size)
    n_loc = block.shape[0]
    k = pipeline_panels
    if k > 1 and n_loc % k:
        # Refuse the silent monolithic fallback: a direct caller (or
        # tuner drift) would time/run a different program than the one
        # requested.  pfft2_distributed validates divisibility before
        # building the phase, so reaching this is a caller bug.
        raise ValueError(
            f"_local_phase: pipeline_panels={k} must divide local rows "
            f"{n_loc}; refusing to silently run the monolithic phase "
            "instead of the requested pipelined one")
    if k <= 1:
        if fused:
            return a2a_t(fft_t(block))  # (N/p, N): a row-block of M^T
        out = a2a(fft(block))
        with obs.scope(obs.TRANSPOSE):
            return out.T

    c = n_loc // k  # rows per panel

    def panel(i: int) -> jnp.ndarray:
        with obs.scope(obs.TRANSPOSE):
            return block[i * c:(i + 1) * c]

    # Software pipeline: FFT panel 0; then alternate (issue all_to_all of
    # panel i, FFT panel i+1) so each exchange overlaps the next FFT.
    # Fused panels exchange transposed (see above); their gathered tiles
    # arrive already column-major, saving the transpose of the interleave.
    gathered = []
    current = fft_t(panel(0)) if fused else fft(panel(0))
    exchange = a2a_t if fused else a2a
    for i in range(1, k):
        in_flight = exchange(current)      # exchange panel i-1 ...
        nxt = panel(i)                     # ... while transforming i
        current = fft_t(nxt) if fused else fft(nxt)
        gathered.append(in_flight)
    gathered.append(exchange(current))

    with obs.scope(obs.TRANSPOSE):
        return _interleave_panels(gathered, c=c, fused=fused)


def _interleave_panels(gathered: list[jnp.ndarray], *, c: int,
                       fused: bool) -> jnp.ndarray:
    """The k exchanged panels of a pipelined phase as the monolithic
    phase's output ``(rows_out, N)``: output column ``q*n_loc + i*c + r``
    is row ``r`` of peer ``q``'s panel ``i``.

    Unfused, each ``g_i`` is ``(p*c, rows_out)``, the peer-major stack of
    every peer's panel-i rows; fused tiles arrive transposed, ``(rows_out,
    p*c)``.  Each panel is cut into its p peer blocks, the p*k blocks are
    joined in output order along the rows (unfused) or columns (fused),
    and the unfused result is transposed once.  The blocks stay 2-D and
    dense: a stack on a new second-minor axis tiles each panel one
    sublane of eight high on a TPU, and a 4-D transpose of the stacked
    panels lets the compiler move the last phase's transpose past the
    complex join, as a complex64 copy.
    """
    if fused:
        p = gathered[0].shape[1] // c
        return jnp.concatenate([g[:, q * c:(q + 1) * c] for q in range(p)
                                for g in gathered], axis=1)
    p = gathered[0].shape[0] // c
    return jnp.concatenate([g[q * c:(q + 1) * c] for q in range(p)
                            for g in gathered]).T


def validate_spmd_schedule(schedule: SegmentSchedule,
                           pad_len: int | None = None) -> PlanConfig:
    """Eagerly reject schedules that genuinely cannot lower to one SPMD
    program; return the schedule's *program config*.

    Heterogeneous schedules are no longer refused wholesale: per-device
    row-FFT variants lower as a device-group program (one ``lax.switch``
    branch per distinct config — ``repro.plan.groups``), and mixed
    effective lengths lower under the uniform-length rule (every branch
    transforms at the schedule's max entry length; an explicit
    ``pad_len`` overrides it).  What still raises — before any device
    work, at plan-build time in ``make_pfft2_fn`` and at the top of
    ``pfft2_distributed``, with the schedule's own ``describe()`` in the
    message — are mixes of the *program-level* knobs that shape the
    collective structure: pad strategy, ``fused``, ``pipeline_panels``
    (see ``repro.plan.groups.spmd_program_config``).  The returned
    config is the common one, or the anchor of a groupable mix (its
    program-level knobs are shared by every entry).
    """
    del pad_len  # mixed lengths always lower now; kept for API compat
    return spmd_program_config(schedule)


def _coerce_dist_config(config: PlanConfig | None,
                        schedule: SegmentSchedule | None,
                        padded: str | None,
                        use_stockham: bool | None,
                        pipeline_panels: int | None,
                        pad_len: int | None = None) -> PlanConfig:
    """Fold the legacy loose kwargs into a ``PlanConfig`` (deprecated shims).

    A ``schedule`` resolves to its *program config* (the common config,
    or the anchor of a heterogeneous-but-groupable mix — its shared
    program-level knobs drive ``padded``/``pipeline_panels`` below);
    ``validate_spmd_schedule`` raises eagerly for the mixes the grouped
    lowering genuinely cannot express.  ``pfft2_distributed`` builds the
    per-shard branching program itself (it knows the mesh size).
    """
    if schedule is not None:
        if config is not None:
            raise ValueError("pass either schedule= or config=, not both")
        config = validate_spmd_schedule(schedule, pad_len)
    if config is not None:
        if use_stockham is not None or pipeline_panels is not None:
            raise ValueError(
                f"pass either {'schedule=' if schedule is not None else 'config='}"
                " or the legacy kwargs (use_stockham/pipeline_panels), not both")
        if padded is not None and config.dist_padded != padded:
            raise ValueError(
                f"config.pad={config.pad!r} conflicts with padded={padded!r}")
        return config
    if use_stockham is not None or pipeline_panels is not None:
        warnings.warn(
            "pfft2_distributed: use_stockham=/pipeline_panels= are "
            "deprecated; pass config=PlanConfig(...) (see repro.plan)",
            DeprecationWarning, stacklevel=3)
    return PlanConfig(
        radix=2 if use_stockham else None,
        pad=_PAD_FROM_PADDED[padded],
        pipeline_panels=int(pipeline_panels) if pipeline_panels else 1)


def _resolve_dist_config(n: int, mesh: Mesh, axis_name: str, *, pad: str,
                         dtype, tune: str, wisdom: str | None,
                         pad_len: int | None
                         ) -> tuple[PlanConfig | SegmentSchedule, dict]:
    """Plan a raw ``pfft2_distributed`` call the way ``plan_pfft`` plans.

    Resolution order mirrors ``core.api._resolve_schedule``: wisdom hit
    (per-topology v3 key) > tuner > default.  A measured pick is recorded
    back — with its comm sample — so the next process on the same mesh is
    served from disk with zero re-measurement.  Keys use the method the
    pad strategy implies, so a ``plan_pfft(mesh=...)`` entry and a raw
    ``pfft2_distributed(tune=...)`` entry for the same problem coincide.
    A wisdom hit that persisted a full ``SegmentSchedule`` (a grouped
    pick included) is returned as the schedule, provided it still lowers
    to this mesh; anything that doesn't is a miss, never an error.
    """
    from repro.plan.calibrate import fit_cost_params
    from repro.plan.tune import dist_panel_space, tune_dist_config
    from repro.plan.wisdom import (lookup_wisdom, record_wisdom,
                                   topology_digest, wisdom_key)

    if tune not in ("off", "estimate", "measure"):
        raise ValueError(f"tune must be 'off'|'estimate'|'measure', got {tune!r}")
    p = int(mesh.shape[axis_name])
    panels = dist_panel_space(n, p)
    topo = topology_digest(mesh, axis_name, panels=panels)
    method = {"none": "lb", "fpm": "fpm-pad", "czt": "fpm-czt"}[pad]
    key = wisdom_key(n=n, dtype=np.dtype(dtype).name, p=p, method=method,
                     backend=jax.default_backend(), topology=topo)
    tuning: dict = {"mode": tune, "wisdom_key": key, "topology": topo}
    if wisdom is not None:
        hit = lookup_wisdom(wisdom, key)
        if hit is not None:
            plan, entry = hit
            if isinstance(plan, SegmentSchedule):
                # Served only when it still lowers to *this* mesh (a
                # hand-edited or drifted entry that cannot is a miss)
                # and its pad semantics match the requested strategy.
                try:
                    device_group_program(plan, p, pad_len=pad_len)
                except ValueError:
                    plan = None
                if plan is not None and plan.n == n \
                        and all(e.config.pad == pad for e in plan):
                    tuning["source"] = "wisdom"
                    tuning["wisdom_entry"] = entry
                    return plan, tuning
            elif plan.pad == pad:
                tuning["source"] = "wisdom"
                tuning["wisdom_entry"] = entry
                return plan, tuning
    if tune == "off":
        tuning["source"] = "off"
        return PlanConfig(pad=pad), tuning
    params = fit_cost_params(wisdom) if wisdom is not None else None
    cfg, info = tune_dist_config(n, mesh, axis_name, mode=tune, pad=pad,
                                 pad_len=pad_len, params=params,
                                 panels=panels, dtype=np.dtype(dtype))
    tuning.update(info)
    tuning["source"] = tune
    if wisdom is not None and tune == "measure" and "time_s" in info:
        extra = {"topology": topo}
        dist = info.get("dist", {})
        if dist.get("comm_time_meas_s") is not None:
            extra["comm_bytes"] = dist["comm_bytes"]
            extra["comm_time_s"] = dist["comm_time_meas_s"]
        if dist.get("comm_samples"):
            # Tier-tagged per-exchange samples (intra-/inter-host): what
            # ``fit_cost_params`` fits the two comm tiers from.
            extra["comm_samples"] = dist["comm_samples"]
        if int(dist.get("hosts", 1)) > 1:
            extra["hosts"] = int(dist["hosts"])
        record_wisdom(wisdom, key, cfg, mode="measure",
                      time_s=info["time_s"], extra=extra)
    return cfg, tuning


def _resolve_dist_plan_kw(n: int, mesh: Mesh, axis_name: str, *,
                          padded: str | None, dtype, tune: str,
                          wisdom: str | None,
                          pad_len: int | None) -> dict:
    """``_resolve_dist_config`` shaped as executor kwargs: ``{"config":
    cfg}`` or ``{"schedule": sched}`` — the one home of the
    pad-vocabulary mapping and the plan/schedule dispatch shared by
    ``pfft2_distributed`` and ``make_pfft2_fn``."""
    plan, _ = _resolve_dist_config(
        n, mesh, axis_name, pad=_PAD_FROM_PADDED[padded], dtype=dtype,
        tune=tune, wisdom=wisdom, pad_len=pad_len)
    key = "schedule" if isinstance(plan, SegmentSchedule) else "config"
    return {key: plan}


def pfft2_distributed(
    m: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "fft",
    *,
    config: PlanConfig | None = None,
    schedule: SegmentSchedule | None = None,
    padded: Literal["crop", "czt", None] = None,
    pad_len: int | None = None,
    use_stockham: bool | None = None,
    backend: str | None = None,
    pipeline_panels: int | None = None,
    tune: str = "off",
    wisdom: str | None = None,
) -> jnp.ndarray:
    """Distributed 2-D DFT of a square matrix sharded by rows over ``axis_name``.

    ``config`` selects the execution variant (``PlanConfig``): its ``pad``
    strategy maps to the ``padded`` semantics ('fpm' -> 'crop',
    'czt' -> 'czt'), ``radix`` picks the local row-FFT backend,
    ``fused`` collapses each local (row FFT, transpose) into one fused
    dispatch feeding a transposed ``all_to_all`` (the planner's fused
    pick carries to pods), and ``pipeline_panels=k`` overlaps each
    phase's all_to_all with compute by chunking the local rows into k
    software-pipelined panels (k must divide N/p; k=1 is the monolithic
    phase).  ``schedule`` routes a planner ``SegmentSchedule`` here: a
    homogeneous schedule executes its common config; a heterogeneous one
    lowers to a *device-group program* — the local phase branches per
    shard via ``lax.switch``, one traced branch per distinct config, at
    the schedule's max effective length (``repro.plan.groups``; mixes of
    pad/fused/pipeline_panels still raise the named SPMD error).  The
    loose ``use_stockham=``/``pipeline_panels=`` kwargs are deprecated
    shims.

    ``tune=``/``wisdom=`` plan the call when no explicit config/schedule
    is given: consult the per-topology wisdom store, tune on a miss
    (``tune="measure"`` times finalists end-to-end on *this* mesh), and
    record the measured pick — the same lifecycle ``plan_pfft(mesh=...)``
    runs, usable straight from the distributed entry point.

    ``pad_len``: FPM-chosen local FFT length (defaults to the model-free
    smooth size for 'crop', next pow2 >= 2N-1 for 'czt').
    """
    if (tune != "off" or wisdom is not None) and config is None \
            and schedule is None:
        resolved = _resolve_dist_plan_kw(
            m.shape[0], mesh, axis_name, padded=padded, dtype=m.dtype,
            tune=tune, wisdom=wisdom, pad_len=pad_len)
        config = resolved.get("config")
        schedule = resolved.get("schedule")
    config = _coerce_dist_config(config, schedule, padded, use_stockham,
                                 pipeline_panels, pad_len)
    if schedule is not None and pad_len is None:
        # The schedule's entries carry the FPM-chosen effective lengths —
        # the very thing the planner picked; honor them rather than the
        # model-free smooth default.  Mixed lengths lower under the
        # uniform-length rule: every device transforms at the max (the
        # program-level analog of ragged_row_layout — see plan.groups).
        pad_len = max(e.length for e in schedule)
    padded = config.dist_padded
    panels = config.pipeline_panels
    n = m.shape[0]
    p = mesh.shape[axis_name]
    require_mesh_divisible(n, p, axis_name)
    if panels > 1 and (n // p) % panels:
        raise ValueError(
            f"pipeline_panels={panels} must divide local rows {n // p}")
    if pad_len is None:
        pad_len = default_dist_pad_len(n, padded)
    program = None
    if schedule is not None and schedule.common_config is None:
        # Heterogeneous-but-groupable: lower to the device-group program
        # (one lax.switch branch per distinct config).  Raises the named
        # SPMD error when the entries cannot tile this mesh's shards.
        program = device_group_program(schedule, int(p), pad_len=pad_len)
        pad_len = program.pad_len  # the lowering owns the uniform length

    host_shape = None
    if config.exchange == "hier":
        # Hierarchy comes from the mesh, not the config: on a mesh with
        # no host-major structure the hier pick degrades to the flat
        # program (mesh_host_shape returns (1, p)) rather than raising —
        # a wisdom entry replayed onto a reshaped mesh stays correct.
        from repro.launch.mesh import mesh_host_shape
        host_shape = mesh_host_shape(mesh, axis_name)

    spec_rows = P(axis_name, None)
    phase = functools.partial(
        _local_phase, axis_name=axis_name, n=n, padded=padded,
        pad_len=pad_len, config=config, backend=backend,
        pipeline_panels=panels, program=program, axis_size=int(p),
        host_shape=host_shape)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec_rows,), out_specs=spec_rows,
        check_vma=False,
    )
    def _run(block):
        # Phase 1: row FFTs + distributed transpose.
        # Phase 2: (original-)column FFTs + distributed transpose back.
        return phase(phase(block))

    return _run(m)


# ---------------------------------------------------------------------------
# Real-input distributed pipeline: the all_to_all moves only half-spectrum
# panels — ~half the bytes per phase of the complex path.
# ---------------------------------------------------------------------------

def _validate_real_dist(config: PlanConfig | None,
                        schedule: SegmentSchedule | None) -> PlanConfig:
    """The real distributed path's program config, validated.

    The half-spectrum exchange reshapes both collectives, so the path
    supports the homogeneous, unfused, monolithic program shape (the one
    the real tuner races); panel pipelining / fused exchange / per-shard
    branching stay complex-path features for now and are refused eagerly
    with the schedule's own description.
    """
    if schedule is not None:
        if config is not None:
            raise ValueError("pass either schedule= or config=, not both")
        config = validate_spmd_schedule(schedule)
        if schedule.common_config is None:
            raise ValueError(
                "rpfft2_distributed runs homogeneous schedules only; "
                f"got {schedule.describe()}")
    if config is None:
        config = PlanConfig(real=True)
    if not config.real:
        raise ValueError(
            f"rpfft2_distributed needs a real config, got {config.describe()}")
    if config.fused or config.pipeline_panels > 1:
        raise ValueError(
            "the real distributed path is unfused and monolithic "
            f"(fused/panels are complex-path features), got {config.describe()}")
    if config.exchange != "flat":
        raise ValueError(
            "the real distributed path exchanges padded half-spectrum "
            "panels over the flat collective only (hier is a complex-path "
            f"feature for now), got {config.describe()}")
    return config


def rpfft2_distributed(
    m: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "fft",
    *,
    config: PlanConfig | None = None,
    schedule: SegmentSchedule | None = None,
    pad_len: int | None = None,
    backend: str | None = None,
) -> jnp.ndarray:
    """Distributed real-input 2-D DFT -> the (N, N//2+1) half spectrum.

    ``m`` is a real square matrix sharded by rows over ``axis_name``.
    Phase 1 rffts each device's rows (two real rows per complex FFT) and
    exchanges only the ``halfspec_cols(n, p)`` surviving spectral columns
    — the panel crossing the interconnect is ~half the complex path's
    bytes; phase 2 runs complex FFTs over the sharded spectral rows and
    exchanges the same half-width panel back.  ``config.pad='fpm'`` pads
    the local FFT length to ``pad_len`` with the padded-signal crop
    semantics, exactly like ``pfft2_distributed``; a homogeneous
    ``schedule`` is validated through ``validate_spmd_schedule`` and its
    max entry length becomes ``pad_len``.
    """
    from repro.plan.cost import halfspec_cols  # lazy: plan imports core

    config = _validate_real_dist(config, schedule)
    if schedule is not None and pad_len is None:
        pad_len = max(e.length for e in schedule)
    padded = config.dist_padded
    n = m.shape[0]
    if m.ndim != 2 or m.shape[1] != n:
        raise ValueError("PFFT operates on square N x N signal matrices")
    if not jnp.issubdtype(m.dtype, jnp.floating):
        raise ValueError(
            f"the real pipeline takes a real-valued matrix, got {m.dtype}")
    p = int(mesh.shape[axis_name])
    require_mesh_divisible(n, p, axis_name)
    if pad_len is None:
        pad_len = default_dist_pad_len(n, padded)
    nh = n // 2 + 1
    hc = halfspec_cols(n, p)
    kw = config.row_fft_kwargs(backend)
    a2a = _flat_a2a(axis_name, 1, 0)

    def local_rfft(block: jnp.ndarray) -> jnp.ndarray:
        with obs.scope(obs.ROWFFT):
            if padded == "crop" and pad_len > n:
                block = jnp.pad(block, ((0, 0), (0, pad_len - n)))
                return rfft_rows(block, **kw)[:, :nh]
            return rfft_rows(block, **kw)

    def local_fft(block: jnp.ndarray) -> jnp.ndarray:
        with obs.scope(obs.ROWFFT):
            if padded == "crop" and pad_len > n:
                block = jnp.pad(block, ((0, 0), (0, pad_len - n)))
                return fft_rows(block, **kw)[:, :n]
            return fft_rows(block, **kw)

    spec_rows = P(axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec_rows,), out_specs=spec_rows,
        check_vma=False,
    )
    def _run(block):
        # Phase 1: local rffts, pad the half spectrum to the p-divisible
        # panel width, exchange + transpose -> spectral rows sharded.
        h = local_rfft(block)                       # (n/p, nh)
        with obs.scope(obs.TRANSPOSE):
            h = jnp.pad(h, ((0, 0), (0, hc - nh)))  # (n/p, hc)
        h = a2a(h)
        with obs.scope(obs.TRANSPOSE):
            h = h.T                                 # (hc/p, n)
        # Phase 2: complex FFTs down the (original) columns, exchange the
        # half-width panel back -> row-sharded (n/p, hc).
        f = a2a(local_fft(h))
        with obs.scope(obs.TRANSPOSE):
            return f.T                              # (n/p, hc)

    out = _run(m)
    with obs.scope(obs.TRANSPOSE):
        return out[:, :nh]


def irpfft2_distributed(
    h: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "fft",
    *,
    n: int | None = None,
) -> jnp.ndarray:
    """Distributed inverse of ``rpfft2_distributed``.

    ``h`` is the (N, N//2+1) half spectrum sharded by rows; the result is
    the real (N, N) signal matrix, same sharding.  ``n`` is the original
    last-axis length (default assumes it was even).  Both collectives
    move the same half-width panel as the forward transform.
    """
    from repro.plan.cost import halfspec_cols  # lazy: plan imports core

    nh = h.shape[-1]
    if n is None:
        n = 2 * (nh - 1)
    if h.ndim != 2 or h.shape[0] != n:
        raise ValueError(
            f"expected the ({n}, {nh}) half spectrum, got {h.shape}")
    p = int(mesh.shape[axis_name])
    require_mesh_divisible(n, p, axis_name)
    hc = halfspec_cols(n, p)
    a2a = _flat_a2a(axis_name, 1, 0)

    spec_rows = P(axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec_rows,), out_specs=spec_rows,
        check_vma=False,
    )
    def _run(block):
        # Inverse column FFTs first (on the transposed, sharded spectral
        # rows), then the real inverse along rows.
        g = jnp.pad(block, ((0, 0), (0, hc - nh)))  # (n/p, hc)
        g = a2a(g).T                                # (hc/p, n)
        g = jnp.fft.ifft(g, axis=-1)
        g = a2a(g).T[:, :nh]                        # (n/p, nh)
        return jnp.fft.irfft(g, n=n, axis=-1)       # (n/p, n) real

    return _run(h)


def make_pfft2_fn(mesh: Mesh, n: int, axis_name: str = "fft", **kw):
    """jit-compiled distributed 2-D DFT closed over a mesh (sharded in/out).

    Planning happens *now*, not at first call: a ``schedule=`` is
    SPMD-validated eagerly — a heterogeneous one is lowered against this
    mesh's device count, so an ungroupable schedule is a build-time error
    with the schedule's ``describe()`` — and ``tune=``/``wisdom=``
    resolve to a concrete config before jit so measurement never runs
    inside a trace (the plan is keyed for complex64 signals, the
    pipeline's working dtype).
    """
    if kw.get("schedule") is not None:
        sched = kw["schedule"]
        validate_spmd_schedule(sched, kw.get("pad_len"))
        if sched.common_config is None:
            device_group_program(sched, int(mesh.shape[axis_name]),
                                 pad_len=kw.get("pad_len"))
    tune = kw.pop("tune", "off")
    wisdom = kw.pop("wisdom", None)
    if (tune != "off" or wisdom is not None) \
            and kw.get("config") is None and kw.get("schedule") is None:
        kw.update(_resolve_dist_plan_kw(
            n, mesh, axis_name, padded=kw.pop("padded", None),
            dtype=np.complex64, tune=tune, wisdom=wisdom,
            pad_len=kw.get("pad_len")))
    sharding = NamedSharding(mesh, P(axis_name, None))
    fn = functools.partial(pfft2_distributed, mesh=mesh, axis_name=axis_name, **kw)
    return jax.jit(fn, in_shardings=(sharding,), out_shardings=sharding)


def ragged_row_layout(d: np.ndarray, p: int) -> tuple[int, np.ndarray]:
    """Block-ragged realisation of an uneven HPOPTA distribution under SPMD.

    SPMD shards must be equal-sized, so each of the ``p`` groups gets a
    buffer of ``max(d)`` rows; group i's valid-row count is d[i] and the
    remainder is masked padding.  Returns (rows_per_shard, valid_counts).
    The waste max(d)*p - sum(d) is the price of SPMD on *homogeneous* pods —
    on heterogeneous fleets (where d is uneven because speeds genuinely
    differ) the time saved dominates; see DESIGN.md §Ragged layouts.
    """
    d = np.asarray(d, dtype=np.int64)
    if len(d) != p:
        raise ValueError("distribution length must equal group count")
    return int(d.max()), d.copy()
