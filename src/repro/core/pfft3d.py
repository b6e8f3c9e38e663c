"""3-D DFT extension (the paper's stated future work, §VII), planner-grade.

The row-column decomposition generalises: a 3-D DFT is three passes of
batched 1-D FFTs with axis rotations between them.  Everything routes
through the same ``PlanConfig`` machinery as the 2-D pipeline:

* ``pfft3_lb`` / ``pfft3_fpm`` — LB / FPM partitioning of the *plane*
  dimension (x-y planes of the cube play the role the rows played in
  2-D), each segment's row FFTs running through the shared dispatch
  program ``core.pfft._group_row_ffts``;
* ``pfft3_fpm_pad`` — per-processor padded transform lengths from the
  FPMs.  The pad strategy is *semantics owned by the method*: any
  explicit config is normalized through ``plan.config.normalize_pad``
  (the PR-5 rule that never reached 3-D), so a drifted
  ``PlanConfig(pad="czt")`` still runs the paper's padded-signal crop;
* ``pfft3_slab`` — the legacy 1-D slab decomposition: three rounds of
  (local FFTs, all_to_all rotation) over one mesh axis;
* ``pfft3_pencil`` — the pencil decomposition on a 2-D ``(r, c)`` device
  mesh: each device owns an ``(N/r, N/c, N)`` pencil, so only *two*
  all_to_all rounds are needed (round 1 over the ``c`` axis, round 2
  over the ``r`` axis) instead of the slab's three, and each round's
  exchange is software-pipelined against the next panel's FFTs exactly
  like ``pfft2_distributed``'s panels.  Heterogeneous schedules lower as
  device-group programs (``repro.plan.groups``) branching on the
  flattened ``(r, c)`` device index.

Dataflow of the pencil (device (i, j), block axes in brackets):

    (N/r, N/c, N) [a0, a1, a2]   --FFT a2->k2--
    --all_to_all over c (split k2, concat a1) + swapaxes-->
    (N/r, N/c, N) [a0, k2, a1]   --FFT a1->k1--
    --all_to_all over r (split k1, concat a0) + moveaxis-->
    (N/c, N/r, N) [k2, k1, a0]   --FFT a0->k0--  => global [k2, k1, k0]

The final global transpose back to ``fftn`` order happens *outside*
``shard_map`` (a GSPMD reshard); ``transpose_back=False`` keeps the raw
[k2, k1, k0] layout for pipelines that consume it directly.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.fpm import FPMSet
from repro.core.partition import lb_partition, partition_rows
from repro.core.pfft import _group_row_ffts
from repro.core.pfft_dist import (_flat_a2a, _local_fft,
                                  default_dist_pad_len, hier_all_to_all,
                                  require_mesh_divisible,
                                  validate_spmd_schedule)
from repro.plan.config import PlanConfig, normalize_pad
from repro.plan.groups import DeviceGroupProgram, device_group_program
from repro.plan.schedule import SegmentSchedule

__all__ = ["pfft3_lb", "pfft3_fpm", "pfft3_fpm_pad", "pfft3_distributed",
           "pfft3_pencil", "pfft3_slab"]


def _require_cube(m: jnp.ndarray) -> int:
    if m.ndim != 3 or len(set(m.shape)) != 1:
        raise ValueError("pfft3 operates on cubic N^3 signals")
    return m.shape[0]


def _axis_pass(m: jnp.ndarray, d: np.ndarray, pads=None,
               config: PlanConfig | None = None,
               backend: str | None = None) -> jnp.ndarray:
    """Batched 1-D FFTs along the last axis, planes split per ``d`` over
    the leading axis.  Each segment's planes flatten to rows and run the
    shared dispatch program (``_group_row_ffts``) at that segment's
    effective length — the same pad-and-crop / czt semantics the 2-D
    segments execute, so 3-D pad handling can never drift again."""
    n = m.shape[-1]
    cfg = config if config is not None else PlanConfig()
    offs = np.concatenate([[0], np.cumsum(d)])
    outs = []
    with obs.scope(obs.ROWFFT):  # the segment slices and concatenation
        for i in range(len(d)):
            lo, hi = int(offs[i]), int(offs[i + 1])
            if hi == lo:
                continue
            seg = m[lo:hi]
            length = n
            if pads is not None and int(pads[i]) > n:
                length = int(pads[i])
            rows = _group_row_ffts(seg.reshape(-1, n), length, n, cfg,
                                   backend)
            outs.append(rows.reshape(seg.shape[:-1] + (n,)))
        return jnp.concatenate(outs, axis=0)


def _pfft3(m: jnp.ndarray, d: np.ndarray, pads=None,
           config: PlanConfig | None = None,
           backend: str | None = None) -> jnp.ndarray:
    """Three passes with axis rotation: z, then y, then x."""
    _require_cube(m)
    for _ in range(3):
        m = _axis_pass(m, d, pads, config, backend)  # FFT along last axis
        with obs.scope(obs.TRANSPOSE):
            m = jnp.moveaxis(m, -1, 0)       # rotate axes (z,y,x) -> (x,z,y)
    return m


def pfft3_lb(m: jnp.ndarray, p: int, *,
             config: PlanConfig | None = None,
             backend: str | None = None) -> jnp.ndarray:
    cfg = normalize_pad(config if config is not None else PlanConfig(),
                        "none")
    return _pfft3(m, lb_partition(m.shape[0], p).d, config=cfg,
                  backend=backend)


def pfft3_fpm(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
              config: PlanConfig | None = None,
              return_partition: bool = False):
    n = m.shape[0]
    cfg = normalize_pad(config if config is not None else PlanConfig(),
                        "none")
    part = partition_rows(n, fpms, eps)
    out = _pfft3(m, part.d, config=cfg)
    return (out, part) if return_partition else out


def pfft3_fpm_pad(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
                  config: PlanConfig | None = None,
                  return_partition: bool = False):
    """PFFT3-FPM-PAD: per-processor padded lengths from the FPMs, the
    paper's padded-signal semantics (DFT of the zero-padded signal
    cropped back to N bins, per pass).

    The method owns the pad strategy: any explicit ``config=`` is
    normalized to ``pad="fpm"`` (``normalize_pad``, shared with the 2-D
    entry points), and pad lengths come from the shared
    ``plan.pads.fpm_pad_lengths`` rather than a private copy of the
    selection loop."""
    from repro.plan.pads import fpm_pad_lengths  # lazy: plan imports core
    n = m.shape[0]
    cfg = normalize_pad(config if config is not None else PlanConfig(),
                        "fpm")
    part = partition_rows(n, fpms, eps)
    pads = fpm_pad_lengths(fpms, part.d, n)
    out = _pfft3(m, part.d, pads, config=cfg)
    return (out, part, pads) if return_partition else out


# ---------------------------------------------------------------- distributed

def _pencil_rows_fft(n: int, *, padded: str | None, pad_len: int,
                     config: PlanConfig, backend: str | None,
                     program: DeviceGroupProgram | None,
                     axis_names: tuple[str, str] | None, c: int):
    """Local row-FFT program on a 3-D block's last axis.

    Flattens the two leading (pencil) axes to rows, runs the 2-D local
    program (``_local_fft`` — crop / czt / plain, same as the 2-D
    pipeline), and reshapes back.  With a ``program``, the row FFT
    branches per device group via ``lax.switch`` on the *flattened*
    (r, c) device index ``idx_r * c + idx_c`` — the 2-D-mesh analog of
    ``_grouped_local_fft`` — while collectives stay outside the switch.
    """
    if program is None:
        fft = functools.partial(_local_fft, n=n, padded=padded,
                                pad_len=pad_len, config=config,
                                backend=backend)
    else:
        branches = [
            functools.partial(_local_fft, n=n, padded=padded,
                              pad_len=pad_len, config=cfg, backend=backend)
            for cfg in program.configs]
        groups = jnp.asarray(
            np.asarray(program.group_of_device, dtype=np.int32))
        ax_r, ax_c = axis_names

        def fft(rows: jnp.ndarray) -> jnp.ndarray:
            flat = jax.lax.axis_index(ax_r) * c + jax.lax.axis_index(ax_c)
            return jax.lax.switch(groups[flat], branches, rows)

    def run(block: jnp.ndarray) -> jnp.ndarray:
        a, b = block.shape[0], block.shape[1]
        return fft(block.reshape(a * b, block.shape[-1])).reshape(a, b, n)

    return run


def _pencil_phase(block: jnp.ndarray, fft3, a2a, rearrange, panels: int,
                  split_dim: int, concat_dim: int) -> jnp.ndarray:
    """One (local FFTs, all_to_all, local rearrange) pencil round.

    ``panels=k > 1`` software-pipelines the round: the block is chunked
    into ``k`` panels along ``split_dim`` — an axis the exchange does not
    touch, so the gathered panels concatenate back in order with no
    re-interleave — and panel ``i``'s all_to_all is issued before panel
    ``i+1``'s FFTs, letting the exchange hide behind the next panel's
    compute (the 2-D pipeline's overlap lever, restated for pencils).
    ``concat_dim`` is where ``split_dim`` lands after ``rearrange``.
    """
    if panels <= 1:
        out = a2a(fft3(block))
        with obs.scope(obs.TRANSPOSE):
            return rearrange(out)
    chunk = block.shape[split_dim] // panels

    def panel(i: int) -> jnp.ndarray:
        idx = [slice(None)] * 3
        idx[split_dim] = slice(i * chunk, (i + 1) * chunk)
        with obs.scope(obs.TRANSPOSE):
            return block[tuple(idx)]

    gathered = []
    current = fft3(panel(0))
    for i in range(1, panels):
        in_flight = a2a(current)       # exchange panel i-1 ...
        current = fft3(panel(i))       # ... while transforming panel i
        gathered.append(in_flight)
    gathered.append(a2a(current))
    with obs.scope(obs.TRANSPOSE):
        return jnp.concatenate([rearrange(g) for g in gathered],
                               axis=concat_dim)


def pfft3_pencil(
    m: jnp.ndarray,
    mesh: Mesh,
    axis_names: tuple[str, str] = ("fft_r", "fft_c"),
    *,
    config: PlanConfig | None = None,
    schedule: SegmentSchedule | None = None,
    pad_len: int | None = None,
    backend: str | None = None,
    transpose_back: bool = True,
) -> jnp.ndarray:
    """Distributed 3-D DFT on a 2-D device mesh (pencil decomposition).

    ``m`` is the (N, N, N) cube sharded ``P(ax_r, ax_c, None)``; each
    device owns an (N/r, N/c, N) pencil and the transform needs only two
    all_to_all rounds (see the module docstring's dataflow).
    ``config.pipeline_panels=k`` chunks each round into ``k``
    software-pipelined panels (k must divide both N/r and N/c);
    ``config.pad`` selects the local padding semantics exactly as in
    ``pfft2_distributed`` ('fpm' -> pad-and-crop, 'czt' -> Bluestein).
    A heterogeneous ``schedule`` lowers to a device-group program over
    the r*c flattened devices.  ``transpose_back=True`` (default)
    returns ``jnp.fft.fftn`` order; ``False`` keeps the raw
    [k2, k1, k0] layout (the transpose is a global reshard).
    """
    n = _require_cube(m)
    ax_r, ax_c = axis_names
    r = int(mesh.shape[ax_r])
    c = int(mesh.shape[ax_c])
    require_mesh_divisible(n, r, ax_r)
    require_mesh_divisible(n, c, ax_c)
    if schedule is not None:
        if config is not None:
            raise ValueError("pass either schedule= or config=, not both")
        config = validate_spmd_schedule(schedule)
        if pad_len is None:
            pad_len = max(e.length for e in schedule)
    if config is None:
        config = PlanConfig()
    if config.fused:
        raise ValueError(
            "the 3-D pencil pipeline is unfused (the fused kernel's "
            f"transposed exchange is a 2-D layout), got {config.describe()}")
    padded = config.dist_padded
    if pad_len is None:
        pad_len = default_dist_pad_len(n, padded)
    k = config.pipeline_panels
    if k > 1 and ((n // r) % k or (n // c) % k):
        raise ValueError(
            f"pipeline_panels={k} must divide both pencil extents "
            f"N/{ax_r}={n // r} and N/{ax_c}={n // c}")
    program = None
    if schedule is not None and schedule.common_config is None:
        program = device_group_program(schedule, r * c, pad_len=pad_len)
        pad_len = program.pad_len  # the lowering owns the uniform length
    fft3 = _pencil_rows_fft(n, padded=padded, pad_len=pad_len, config=config,
                            backend=backend, program=program,
                            axis_names=(ax_r, ax_c), c=c)
    a2a_c = _flat_a2a(ax_c, 2, 1)
    a2a_r = _flat_a2a(ax_r, 2, 0)
    if config.exchange == "hier":
        # On a host-major pencil mesh only the r axis spans hosts (the
        # c-axis communicators live inside one box — make_pfft3_mesh's
        # layout), so only round 2 takes the hierarchical form; with no
        # exploitable host shape it degrades to the flat round.
        from repro.launch.mesh import mesh_host_shape
        hosts_r, local_r = mesh_host_shape(mesh, ax_r)
        if hosts_r > 1 and local_r > 1:
            a2a_r = functools.partial(hier_all_to_all, axis_name=ax_r,
                                      hosts=hosts_r, local=local_r,
                                      split_axis=2, concat_axis=0)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(ax_r, ax_c, None),),
                       out_specs=P(ax_c, ax_r, None), check_vma=False)
    def _run(block):                       # (N/r, N/c, N)  [a0, a1, a2]
        # Round 1: FFT a2 -> k2, exchange over c (split k2, concat a1),
        # swap back to pencil layout.  Panels split a0 — untouched by the
        # exchange, so gathered panels concatenate in order.
        block = _pencil_phase(block, fft3, a2a_c,
                              lambda g: jnp.swapaxes(g, 1, 2), k,
                              split_dim=0, concat_dim=0)  # [a0, k2, a1]
        # Round 2: FFT a1 -> k1, exchange over r (split k1, concat a0).
        # Panels split a1, which moveaxis lands on axis 0.
        block = _pencil_phase(block, fft3, a2a_r,
                              lambda g: jnp.moveaxis(g, 0, -1), k,
                              split_dim=1, concat_dim=0)  # [k2, k1, a0]
        # Pass 3: FFT a0 -> k0; no exchange left.
        return fft3(block)                 # (N/c, N/r, N)  [k2, k1, k0]

    out = _run(m)
    if not transpose_back:
        return out
    # Outside shard_map: GSPMD reshards, and the result matches
    # jnp.fft.fftn bin for bin.
    with obs.scope(obs.TRANSPOSE):
        return jnp.transpose(out, (2, 1, 0))


def pfft3_slab(m: jnp.ndarray, mesh: Mesh, axis_name: str = "fft", *,
               config: PlanConfig | None = None,
               pad_len: int | None = None,
               backend: str | None = None) -> jnp.ndarray:
    """Distributed 3-D DFT, x-planes sharded over one mesh axis (slab).

    Each of the three passes FFTs the (local) last axis then performs the
    distributed axis rotation: a tiled all_to_all exchanging last-axis
    panels while concatenating along the sharded plane axis — three
    exchange rounds where the pencil needs two (the measured delta is the
    microbench's ``pfft3`` sweep).  Local FFTs run the shared
    ``_local_fft`` program under ``config``.
    """
    n = _require_cube(m)
    p = int(mesh.shape[axis_name])
    require_mesh_divisible(n, p, axis_name)
    cfg = config if config is not None else PlanConfig()
    padded = cfg.dist_padded
    if pad_len is None:
        pad_len = default_dist_pad_len(n, padded)
    fft3 = _pencil_rows_fft(n, padded=padded, pad_len=pad_len, config=cfg,
                            backend=backend, program=None, axis_names=None,
                            c=1)
    rotate = _flat_a2a(axis_name, 2, 0)
    if cfg.exchange == "hier":
        from repro.launch.mesh import mesh_host_shape
        hosts, local = mesh_host_shape(mesh, axis_name)
        if hosts > 1 and local > 1:
            rotate = functools.partial(hier_all_to_all, axis_name=axis_name,
                                       hosts=hosts, local=local,
                                       split_axis=2, concat_axis=0)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis_name, None, None),),
                       out_specs=P(axis_name, None, None), check_vma=False)
    def _run(block):                        # (n/p, n, n)
        for _ in range(3):
            block = fft3(block)
            # distributed rotation: split the transformed axis, concat the
            # sharded plane axis, then rotate locally.
            block = rotate(block)                                  # (n, n, n/p)
            with obs.scope(obs.TRANSPOSE):
                block = jnp.moveaxis(block, -1, 0)                 # (n/p, n, n)
        return block

    return _run(m)


def pfft3_distributed(m: jnp.ndarray, mesh: Mesh,
                      axis_name="fft", **kw) -> jnp.ndarray:
    """Distributed 3-D DFT; dispatches on the mesh decomposition.

    A single ``axis_name`` runs the 1-D slab path (``pfft3_slab``); a
    pair of axis names runs the two-exchange pencil path
    (``pfft3_pencil``).  Keyword arguments pass through.
    """
    if isinstance(axis_name, (tuple, list)):
        return pfft3_pencil(m, mesh, tuple(axis_name), **kw)
    return pfft3_slab(m, mesh, axis_name, **kw)
