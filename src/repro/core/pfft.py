"""PFFT-LB / PFFT-FPM / PFFT-FPM-PAD — the paper's parallel 2-D DFT methods.

Three layers:

1. **Abstract-processor (single-host) versions** — faithful to the paper's
   Algorithms 1/3/6/7: the N rows are split into ``p`` segments per the
   distribution ``d``; each segment's row FFTs run as a *separate* FFT call
   (on the CPU benchmark backend this is what makes the distribution
   performance-relevant, exactly like the paper's per-group
   ``fftw_plan_many_dft`` calls), then transpose, row FFTs again, transpose.

2. **PFFT-FPM-PAD** — each segment's row length is padded ``N -> N_padded_i``
   chosen from that processor's FPM (paper Alg. 7).  NOTE on semantics: like
   the paper (and its fftw implementation, which sets the transform size to
   N_padded), the padded method computes the DFT *of the zero-padded signal*
   cropped back to N bins — a spectral interpolation, not the exact N-point
   DFT.  Tests validate it against exactly that oracle.

3. **PFFT-FPM-CZT (beyond paper)** — exact N-point DFT with full padding
   freedom via the Bluestein/chirp-Z identity: the N-point DFT is computed
   with FFTs of any model-chosen length m >= 2N-1.  This keeps the paper's
   "run a faster larger FFT" win while preserving exactness.

The distributed (mesh / shard_map) versions live in ``repro.core.pfft_dist``.
"""

from __future__ import annotations

import dataclasses
import warnings

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core.fpm import FPMSet
from repro.core.partition import PartitionResult, lb_partition, partition_rows
from repro.fft.fft2d import fft_rows, rfft_rows
from repro.plan.config import PlanConfig, normalize_pad
from repro.plan.schedule import SegmentPlan, SegmentSchedule

__all__ = [
    "pfft_lb",
    "pfft_fpm",
    "pfft_fpm_pad",
    "pfft_fpm_czt",
    "rpfft_lb",
    "rpfft_fpm",
    "rpfft_fpm_pad",
    "czt_dft",
    "halfspec_distribution",
    "segment_row_ffts",
    "segment_row_rffts",
    "plan_segment_batches",
]


def _coerce_config(config: PlanConfig | None, caller: str, **flags) -> PlanConfig:
    """Fold the PR-1 loose booleans into a ``PlanConfig``.

    ``flags`` values of ``None`` mean "not passed"; any explicit value
    triggers a deprecation warning — the planner (``repro.plan``) owns
    variant selection now, and one config object is the only way every
    variant stays choosable from a single point.
    """
    passed = {k: v for k, v in flags.items() if v is not None}
    if config is not None:
        if passed:
            raise ValueError(
                f"{caller}: pass either config= or the legacy flags "
                f"({', '.join(sorted(passed))}), not both")
        return config
    if passed:
        warnings.warn(
            f"{caller}: the {', '.join(sorted(passed))} kwarg(s) are "
            "deprecated; pass config=PlanConfig(...) (see repro.plan)",
            DeprecationWarning, stacklevel=3)
    return PlanConfig.from_flags(**passed)


def _segments(d: np.ndarray) -> list[tuple[int, int]]:
    offs = np.concatenate([[0], np.cumsum(np.asarray(d))])
    return [(int(offs[i]), int(offs[i + 1])) for i in range(len(d))]


def plan_segment_batches(d: np.ndarray, pad_lengths, n: int, configs=None):
    """Group the segments of distribution ``d`` into dispatch batches.

    Without ``configs``, groups by effective FFT length alone and returns
    ``{fft_length: row_indices}``: all rows transformed at the same
    length form one batch — one FFT dispatch per distinct *plan*, the
    moral equivalent of the paper sharing an ``fftw_plan_many_dft`` across
    same-shaped groups.  len(result) is the dispatch count of the batched
    ``segment_row_ffts``.

    With ``configs`` (one ``PlanConfig`` per processor — a heterogeneous
    schedule's assignment), groups by ``(effective_length, config)`` and
    returns ``{(length, config): row_indices}``: same-length segments on
    *different* execution variants get different dispatches, so a slow
    segment can keep the library FFT while a fast one takes the kernel
    in the same phase (see ``repro.plan.schedule``).  A ``batched=False``
    config opts its segment out of sharing — those entries keep their
    per-segment key ``(length, config, index)`` so ``len(result)`` stays
    the executor's true dispatch count.
    """
    if configs is not None:
        sched = SegmentSchedule.from_parts(n, d, pad_lengths, list(configs))
        out: dict[tuple, np.ndarray] = {}
        for length, cfg, idx in sched.batch_groups():
            key = ((length, cfg) if cfg.batched
                   else (length, cfg, int(idx[0])))
            out[key] = idx
        return out
    groups: dict[int, list[np.ndarray]] = {}
    for i, (lo, hi) in enumerate(_segments(d)):
        if hi == lo:
            continue
        length = n
        if pad_lengths is not None and int(pad_lengths[i]) > n:
            length = int(pad_lengths[i])
        groups.setdefault(length, []).append(np.arange(lo, hi, dtype=np.int64))
    return {length: np.concatenate(idx) for length, idx in groups.items()}


def _row_fft(rows: jnp.ndarray, config: PlanConfig,
             backend: str | None) -> jnp.ndarray:
    """Row FFTs under ``config``'s backend (``backend`` is an explicit
    override, e.g. the test suite forcing the Pallas kernel)."""
    return fft_rows(rows, **config.row_fft_kwargs(backend))


def _group_row_ffts(rows: jnp.ndarray, length: int, n: int,
                    config: PlanConfig, backend: str | None) -> jnp.ndarray:
    """One dispatch group's program: transform ``rows`` at effective
    ``length`` under ``config``, cropped back to N bins.

    ``pad='czt'`` entries run the exact Bluestein transform at the
    entry's length (``czt_dft``); pad-and-crop entries zero-pad, FFT,
    and crop (the paper's padded-signal semantics); unpadded entries
    FFT in place.
    """
    if config.pad == "czt" and length > n:
        return czt_dft(rows, length)
    with obs.scope(obs.ROWFFT):
        if length > n:
            rows = jnp.pad(rows, ((0, 0), (0, length - n)))
            return _row_fft(rows, config, backend)[:, :n]
        return _row_fft(rows, config, backend)


def segment_row_ffts(m: jnp.ndarray, d: np.ndarray, *, pad_lengths=None,
                     config: PlanConfig | None = None,
                     schedule: SegmentSchedule | None = None,
                     use_stockham: bool | None = None,
                     backend: str | None = None,
                     batched: bool | None = None) -> jnp.ndarray:
    """Step 2/4 of PFFT-FPM: processor i runs row FFTs on its d_i rows.

    ``pad_lengths[i]`` (optional) is N_padded for processor i; rows are
    zero-padded to that length, transformed, and cropped back to N bins
    (or chirp-Z-transformed at it when the config says ``pad='czt'``).

    ``schedule`` (a ``repro.plan.SegmentSchedule``) is the general form:
    each segment executes its own entry's config, and dispatch groups are
    ``(effective_length, config)`` — same-length segments on the same
    variant share one FFT dispatch, segments on different variants get
    their own.  ``config`` is the homogeneous shim: it becomes the
    degenerate every-segment-alike schedule, whose grouping (by length,
    ``batched=True``) or per-segment loop (``batched=False``) reproduces
    the PR-2 behavior exactly.  The loose ``use_stockham=``/``batched=``
    kwargs are deprecated shims for the pre-planner API.
    """
    n = m.shape[-1]
    if schedule is not None:
        if (config is not None or pad_lengths is not None
                or use_stockham is not None or batched is not None):
            raise ValueError(
                "segment_row_ffts: pass either schedule= (which carries its "
                "own lengths) or config=/pad_lengths=/legacy flags, not both")
    else:
        config = _coerce_config(config, "segment_row_ffts",
                                use_stockham=use_stockham, batched=batched)
        schedule = SegmentSchedule.homogeneous(config, n, d, pad_lengths)
    if int(np.sum(np.asarray(d))) != m.shape[0]:
        raise ValueError(
            f"distribution sums to {int(np.sum(np.asarray(d)))} rows, "
            f"matrix has {m.shape[0]}")
    if schedule.total_rows != m.shape[0]:
        raise ValueError(
            f"schedule covers {schedule.total_rows} rows, "
            f"matrix has {m.shape[0]}")

    groups = schedule.batch_groups()
    if len(groups) == 1:
        # Single plan covering every row in order: one dispatch, no
        # gather/scatter at all.
        length, cfg, idx = groups[0]
        if len(idx) == m.shape[0] and np.array_equal(idx, np.arange(len(idx))):
            return _group_row_ffts(m, length, n, cfg, backend)
    with obs.scope(obs.ROWFFT):  # the segment gather/scatter
        out = jnp.zeros(m.shape, jnp.result_type(m, jnp.complex64))
        for length, cfg, idx in groups:
            res = _group_row_ffts(m[idx], length, n, cfg, backend)
            out = out.at[idx].set(res)
        return out


def _pfft_limb(m: jnp.ndarray, d: np.ndarray, *, pad_lengths=None,
               config: PlanConfig | None = None,
               schedule: SegmentSchedule | None = None,
               use_stockham: bool | None = None,
               fused: bool | None = None) -> jnp.ndarray:
    """Paper Algorithm 3 (PFFT_LIMB): rows -> T -> rows -> T.

    ``schedule`` runs each segment under its own entry's config (the
    heterogeneous executor); ``config`` is the homogeneous shim (it
    becomes the degenerate schedule).  A homogeneous ``fused=True``
    schedule with no per-segment padding runs each (row FFTs, transpose)
    phase as one fused Pallas dispatch — segmentation is then purely a
    scheduling notion, so the fused whole-matrix transform computes the
    identical value with no intermediate HBM matrix.  Padded
    distributions keep the segment path (the pad semantics are
    per-processor).  The loose ``use_stockham=``/``fused=`` kwargs are
    deprecated shims.
    """
    if schedule is not None:
        if (config is not None or pad_lengths is not None
                or use_stockham is not None or fused is not None):
            raise ValueError(
                "_pfft_limb: pass either schedule= (which carries its own "
                "lengths) or config=/pad_lengths=/legacy flags, not both")
    else:
        config = _coerce_config(config, "_pfft_limb",
                                use_stockham=use_stockham, fused=fused)
        schedule = SegmentSchedule.homogeneous(config, m.shape[-1], d,
                                               pad_lengths)
    if m.ndim != 2 or m.shape[0] != m.shape[1]:
        raise ValueError("PFFT operates on square N x N signal matrices")
    common = schedule.common_config
    if (common is not None and common.fused
            and all(e.length == schedule.n for e in schedule)):
        # Segmentation without padding is purely a scheduling notion, so
        # the whole-matrix fused transform computes the identical value.
        # fft2d_fused itself falls back to the unfused computation when
        # the kernel doesn't apply (non-pow2 N, dtypes wider than the
        # f32 planes).
        from repro.fft.fft2d import fft2d_fused
        return fft2d_fused(m)
    for _ in range(2):
        m = segment_row_ffts(m, d, schedule=schedule)
        with obs.scope(obs.TRANSPOSE):
            m = m.T
    return m


# ---------------------------------------------------------------------------
# Real-input (half-spectrum) variants: rows are real, phase 1 runs rffts
# (two rows per complex FFT), phase 2 transforms only the N//2+1
# Hermitian-unique spectral columns.
# ---------------------------------------------------------------------------

def halfspec_distribution(d: np.ndarray, nh: int) -> np.ndarray:
    """Clip a row distribution to the first ``nh`` half-spectrum rows.

    Phase 2 of the real pipeline transforms the ``nh = N//2+1`` surviving
    spectral rows; prefix-clipping keeps spectral row ``j < nh`` on the
    *same* processor that owns row ``j`` in the complex path, so a padded
    real transform computes exactly ``complex_result[:, :nh]`` (identical
    per-row pad lengths) — the property that keeps the tuner's
    real-vs-complex race apples-to-apples.
    """
    d = np.asarray(d)
    offs = np.concatenate([[0], np.cumsum(d)])
    lo = np.minimum(offs[:-1], nh)
    hi = np.minimum(offs[1:], nh)
    return (hi - lo).astype(np.int64)


def _clip_schedule(schedule: SegmentSchedule, d: np.ndarray,
                   nh: int) -> tuple[np.ndarray, SegmentSchedule]:
    """(clipped distribution, clipped schedule) covering ``nh`` rows.

    Entries keep their index/length/config; rows shrink per
    ``halfspec_distribution`` and emptied segments drop out.
    """
    d2 = halfspec_distribution(d, nh)
    entries = []
    for e in schedule.entries:
        rows = int(d2[e.index])
        if rows <= 0:
            continue
        entries.append(SegmentPlan(index=e.index, rows=rows,
                                   length=e.length, config=e.config))
    return d2, SegmentSchedule(n=schedule.n, entries=tuple(entries))


def _group_row_rffts(rows: jnp.ndarray, length: int, n: int,
                     config: PlanConfig, backend: str | None) -> jnp.ndarray:
    """One dispatch group's real phase-1 program: rfft ``rows`` at
    effective ``length``, cropped to the N//2+1 half spectrum.

    The crop identity: for any pad length L >= N, bins 0..N//2 of the
    length-L transform are exactly the first N//2+1 bins the complex
    pad-and-crop path keeps — so the padded real phase equals the padded
    complex phase's half spectrum, column for column.
    """
    nh = n // 2 + 1
    if config.pad == "czt":
        raise ValueError("the real pipeline has no Bluestein form "
                         "(PlanConfig rejects real+czt)")
    kwargs = config.row_fft_kwargs(backend)
    with obs.scope(obs.ROWFFT):
        if length > n:
            rows = jnp.pad(rows, ((0, 0), (0, length - n)))
            return rfft_rows(rows, **kwargs)[:, :nh]
        return rfft_rows(rows, **kwargs)


def segment_row_rffts(m: jnp.ndarray, d: np.ndarray, *, pad_lengths=None,
                      config: PlanConfig | None = None,
                      schedule: SegmentSchedule | None = None,
                      backend: str | None = None) -> jnp.ndarray:
    """Real phase 1: processor i runs row rffts on its d_i real rows.

    The (rows, N) real matrix comes back as the (rows, N//2+1) complex
    half spectrum; grouping/dispatch semantics are exactly
    ``segment_row_ffts``'s (same ``SegmentSchedule.batch_groups``).
    """
    n = m.shape[-1]
    nh = n // 2 + 1
    if schedule is not None:
        if config is not None or pad_lengths is not None:
            raise ValueError(
                "segment_row_rffts: pass either schedule= (which carries "
                "its own lengths) or config=/pad_lengths=, not both")
    else:
        if config is None:
            config = PlanConfig(real=True)
        schedule = SegmentSchedule.homogeneous(config, n, d, pad_lengths)
    if int(np.sum(np.asarray(d))) != m.shape[0]:
        raise ValueError(
            f"distribution sums to {int(np.sum(np.asarray(d)))} rows, "
            f"matrix has {m.shape[0]}")
    if schedule.total_rows != m.shape[0]:
        raise ValueError(
            f"schedule covers {schedule.total_rows} rows, "
            f"matrix has {m.shape[0]}")

    groups = schedule.batch_groups()
    if len(groups) == 1:
        length, cfg, idx = groups[0]
        if len(idx) == m.shape[0] and np.array_equal(idx, np.arange(len(idx))):
            return _group_row_rffts(m, length, n, cfg, backend)
    ctype = jnp.result_type(m, jnp.complex64)
    with obs.scope(obs.ROWFFT):  # the segment gather/scatter
        out = jnp.zeros((m.shape[0], nh), ctype)
        for length, cfg, idx in groups:
            res = _group_row_rffts(m[idx], length, n, cfg, backend)
            out = out.at[idx].set(res)
        return out


def _rpfft_limb(m: jnp.ndarray, d: np.ndarray, *, pad_lengths=None,
                config: PlanConfig | None = None,
                schedule: SegmentSchedule | None = None) -> jnp.ndarray:
    """Real PFFT_LIMB: real rows -> T -> complex rows on the half spectrum.

    Returns the (N, N//2+1) half spectrum of the 2-D DFT (``rfft2``
    layout).  Phase 1 rffts each segment (half the complex FFTs via row
    packing); phase 2 runs *complex* row FFTs over the nh surviving
    spectral rows under the prefix-clipped schedule
    (``halfspec_distribution``), so per-processor pad lengths apply to
    exactly the rows the complex path would pad.  A homogeneous
    ``fused=True`` schedule with no padding runs both phases as fused
    Pallas dispatches, like ``_pfft_limb``.
    """
    if schedule is not None:
        if config is not None or pad_lengths is not None:
            raise ValueError(
                "_rpfft_limb: pass either schedule= (which carries its own "
                "lengths) or config=/pad_lengths=, not both")
    else:
        if config is None:
            config = PlanConfig(real=True)
        schedule = SegmentSchedule.homogeneous(config, m.shape[-1], d,
                                               pad_lengths)
    if m.ndim != 2 or m.shape[0] != m.shape[1]:
        raise ValueError("PFFT operates on square N x N signal matrices")
    if not jnp.issubdtype(m.dtype, jnp.floating):
        raise ValueError(
            f"the real pipeline takes a real-valued matrix, got {m.dtype}")
    n = m.shape[-1]
    nh = n // 2 + 1
    common = schedule.common_config
    if (common is not None and common.fused
            and all(e.length == schedule.n for e in schedule)):
        from repro.fft.fft2d import (fft_rows_then_transpose,
                                     rfft_rows_then_transpose)
        h = rfft_rows_then_transpose(m)     # (nh, n)
        return fft_rows_then_transpose(h)   # (n, nh)
    h = segment_row_rffts(m, d, schedule=schedule)
    with obs.scope(obs.TRANSPOSE):
        h = h.T                                                # (nh, n)
    d2, sched2 = _clip_schedule(schedule, np.asarray(d), nh)
    h = segment_row_ffts(h, d2, schedule=sched2)
    with obs.scope(obs.TRANSPOSE):
        return h.T                                             # (n, nh)


def pfft_lb(m: jnp.ndarray, p: int, *, use_stockham: bool | None = None,
            fused: bool | None = None,
            config: PlanConfig | None = None) -> jnp.ndarray:
    """PFFT-LB (paper §III-B): even row distribution over p processors."""
    cfg = _coerce_config(config, "pfft_lb",
                         use_stockham=use_stockham, fused=fused)
    d = lb_partition(m.shape[0], p).d
    return _pfft_limb(m, d, config=cfg)


def pfft_fpm(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
             use_stockham: bool | None = None, fused: bool | None = None,
             config: PlanConfig | None = None,
             return_partition: bool = False):
    """PFFT-FPM (paper §III-C / Alg. 1): FPM-optimal (possibly imbalanced)
    row distribution, then the 4-step row-column pipeline."""
    n = m.shape[0]
    cfg = _coerce_config(config, "pfft_fpm",
                         use_stockham=use_stockham, fused=fused)
    part: PartitionResult = partition_rows(n, fpms, eps)
    out = _pfft_limb(m, part.d, config=cfg)
    return (out, part) if return_partition else out


def pfft_fpm_pad(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
                 use_stockham: bool | None = None,
                 config: PlanConfig | None = None,
                 return_partition: bool = False):
    """PFFT-FPM-PAD (paper §III-D): PFFT-FPM + per-processor row padding
    N -> N_padded_i determined from the FPMs (padded-signal DFT semantics).

    The method owns the pad strategy: any explicit ``config=`` is
    normalized to ``pad="fpm"`` (``normalize_pad``, shared with
    ``core.api``), so a drifted ``PlanConfig(pad="czt")`` still runs the
    paper's padded-signal crop rather than Bluestein."""
    from repro.plan.pads import fpm_pad_lengths  # lazy: plan imports core
    n = m.shape[0]
    cfg = _coerce_config(config, "pfft_fpm_pad", use_stockham=use_stockham)
    cfg = normalize_pad(cfg, "fpm")
    part = partition_rows(n, fpms, eps)
    pads = fpm_pad_lengths(fpms, part.d, n)
    out = _pfft_limb(m, part.d, pad_lengths=pads, config=cfg)
    return (out, part, pads) if return_partition else out


def _real_config(config: PlanConfig | None) -> PlanConfig:
    """Default/force the ``real`` flag for the rpfft entry points."""
    if config is None:
        return PlanConfig(real=True)
    return config if config.real else dataclasses.replace(config, real=True)


def rpfft_lb(m: jnp.ndarray, p: int, *,
             config: PlanConfig | None = None) -> jnp.ndarray:
    """Real-input PFFT-LB: even row distribution, half-spectrum output."""
    cfg = _real_config(config)
    d = lb_partition(m.shape[0], p).d
    return _rpfft_limb(m, d, config=cfg)


def rpfft_fpm(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
              config: PlanConfig | None = None,
              return_partition: bool = False):
    """Real-input PFFT-FPM: FPM-optimal row distribution, half-spectrum
    output.  The partition is computed for the full N rows (phase 1 sees
    all of them); phase 2 prefix-clips it to the half spectrum."""
    n = m.shape[0]
    cfg = _real_config(config)
    part: PartitionResult = partition_rows(n, fpms, eps)
    out = _rpfft_limb(m, part.d, config=cfg)
    return (out, part) if return_partition else out


def rpfft_fpm_pad(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
                  config: PlanConfig | None = None,
                  return_partition: bool = False):
    """Real-input PFFT-FPM-PAD: per-processor row padding chosen by
    ``rfft_pad_lengths`` (even lengths only), padded-signal DFT semantics
    — the output equals the complex ``pfft_fpm_pad`` result's first
    N//2+1 columns, bin for bin."""
    from repro.plan.pads import rfft_pad_lengths  # lazy: plan imports core
    n = m.shape[0]
    cfg = normalize_pad(_real_config(config), "fpm")
    part = partition_rows(n, fpms, eps)
    pads = rfft_pad_lengths(fpms, part.d, n)
    out = _rpfft_limb(m, part.d, pad_lengths=pads, config=cfg)
    return (out, part, pads) if return_partition else out


# ---------------------------------------------------------------------------
# Beyond paper: exact N-point DFT at arbitrary (model-chosen) FFT length.
# ---------------------------------------------------------------------------

def _czt_chirp(n: int) -> np.ndarray:
    """Bluestein chirp c_j = exp(-i*pi*(j^2 mod 2N)/N), j = 0..N-1.

    Computed host-side (N is static): ``jnp.arange(n)`` is int32 under
    the default x64-off config, so a traced ``j*j`` wraps for
    j >= 46341 and the chirp — hence the "exact" transform — would be
    silently wrong for every N > 46340.  ``np.int64`` squares stay exact
    to N ~ 2^31, and the reduced residue (< 2N) keeps the float64 angle
    small, which is the whole point of the mod-2N identity.
    """
    j = np.arange(n, dtype=np.int64)
    return np.exp(-1j * np.pi * ((j * j) % (2 * n)) / n)


def czt_dft(x: jnp.ndarray, m_fft: int | None = None) -> jnp.ndarray:
    """Exact N-point DFT along the last axis via Bluestein's chirp-Z trick.

    DFT_N(x)[k] = conj(c_k) * IFFT_m( FFT_m(x*conj(c)) * FFT_m(c') )[k]
    with chirp c_j = exp(i*pi*j^2/N) and any FFT length m >= 2N-1.  ``m_fft``
    is the model-chosen fast length (defaults to next power of two).
    """
    n = x.shape[-1]
    if m_fft is None:
        m_fft = 1 << int(np.ceil(np.log2(2 * n - 1)))
    if m_fft < 2 * n - 1:
        raise ValueError(f"m_fft={m_fft} < 2N-1={2 * n - 1}")
    ctype = jnp.result_type(x, jnp.complex64)
    chirp = jnp.asarray(_czt_chirp(n).astype(ctype))
    with obs.scope(obs.ROWFFT):
        a = jnp.zeros(x.shape[:-1] + (m_fft,), ctype).at[..., :n].set(
            x * chirp)
        # Kernel b_j = conj(chirp)_{|j|}, wrapped for circular convolution.
        b = jnp.zeros(m_fft, ctype)
        b = b.at[:n].set(jnp.conj(chirp))
        b = b.at[m_fft - n + 1:].set(jnp.conj(chirp)[1:n][::-1])
        conv = jnp.fft.ifft(jnp.fft.fft(a, axis=-1) * jnp.fft.fft(b),
                            axis=-1)
        return (conv[..., :n] * chirp).astype(ctype)


def pfft_fpm_czt(m: jnp.ndarray, fpms: FPMSet, eps: float = 0.05, *,
                 return_partition: bool = False):
    """PFFT-FPM with exact padded transforms: each processor runs its row
    DFTs through the chirp-Z identity at an FPM-chosen smooth FFT length.
    Output equals the exact 2-D DFT (unlike PFFT-FPM-PAD's interpolation).

    Executes through the schedule path, so same-length czt segments share
    one Bluestein dispatch (``plan_segment_batches`` semantics)."""
    from repro.plan.pads import czt_fft_lengths  # lazy: plan imports core
    n = m.shape[0]
    part = partition_rows(n, fpms, eps)
    lens = czt_fft_lengths(fpms, part.d, n, limit_ratio=2.0)
    out = _pfft_limb(m, part.d, pad_lengths=lens,
                     config=PlanConfig(pad="czt"))
    return (out, part, lens) if return_partition else out
