"""Row-column 2-D DFT (paper §III-A) built from 1-D FFTs.

``fft2d_rowcol`` is the sequential algorithm the parallel methods decompose:
row FFTs -> transpose -> row FFTs -> transpose.  It reduces the O(N^4)
direct 2-D DFT to O(N^2 log N).

``fused=True`` collapses each (row FFT, transpose) pair into one Pallas
dispatch (``repro.kernels.fused``): the transformed row block is written
straight to its transposed tile, so the intermediate HBM matrix between
steps 1-2 and 3-4 never exists.  See DESIGN.md §Fused pipeline.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import obs
from repro.fft.fft1d import fft1d_stockham

__all__ = ["fft2d_rowcol", "fft_rows", "fft_rows_then_transpose",
           "irfft2", "rfft2", "rfft_rows", "rfft_rows_then_transpose"]


def fft_rows(m: jnp.ndarray, *, use_stockham: bool = False,
             backend: str | None = None) -> jnp.ndarray:
    """1-D FFT along the last axis.

    backend: None/'xla' -> jnp.fft; 'stockham' -> pure-jnp radix-2;
    'pallas' -> the Pallas TPU kernel (interpret-mode on CPU).  Power-of-two
    lengths required for stockham/pallas; XLA otherwise.
    """
    n = m.shape[-1]
    if backend is None:
        backend = "stockham" if use_stockham else "xla"
    with obs.scope(obs.ROWFFT):
        if backend == "pallas" and not (n & (n - 1)):
            from repro.kernels.fft.ops import fft_rows_op
            return fft_rows_op(m)
        if backend == "stockham" and not (n & (n - 1)):
            return fft1d_stockham(m)
        return jnp.fft.fft(m, axis=-1)


def fft_rows_then_transpose(m: jnp.ndarray, *,
                            backend: str | None = None) -> jnp.ndarray:
    """One fused phase: ``FFT_rows(m).T`` without the intermediate matrix.

    Dispatches to the fused Pallas kernel when it applies (2-D input,
    power-of-two row length, single-precision data — the kernel computes
    in f32 planes, so wider dtypes keep the full-precision path);
    otherwise computes the same value as ``fft_rows`` + ``swapaxes`` so
    callers can use it unconditionally.
    """
    n = m.shape[-1]
    eligible = (m.ndim == 2 and n > 1 and not (n & (n - 1))
                and jnp.result_type(m, jnp.complex64) == jnp.complex64)
    if eligible and backend in (None, "pallas", "fused"):
        from repro.kernels.fused.ops import fft_rows_transpose_op
        with obs.scope(obs.ROWFFT):
            return fft_rows_transpose_op(m)
    out = fft_rows(m, backend=backend)
    with obs.scope(obs.TRANSPOSE):
        return out.swapaxes(-1, -2)


def _packed_rfft(m: jnp.ndarray, fft_fn) -> jnp.ndarray:
    """Real row FFT by packing two real rows per complex transform.

    ``fft_fn`` runs a complex FFT along the last axis; the conjugate
    split recovers both spectra (kernels.fft.real runs the plane form of
    the same identity inside Pallas).  Returns the (..., rows, n//2+1)
    half spectrum.
    """
    rows, n = m.shape[-2], m.shape[-1]
    nh = n // 2 + 1
    with obs.scope(obs.JOIN):
        if rows % 2:
            pad = [(0, 0)] * (m.ndim - 2) + [(0, 1), (0, 0)]
            m = jnp.pad(m, pad)
        z = m[..., 0::2, :] + 1j * m[..., 1::2, :]
    with obs.scope(obs.ROWFFT):
        zf = fft_fn(z)
        zrev = jnp.concatenate([zf[..., :1], jnp.flip(zf[..., 1:], axis=-1)],
                               axis=-1)
        spec_a = 0.5 * (zf + jnp.conj(zrev))
        spec_b = -0.5j * (zf - jnp.conj(zrev))
        out = jnp.stack([spec_a, spec_b], axis=-2)
        out = out.reshape(out.shape[:-3] + (-1, n))
        return out[..., :rows, :nh]


def rfft_rows(m: jnp.ndarray, *, backend: str | None = None) -> jnp.ndarray:
    """1-D *real* FFT along the last axis -> (..., n//2+1) half spectrum.

    Same backend vocabulary as ``fft_rows``: 'pallas' runs the packed
    two-rows-per-FFT Pallas kernel, 'stockham' packs through the pure-jnp
    radix-2 Stockham, None/'xla' is the library rfft.  Power-of-two
    lengths required for the kernel backends, XLA otherwise.
    """
    n = m.shape[-1]
    if backend == "stockham" and m.ndim >= 2 and not (n & (n - 1)):
        return _packed_rfft(m, fft1d_stockham)
    with obs.scope(obs.ROWFFT):
        if backend == "pallas" and m.ndim >= 2 and not (n & (n - 1)):
            from repro.kernels.fft.real import rfft_rows_op
            return rfft_rows_op(m)
        return jnp.fft.rfft(m, axis=-1)


def rfft_rows_then_transpose(m: jnp.ndarray, *,
                             backend: str | None = None) -> jnp.ndarray:
    """One fused real phase: ``rfft_rows(m).T`` without the intermediate.

    Eligibility mirrors ``fft_rows_then_transpose`` (2-D input,
    power-of-two row length, f32-representable data); otherwise the
    unfused value, so callers can use it unconditionally.
    """
    n = m.shape[-1]
    eligible = (m.ndim == 2 and n > 1 and not (n & (n - 1))
                and jnp.result_type(m, jnp.complex64) == jnp.complex64)
    if eligible and backend in (None, "pallas", "fused"):
        from repro.kernels.fused.real import rfft_rows_transpose_op
        with obs.scope(obs.ROWFFT):
            return rfft_rows_transpose_op(m)
    out = rfft_rows(m, backend=backend)
    with obs.scope(obs.TRANSPOSE):
        return out.swapaxes(-1, -2)


def rfft2(m: jnp.ndarray, *, backend: str | None = None) -> jnp.ndarray:
    """Real-input 2-D DFT -> the (..., n_rows, n//2+1) half spectrum.

    Matches ``jnp.fft.rfft2``: real row FFTs (half the transforms via row
    packing), then full complex FFTs down the surviving half-spectrum
    columns.  Phase 2 is a plain complex ``fft_rows`` on the transposed
    half spectrum — the conjugate-symmetric half never materialises.
    """
    h = rfft_rows(m, backend=backend)
    with obs.scope(obs.TRANSPOSE):
        h = h.swapaxes(-1, -2)
    h = fft_rows(h, backend=backend)
    with obs.scope(obs.TRANSPOSE):
        return h.swapaxes(-1, -2)


def irfft2(h: jnp.ndarray, *, n: int | None = None) -> jnp.ndarray:
    """Inverse of ``rfft2``: (..., rows, nh) half spectrum -> real matrix.

    ``n`` is the last-axis length of the original signal; the default
    ``2 * (nh - 1)`` assumes it was even (pass ``n`` explicitly for odd).
    """
    if n is None:
        n = 2 * (h.shape[-1] - 1)
    g = jnp.fft.ifft(h, axis=-2)
    return jnp.fft.irfft(g, n=n, axis=-1)


def fft2d_rowcol(m: jnp.ndarray, *, use_stockham: bool = False,
                 fused: bool = False) -> jnp.ndarray:
    """2-D DFT via row-column decomposition, mirroring the paper's 4 steps:

      1. 1-D FFTs on rows
      2. transpose
      3. 1-D FFTs on rows (i.e. the original columns)
      4. transpose

    ``fused=True`` runs steps 1+2 and 3+4 as single fused dispatches
    (numerically equivalent; no intermediate HBM matrix).
    """
    if fused:
        m = fft_rows_then_transpose(m)              # steps 1+2
        m = fft_rows_then_transpose(m)              # steps 3+4
        return m
    m = fft_rows(m, use_stockham=use_stockham)      # step 1
    m = m.swapaxes(-1, -2)                          # step 2
    m = fft_rows(m, use_stockham=use_stockham)      # step 3
    m = m.swapaxes(-1, -2)                          # step 4
    return m
