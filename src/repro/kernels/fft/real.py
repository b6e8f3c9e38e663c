"""Pallas TPU kernel: batched *real* row FFT (pack-two-rows trick).

A real length-``n`` row has a conjugate-symmetric spectrum, so only the
``n//2+1`` Hermitian-unique bins need computing/storing.  Rather than a
separate real transform, this kernel packs **two real rows per complex
FFT** — the classic trick (Korotkevich's SMP 2-D Fourier code is built on
the same r2c subroutine structure):

    z = a + i*b          (a, b: consecutive real rows)
    Z = FFT(z)           (one complex transform, shared with the complex
                          kernel's ``dft_planes``)
    A[k] = (Z[k] + conj(Z[n-k])) / 2     = FFT(a)[k]
    B[k] = (Z[k] - conj(Z[n-k])) / 2i    = FFT(b)[k]

so the row phase runs *half* the complex FFTs.  The reversed-bin plane
``Z[(n-k) mod n]`` is built without a lane reversal (which Mosaic cannot
lower), in ``dft_digits``' order where bin ``k1 + n1*k2`` sits at
``[k1, k2]``: ``n - k`` is ``(n1 - k1) mod n1`` in the first digit and
``n2 - k2`` (``k1 == 0``) or ``n2 - 1 - k2`` (otherwise) in the second, so
one permutation product on each axis and a select by ``k1`` build it
(``reverse_digits``).

The kernel emits **full-width** ``(block_rows, n)`` output planes (lane
alignment: a ``n//2+1``-wide block would be misaligned for every n), and
the host-side op crops to the half spectrum after reassembly.  The crop
is free in practice — it fuses into the surrounding jit.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.kernels.fft.kernel import (bmm_left, compiler_params,
                                      dft_digits, dft_tables, frozen_f32,
                                      hi_dot, split_length, table_specs,
                                      to_natural, to_transposed)
from repro.kernels.fft.ops import resolve_call_params

__all__ = ["real_tables", "reverse_digits", "reverse_tables",
           "rfft_rows_pallas", "rfft_rows_op", "unpack_packed_fft"]

@functools.lru_cache(maxsize=None)
def reverse_tables(n: int) -> tuple[np.ndarray, ...]:
    """0/1 permutation tables for ``reverse_digits`` at length ``n``.

    ``(Qbin,)`` for a direct DFT, else ``(Qbin, P1, Qrev)``: with
    ``(n1, n2) = split_length(n)``, ``x @ Qbin`` takes lane ``(n2 - j) mod
    n2`` to lane ``j``, ``x @ Qrev`` takes lane ``n2 - 1 - j``, and ``P1 @
    x`` takes sublane ``(n1 - i) mod n1`` to sublane ``i``.
    """
    n1, n2 = split_length(n)
    j = np.arange(n2)
    qbin = np.zeros((n2, n2))
    qbin[(n2 - j) % n2, j] = 1.0
    if n1 == 1:
        return frozen_f32(qbin)
    qrev = np.zeros((n2, n2))
    qrev[n2 - 1 - j, j] = 1.0
    i = np.arange(n1)
    p1 = np.zeros((n1, n1))
    p1[i, (n1 - i) % n1] = 1.0
    return frozen_f32(qbin, p1, qrev)


def real_tables(n: int) -> tuple[np.ndarray, ...]:
    """Every table the packed real kernels take: forward DFT, then reverse."""
    return dft_tables(n) + reverse_tables(n)


def reverse_digits(x: jnp.ndarray, tables: tuple[jnp.ndarray, ...], *,
                   k1_major: bool = False) -> jnp.ndarray:
    """Bin ``(n - k) mod n`` at the place of bin ``k``, both in the
    ``dft_digits`` layout ``(rows * n1, n2)`` (``k1_major`` as there);
    ``tables`` is ``reverse_tables(n)``.  The products carry 0/1 weights
    at full f32 precision, so every output is exactly one input."""
    if len(tables) == 1:
        return hi_dot(x, tables[0])
    qbin, p1, qrev = tables
    n1, n2 = p1.shape[0], qbin.shape[0]
    rows = x.shape[0] // n1
    if k1_major:
        shape, axis = (n1, rows, n2), 0
        y = hi_dot(p1, x.reshape(n1, rows * n2)).reshape(rows * n1, n2)
    else:
        shape, axis = (rows, n1, n2), 1
        y = bmm_left(p1, x.reshape(shape)).reshape(rows * n1, n2)
    k1 = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.where(k1 == 0, hi_dot(y, qbin).reshape(shape),
                    hi_dot(y, qrev).reshape(shape))
    return out.reshape(rows * n1, n2)


def unpack_packed_fft(zr: jnp.ndarray, zi: jnp.ndarray,
                      rev: tuple[jnp.ndarray, ...], *,
                      k1_major: bool = False):
    """Split ``Z = FFT(a + i*b)`` planes into FFT(a) and FFT(b) planes.

    ``zr``/``zi`` and the returned ``(a_re, a_im, b_re, b_im)`` are in
    ``dft_digits`` layout, full width (callers crop to the half spectrum);
    ``rev`` is ``reverse_tables(n)``.  Pure jnp — runs inside the Pallas
    kernels and is unit-tested standalone against the complex oracle.
    """
    rzr = reverse_digits(zr, rev, k1_major=k1_major)
    rzi = reverse_digits(zi, rev, k1_major=k1_major)
    a_re = (zr + rzr) * 0.5
    a_im = (zi - rzi) * 0.5
    b_re = (zi + rzi) * 0.5
    b_im = (rzr - zr) * 0.5
    return a_re, a_im, b_re, b_im


def packed_spectra(a, b, table_refs, *, transposed: bool = False):
    """Kernel body shared by the plain and fused real kernels: load the
    tables, transform ``a + i b`` and unpack both spectra, returned as
    four natural-order ``(rows, n)`` planes, or ``(n, rows)`` ones when
    ``transposed`` (the fused kernel, which lays its digits out k1-major).
    """
    rows, n = a.shape
    tables = tuple(t[...] for t in table_refs)
    k = len(dft_tables(n))
    zr, zi = dft_digits(a, b, tables[:k], k1_major=transposed)
    finish = to_transposed if transposed else to_natural
    return tuple(finish(p, rows) for p in
                 unpack_packed_fft(zr, zi, tables[k:], k1_major=transposed))


def _rfft_kernel(a_ref, b_ref, *refs):
    *table_refs, aor_ref, aoi_ref, bor_ref, boi_ref = refs
    a_re, a_im, b_re, b_im = packed_spectra(a_ref[...], b_ref[...],
                                            table_refs)
    aor_ref[...] = a_re
    aoi_ref[...] = a_im
    bor_ref[...] = b_re
    boi_ref[...] = b_im


def rfft_rows_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_rows: int = 8,
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """pallas_call wrapper: two (pairs, n) real row planes -> four planes
    ``(FFT(a).re, FFT(a).im, FFT(b).re, FFT(b).im)``, each (pairs, n).

    pairs must be a multiple of block_rows (the op pads); n a power of two.
    """
    pairs, n = a.shape
    if pairs % block_rows:
        raise ValueError(
            f"pairs={pairs} not a multiple of block_rows={block_rows}")
    tables = real_tables(n)
    spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((pairs, n), a.dtype)] * 4
    fn = pl.pallas_call(
        _rfft_kernel,
        grid=(pairs // block_rows,),
        in_specs=[spec, spec, *table_specs(tables)],
        out_specs=[spec] * 4,
        out_shape=out_shape,
        compiler_params=compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )
    return fn(a, b, *tables)


def _pack_real_rows(x2: jnp.ndarray, block_rows: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """(rows, n) real -> f32 (even-row, odd-row) planes padded so the pair
    count is a block multiple, plus the original row count for cropping."""
    total = x2.shape[0]
    pairs = (total + 1) // 2
    padded_pairs = (pairs + block_rows - 1) // block_rows * block_rows
    padded_rows = 2 * padded_pairs
    with obs.scope(obs.SPLIT):
        if padded_rows != total:
            x2 = jnp.pad(x2, ((0, padded_rows - total), (0, 0)))
        x2 = x2.astype(jnp.float32)
        return x2[0::2], x2[1::2], total


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rfft_rows_op(
    x: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Real row FFT via the packed Pallas kernel.

    x: (..., rows, n) real -> (..., rows, n//2+1) complex half spectrum,
    matching ``jnp.fft.rfft(x, axis=-1)``.
    """
    n = x.shape[-1]
    nh = n // 2 + 1
    lead = x.shape[:-2]
    rows = x.shape[-2]
    x2 = x.reshape((-1, n))
    block_rows, interpret, limit = resolve_call_params(
        "rfft", n, (x2.shape[0] + 1) // 2, block_rows, interpret)
    a, b, total = _pack_real_rows(x2, block_rows)
    ar, ai, br, bi = rfft_rows_pallas(a, b, block_rows=block_rows,
                                      interpret=interpret,
                                      vmem_limit_bytes=limit)
    with obs.scope(obs.JOIN):
        spec_a = ar + 1j * ai
        spec_b = br + 1j * bi
        # Re-interleave the even/odd row pairs, then crop rows and bins.
        out = jnp.stack([spec_a, spec_b], axis=1).reshape(-1, n)[:total, :nh]
        out = out.astype(jnp.result_type(x, jnp.complex64))
        return (out.reshape(lead + (rows, nh)) if lead
                else out.reshape((rows, nh)))
