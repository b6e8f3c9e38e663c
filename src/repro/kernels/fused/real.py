"""Pallas TPU kernel: fused *real* row FFT -> transposed write.

The real-pipeline sibling of ``kernels.fused.kernel``: each grid program
packs two real rows per complex FFT (see ``kernels.fft.real``), unpacks
the pair in VMEM, transposes both spectra in VMEM, and
writes them to their transposed tile positions.  The half-spectrum crop
happens host-side after reassembly — output tiles are full transform
length ``n`` high for lane alignment, exactly like the complex fused
kernel's output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.kernels.fft.kernel import compiler_params, table_specs
from repro.kernels.fft.ops import resolve_call_params
from repro.kernels.fft.real import (_pack_real_rows, packed_spectra,
                                    real_tables)

__all__ = ["rfft_rows_transpose_pallas", "rfft_rows_transpose_op"]


def _rfused_kernel(a_ref, b_ref, *refs):
    *table_refs, aor_ref, aoi_ref, bor_ref, boi_ref = refs
    a_re, a_im, b_re, b_im = packed_spectra(a_ref[...], b_ref[...],
                                            table_refs, transposed=True)
    aor_ref[...] = a_re
    aoi_ref[...] = a_im
    bor_ref[...] = b_re
    boi_ref[...] = b_im


def rfft_rows_transpose_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_rows: int = 8,
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Two (pairs, n) real row planes -> four transposed (n, pairs) planes
    ``(FFT(a).T.re, FFT(a).T.im, FFT(b).T.re, FFT(b).T.im)``.

    pairs must be a multiple of block_rows (the op pads); n a power of two.
    """
    pairs, n = a.shape
    if pairs % block_rows:
        raise ValueError(
            f"pairs={pairs} not a multiple of block_rows={block_rows}")
    tables = real_tables(n)
    in_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    out_spec = pl.BlockSpec((n, block_rows), lambda i: (0, i))
    out_shape = [jax.ShapeDtypeStruct((n, pairs), a.dtype)] * 4
    fn = pl.pallas_call(
        _rfused_kernel,
        grid=(pairs // block_rows,),
        in_specs=[in_spec, in_spec, *table_specs(tables)],
        out_specs=[out_spec] * 4,
        out_shape=out_shape,
        compiler_params=compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )
    return fn(a, b, *tables)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rfft_rows_transpose_op(
    x: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused ``rfft_rows(x).T`` via one Pallas dispatch.

    x: (rows, n) real -> (n//2+1, rows) complex, the transposed half
    spectrum — the phase-1 output of ``rfft2`` without the intermediate
    HBM matrix.
    """
    if x.ndim != 2:
        raise ValueError(f"fused op takes a 2-D matrix, got shape {x.shape}")
    rows, n = x.shape
    nh = n // 2 + 1
    block_rows, interpret, limit = resolve_call_params(
        "rfused", n, (rows + 1) // 2, block_rows, interpret)
    a, b, total = _pack_real_rows(x, block_rows)
    ar, ai, br, bi = rfft_rows_transpose_pallas(a, b, block_rows=block_rows,
                                                interpret=interpret,
                                                vmem_limit_bytes=limit)
    with obs.scope(obs.JOIN):
        spec_a = ar + 1j * ai   # (n, padded_pairs): columns are even rows
        spec_b = br + 1j * bi   # (n, padded_pairs): columns are odd rows
        # Re-interleave pair columns, then crop bins (rows) and columns.
        out = jnp.stack([spec_a, spec_b], axis=2).reshape(n, -1)[:nh, :total]
        return out.astype(jnp.result_type(x, jnp.complex64))
