"""Pallas TPU kernel: fused row FFT -> transposed write.

The unfused pipeline (steps 1-2 / 3-4 of ``fft2d_rowcol``) materialises the
row-transformed matrix in HBM, then a second kernel streams it back through
VMEM to transpose it.  This kernel fuses the two: each grid program loads a
``block_rows x n`` row block, runs the row DFT in VMEM with its digits
laid out k1-major (``dft_digits(k1_major=True)``), so that one 2-D
transpose of the digit planes leaves every row's spectrum as a column
(``to_transposed``), and writes the block directly to its transposed
tile position ``(0, i)`` of the ``(n, rows)`` output.  The intermediate HBM
matrix — 2 planes x rows x n x 4B of write + read traffic per phase —
disappears entirely; the transform pass IS the transpose pass (the EFFT /
Korotkevich fused-transform structure, arXiv:1409.5757 / arXiv:2008.07031).

The output block is ``(n, block_rows)``, so block_rows is its lane axis:
``ops.pick_block_rows(n, "fused")`` keeps it at 128 (or the whole row
count when that is smaller), ``ops.vmem_limit_for`` raises the
scoped-VMEM limit where 128 rows outgrow the default, and
``ops.tpu_unsupported`` names the lengths where they outgrow VMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fft.kernel import (compiler_params, dft_digits,
                                      dft_tables, table_specs, to_transposed)

__all__ = ["fft_rows_transpose_pallas"]


def _fused_kernel(re_ref, im_ref, *refs):
    *table_refs, ore_ref, oim_ref = refs
    rows = re_ref.shape[0]
    re, im = dft_digits(re_ref[...], im_ref[...],
                        tuple(t[...] for t in table_refs), k1_major=True)
    ore_ref[...] = to_transposed(re, rows)
    oim_ref[...] = to_transposed(im, rows)


def fft_rows_transpose_pallas(
    re: jnp.ndarray,
    im: jnp.ndarray,
    *,
    block_rows: int = 8,
    inverse: bool = False,
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rows, n) planes -> FFT along rows, written transposed as (n, rows).

    rows must be a multiple of block_rows (ops.py pads); n a power of two.
    """
    rows, n = re.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of block_rows={block_rows}")
    tables = dft_tables(n, inverse)
    in_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    out_spec = pl.BlockSpec((n, block_rows), lambda i: (0, i))
    out_shape = [
        jax.ShapeDtypeStruct((n, rows), re.dtype),
        jax.ShapeDtypeStruct((n, rows), im.dtype),
    ]
    fn = pl.pallas_call(
        _fused_kernel,
        grid=(rows // block_rows,),
        in_specs=[in_spec, in_spec, *table_specs(tables)],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        compiler_params=compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )
    return fn(re, im, *tables)
