"""Pallas TPU kernel: batched row FFT as DFT matrix products on the MXU.

TPU adaptation of the paper's 1D_ROW_FFTS_LOCAL hot loop.  Design notes:

* Complex data is carried as two f32 planes (re, im) — TPU Pallas has no
  complex dtype.  A complex product by a DFT matrix ``F = C + iS`` is
  three real matrix products, not four: the tables hold ``C``, ``C + S``
  and ``S - C`` (``dft_tables``), and for data ``a + ib``
  ``t1 = C (a + b)``, ``t2 = (C + S) b``, ``t3 = (S - C) a`` give
  ``Re = t1 - t2`` and ``Im = t1 + t3`` (``CMUL_PRODUCTS``).
* Rows of length ``n <= 512`` are transformed directly: ``X = x @ F`` with
  the ``n x n`` DFT matrix ``F``.
* Longer rows use the four-step split ``n = n1 * n2`` with ``n2 = 128``
  (one lane tile) and ``n1 >= 8`` (one sublane tile; smaller ``n1`` pads
  every tile and blows the VMEM budget): each row is viewed as an
  ``(n1, n2)`` matrix, then
    1. length-``n1`` DFTs down its columns (a batched ``F1 @ x``),
    2. a pointwise twiddle ``W_n^(k1 * b)``,
    3. length-``n2`` DFTs along its rows (one ``(rows*n1, n2) @ F2``
       product for the whole block),
    4. a digit transpose ``(n1, n2) -> (n2, n1)``, after which the row is
       in natural bin order ``k = k1 + n1 * k2``.
  Every in-kernel view keeps the lane axis whole or splits it at multiples
  of 128, the only lane reshapes Mosaic lays out; there is no ``rev``, no
  float iota and no gather.
* The DFT, twiddle and permutation matrices are built once per length on
  the host in float64 (``dft_tables``) and passed as kernel inputs whose
  block index never changes, so they are copied to VMEM once per call.
* Products run at ``precision=HIGHEST`` (multi-pass bf16 on the MXU): the
  planes are f32 data and a single bf16 pass would lose ~3 digits.
* Grid is over row blocks: each program transforms ``block_rows`` rows of
  length ``n`` in VMEM.  ``ops.pick_block_rows`` sizes the block.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "CMUL_PRODUCTS",
    "LANES",
    "bmm_left",
    "compiler_params",
    "dft_planes",
    "dft_digits",
    "dft_macs",
    "dft_tables",
    "fft_rows_pallas",
    "frozen_f32",
    "hi_dot",
    "split_length",
    "table_specs",
    "to_natural",
    "to_transposed",
]

LANES = 128  # TPU vreg lane width; the four-step inner length n2
_DIRECT_MAX = 512  # longest row transformed by one DFT product
_HI = jax.lax.Precision.HIGHEST
CMUL_PRODUCTS = 3  # real matrix products per complex product by a table


def split_length(n: int) -> tuple[int, int]:
    """Four-step factors ``(n1, n2)`` of a power-of-two length: ``(1, n)``
    (a direct DFT) up to 512, else ``(n // 128, 128)``."""
    if n < 1 or n & (n - 1):
        raise ValueError(f"length {n} must be a power of two")
    n2 = n if n <= _DIRECT_MAX else LANES
    return n // n2, n2


def _cis(num: np.ndarray, den: int, sign: float) -> np.ndarray:
    """exp(sign * 2*pi*i * num / den) in float64, ``num`` reduced mod den
    first so large index products keep full precision."""
    return np.exp(sign * 2j * np.pi * (num % den) / den)


def frozen_f32(*arrays: np.ndarray) -> tuple[np.ndarray, ...]:
    """Read-only contiguous f32 copies (cached tables are shared)."""
    out = []
    for a in arrays:
        a = np.ascontiguousarray(a, dtype=np.float32)
        a.flags.writeable = False
        out.append(a)
    return tuple(out)


def _three_product(f: np.ndarray) -> tuple[np.ndarray, ...]:
    """``(C, C + S, S - C)`` of a complex table ``F = C + iS``, formed in
    float64 before the one rounding to f32."""
    return f.real, f.real + f.imag, f.imag - f.real


@functools.lru_cache(maxsize=None)
def dft_tables(n: int, inverse: bool = False) -> tuple[np.ndarray, ...]:
    """Read-only f32 tables ``dft_planes`` multiplies by.

    Each DFT matrix ``F = C + iS`` is held as ``(C, C + S, S - C)``, the
    three-product form ``_cmul`` takes.  ``n <= 512``: the ``n x n`` DFT
    matrix's three.  Longer: ``(F1c, F1p, F1m, Tr, Ti, F2c, F2p, F2m)`` —
    the ``n1 x n1`` column DFT, the ``n1 x n2`` twiddle (re, im: a
    pointwise product) and the ``n2 x n2`` row DFT.  The inverse
    conjugates every table and folds ``1/n`` into the last one.
    """
    n1, n2 = split_length(n)
    sign = 1.0 if inverse else -1.0
    i2 = np.arange(n2)
    f2 = _cis(np.outer(i2, i2), n2, sign) / (n if inverse else 1)
    if n1 == 1:
        return frozen_f32(*_three_product(f2))
    i1 = np.arange(n1)
    f1 = _cis(np.outer(i1, i1), n1, sign)
    tw = _cis(np.outer(i1, i2), n, sign)
    return frozen_f32(*_three_product(f1), tw.real, tw.imag,
                      *_three_product(f2))


def dft_macs(rows: int, n: int) -> int:
    """Real multiply-accumulates the DFT products of ``dft_digits`` perform
    on ``(rows, n)`` planes, before the precision passes: ``n`` complex
    MACs an element for a direct DFT, ``n1 + n2`` for the four-step."""
    n1, n2 = split_length(n)
    return CMUL_PRODUCTS * rows * n * (n if n1 == 1 else n1 + n2)


def hi_dot(a, b):
    """f32 matrix product at full f32 precision (multi-pass on the MXU)."""
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _cmul(dot, xr, xi, c, cps, smc):
    """Complex product of data ``xr + i xi`` by a table held as ``(C, C +
    S, S - C)`` in three real products; ``dot(table, x)`` is the product
    in the caller's order."""
    t1 = dot(c, xr + xi)
    return t1 - dot(cps, xi), t1 + dot(smc, xr)


def _cdot(xr, xi, *table):
    """Complex ``(xr + i xi) @ F``."""
    return _cmul(lambda f, x: hi_dot(x, f), xr, xi, *table)


def bmm_left(f, x):
    """``f @ x[r]`` for every ``r`` of a ``(rows, m, k)`` batch."""
    return jnp.einsum("ka,rab->rkb", f, x, precision=_HI,
                      preferred_element_type=jnp.float32)


def dft_digits(re: jnp.ndarray, im: jnp.ndarray,
               tables: tuple[jnp.ndarray, ...], *, k1_major: bool = False):
    """Steps 1-3 on ``(rows, n)`` planes -> ``(rows * n1, n2)`` planes of
    digits: bin ``k1 + n1 * k2`` of row ``r`` sits in row ``r * n1 + k1``
    (or ``k1 * rows + r`` when ``k1_major``), lane ``k2``.  For a direct
    DFT (``n1 == 1``) that is already natural order.

    ``tables`` is ``dft_tables(n, inverse)`` (as arrays or loaded values).
    """
    rows, n = re.shape
    n1, n2 = split_length(n)
    if n1 == 1:
        return _cdot(re, im, *tables)
    f1, (twr, twi), f2 = tables[:3], tables[3:5], tables[5:]
    if k1_major:
        def view(x):  # [a, (r, b)]
            x = jnp.swapaxes(x.reshape(rows, n1, n2), 0, 1)
            return x.reshape(n1, rows * n2)
        yr, yi = _cmul(hi_dot, view(re), view(im), *f1)       # step 1
        yr, yi = yr.reshape(n1, rows, n2), yi.reshape(n1, rows, n2)
        twr, twi = twr[:, None, :], twi[:, None, :]
    else:
        yr, yi = _cmul(bmm_left, re.reshape(rows, n1, n2),
                       im.reshape(rows, n1, n2), *f1)        # step 1
    zr = yr * twr - yi * twi                                  # step 2
    zi = yr * twi + yi * twr
    return _cdot(zr.reshape(rows * n1, n2), zi.reshape(rows * n1, n2),
                 *f2)                                         # step 3


def to_natural(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Step 4 for row-major digits -> ``(rows, n)`` natural bin order.

    The ``(rows, n2, n1)`` intermediate has ``n1`` lanes: below 128 it is
    padded to a full lane tile, which costs VMEM and time in proportion
    to ``128 / n1`` (see ``ops.pick_block_rows``)."""
    n1, n2 = x.shape[0] // rows, x.shape[1]
    if n1 == 1:
        return x
    return jnp.swapaxes(x.reshape(rows, n1, n2), 1, 2).reshape(rows, n1 * n2)


def to_transposed(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Step 4 for k1-major digits -> ``(n, rows)``: the natural-order
    spectra as columns, every view lane-dense when ``rows`` is a multiple
    of 128 (the fused kernels' block)."""
    n1, n2 = x.shape[0] // rows, x.shape[1]
    xt = x.T                                    # [k2, k1 * rows + r]
    if n1 == 1:
        return xt
    return xt.reshape(n2, n1, rows).reshape(n2 * n1, rows)


def dft_planes(re: jnp.ndarray, im: jnp.ndarray,
               tables: tuple[jnp.ndarray, ...]):
    """DFT along the last axis of ``(rows, n)`` planes.  Returns (re, im).

    ``tables`` is ``dft_tables(n, inverse)`` (as arrays or loaded values).
    Pure jnp: this exact body runs inside the Pallas kernels and is also
    unit-tested standalone against the complex oracle.
    """
    rows = re.shape[0]
    xr, xi = dft_digits(re, im, tables)
    return to_natural(xr, rows), to_natural(xi, rows)


def table_specs(tables: tuple[np.ndarray, ...]) -> list:
    """Whole-array BlockSpecs for the tables: the same block at every grid
    step, so Pallas copies each table to VMEM once."""
    return [pl.BlockSpec(t.shape, lambda *_: (0, 0)) for t in tables]


def _fft_kernel(re_ref, im_ref, *refs):
    *table_refs, ore_ref, oim_ref = refs
    re, im = dft_planes(re_ref[...], im_ref[...],
                        tuple(t[...] for t in table_refs))
    ore_ref[...] = re
    oim_ref[...] = im


def fft_rows_pallas(
    re: jnp.ndarray,
    im: jnp.ndarray,
    *,
    block_rows: int = 8,
    inverse: bool = False,
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """pallas_call wrapper: (rows, n) planes -> transformed planes.

    rows must be a multiple of block_rows (ops.py pads); n a power of two.
    """
    rows, n = re.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of block_rows={block_rows}")
    tables = dft_tables(n, inverse)
    spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((rows, n), re.dtype),
        jax.ShapeDtypeStruct((rows, n), im.dtype),
    ]
    fn = pl.pallas_call(
        _fft_kernel,
        grid=(rows // block_rows,),
        in_specs=[spec, spec, *table_specs(tables)],
        out_specs=[spec, spec],
        out_shape=out_shape,
        compiler_params=compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )
    return fn(re, im, *tables)


def compiler_params(vmem_limit_bytes: int | None):
    """Mosaic params raising the scoped-VMEM limit when a block needs it."""
    if vmem_limit_bytes is None:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))
