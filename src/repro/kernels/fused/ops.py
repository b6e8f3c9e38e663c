"""jit'd public op for the fused row-FFT -> transpose kernel.

Same contract shape as ``repro.kernels.fft.ops.fft_rows_op`` (complex in,
complex out, row padding to the block multiple, interpret mode on the CPU
backend) except the result comes back transposed: input
``(rows, n)`` -> output ``(n, rows)`` holding ``FFT_rows(x).T``.

The op also takes an ``(re, im)`` pair of f32 planes and then returns the
output planes, so fused phases can pass planes from one to the next and
convert complex <-> planes once at each end (``fft.fft2d.fft2d_fused``).
The kernel stays inside this jit in both forms: the compiled instruction
is named after it, and the benchmark's row-FFT reader finds it by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.fft.ops import resolve_call_params, rows_to_padded_planes
from repro.kernels.fused.kernel import fft_rows_transpose_pallas

__all__ = ["fft_rows_transpose_op"]


@functools.partial(jax.jit,
                   static_argnames=("inverse", "block_rows", "interpret"))
def fft_rows_transpose_op(
    x: jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray],
    *,
    inverse: bool = False,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``FFT_rows(x).T`` via one Pallas dispatch.

    x: (rows, n) complex -> (n, rows) complex; or an ``(re, im)`` pair of
    (rows, n) f32 planes -> the (n, rows) ``(re, im)`` output planes.
    """
    planes = isinstance(x, tuple)
    shape = x[0].shape if planes else x.shape
    if planes and x[1].shape != shape:
        raise ValueError(f"fused op takes planes of one shape, got {shape} "
                         f"and {x[1].shape}")
    if len(shape) != 2:
        raise ValueError(f"fused op takes a 2-D matrix, got shape {shape}")
    rows, n = shape
    block_rows, interpret, limit = resolve_call_params(
        "fused", n, rows, block_rows, interpret)
    re, im, _ = rows_to_padded_planes(x, block_rows)
    ore, oim = fft_rows_transpose_pallas(re, im, block_rows=block_rows,
                                         inverse=inverse, interpret=interpret,
                                         vmem_limit_bytes=limit)
    if planes:
        return ore[:, :rows], oim[:, :rows]
    with obs.scope(obs.JOIN):
        out = jax.lax.complex(ore[:, :rows], oim[:, :rows])
        return out.astype(jnp.result_type(x, jnp.complex64))
