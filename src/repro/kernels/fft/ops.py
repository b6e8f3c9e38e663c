"""jit'd public op for the Pallas row-FFT kernel.

Handles: complex <-> plane conversion, row padding to the block multiple,
VMEM-aware block-rows selection, and interpret mode on the CPU backend
(where the test suite runs the kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.fft.kernel import (LANES, dft_tables, fft_rows_pallas,
                                      split_length)

__all__ = ["KERNEL_KINDS", "KernelUnsupported", "fft_rows_op",
           "pick_block_rows", "resolve_call_params", "rows_to_padded_planes",
           "tpu_unsupported", "vmem_bytes", "vmem_limit_for"]

KERNEL_KINDS = ("fft", "rfft", "fused", "rfused")


class KernelUnsupported(ValueError):
    """A Pallas row-FFT kernel cannot compile for the TPU at this length."""


# VMEM of a TPU v5e TensorCore is 128 MiB; Mosaic's default scoped limit
# for one kernel is 16 MiB.  Blocks are sized to the default with
# headroom; a kernel whose smallest legal block needs more raises its
# limit, up to _VMEM_CAP, and past that is refused (``tpu_unsupported``).
_VMEM_BUDGET = 14 * 1024 * 1024
_VMEM_CAP = 100 * 1024 * 1024
_SUBLANES = 8
_MAX_BLOCK_ROWS = 256

# f32 row-length planes live per block row, fitted to the scoped VMEM the
# v5e compiler allocates (n = 512..16384).  Plain kernels: the pipeline's
# double-buffered in/out planes, plus intermediates; the four-step's digit
# transpose pads each intermediate to at least a 128 x 128 tile per row.
# Fused kernels (k1-major digits, 128-row blocks): all planes lane-dense.
_PIPE = {"fft": 8, "rfft": 12}
_TEMP = {"fft": 10, "rfft": 14}
_FUSED_PLANES = {"fused": 22, "rfused": 30}


def _tables(kind: str, n: int):
    from repro.kernels.fft.real import real_tables
    return real_tables(n) if kind in ("rfft", "rfused") else dft_tables(n)


def vmem_bytes(kind: str, n: int, block_rows: int) -> int:
    """Estimated scoped VMEM of one ``kind`` kernel program at length n."""
    if kind in _FUSED_PLANES:
        per_row = _FUSED_PLANES[kind] * n
    else:
        n1, _ = split_length(n)
        tmp = max(n, LANES * LANES) if n1 > 1 else 2 * n
        per_row = _PIPE[kind] * n + _TEMP[kind] * tmp
    tables = sum(t.size for t in _tables(kind, n))
    return 4 * (block_rows * per_row + 2 * tables)


def pick_block_rows(n: int, kind: str = "fft") -> int:
    """Rows per grid step for a ``kind`` kernel at length n.

    Fused kernels write an ``(n, block_rows)`` output block, so there
    block_rows is the lane axis and is one lane tile, 128.  Plain kernels
    take the largest power of two (8..256) whose estimated VMEM fits the
    default scoped limit: every block is whole (8, 128) f32 tiles.
    """
    if kind in _FUSED_PLANES:
        return LANES
    b = _MAX_BLOCK_ROWS
    while b > _SUBLANES and vmem_bytes(kind, n, b) > _VMEM_BUDGET:
        b //= 2
    return b


def vmem_limit_for(kind: str, n: int, block_rows: int) -> int | None:
    """Scoped-VMEM limit for a block, or None when the default suffices."""
    need = vmem_bytes(kind, n, block_rows)
    if need <= _VMEM_BUDGET:
        return None
    return int(min(need * 6 // 5, _VMEM_CAP))


def tpu_unsupported(kind: str, n: int) -> str | None:
    """Why a ``kind`` kernel cannot compile for TPU v5e at length n (its
    smallest legal block outgrows the VMEM a kernel may take), or None."""
    need = vmem_bytes(kind, n, _SUBLANES if kind in _PIPE else LANES)
    if need <= _VMEM_CAP:
        return None
    return (f"{kind} kernel at n={n}: its smallest legal block needs "
            f"~{need >> 20} MiB of VMEM, over the {_VMEM_CAP >> 20} MiB a "
            f"kernel may take of a v5e core's 128 MiB")


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def resolve_call_params(kind: str, n: int, rows: int,
                        block_rows: int | None, interpret: bool | None
                        ) -> tuple[int, bool, int | None]:
    """Shared prologue for the row-FFT op wrappers: validate the length,
    refuse a length the chip cannot run, and fill in the block_rows /
    interpret defaults.  An automatic block never exceeds ``rows`` rounded
    up to a sublane tile, so small inputs are not padded to a full block
    (a block equal to the whole padded array is legal at any width).
    Returns ``(block_rows, interpret, vmem_limit_bytes)``."""
    if n & (n - 1):
        raise ValueError(f"pallas fft kernel requires power-of-two length, got {n}")
    if interpret is None:
        interpret = _on_cpu()
    if not interpret:
        reason = tpu_unsupported(kind, n)
        if reason is not None:
            raise KernelUnsupported(reason)
    if block_rows is None:
        block_rows = min(pick_block_rows(n, kind),
                         max(-(-rows // _SUBLANES), 1) * _SUBLANES)
    limit = None if interpret else vmem_limit_for(kind, n, block_rows)
    return block_rows, interpret, limit


def rows_to_padded_planes(x2, block_rows: int
                          ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """(rows, n) complex, or an ``(re, im)`` pair of f32 planes -> f32
    (re, im) planes row-padded to the block multiple, plus the original
    row count for cropping the result."""
    planes = isinstance(x2, tuple)
    total = (x2[0] if planes else x2).shape[0]
    extra = -total % block_rows
    pad = ((0, extra), (0, 0))
    with obs.scope(obs.SPLIT):
        if planes:
            re, im = x2
            if extra:
                re, im = jnp.pad(re, pad), jnp.pad(im, pad)
            return re, im, total
        if extra:
            x2 = jnp.pad(x2, pad)
        return (jnp.real(x2).astype(jnp.float32),
                jnp.imag(x2).astype(jnp.float32), total)


@functools.partial(jax.jit,
                   static_argnames=("inverse", "block_rows", "interpret"))
def fft_rows_op(
    x: jnp.ndarray,
    *,
    inverse: bool = False,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Complex row FFT via the Pallas kernel. x: (..., rows, n) complex."""
    n = x.shape[-1]
    lead = x.shape[:-2]
    rows = x.shape[-2]
    block_rows, interpret, limit = resolve_call_params(
        "fft", n, x.size // n, block_rows, interpret)
    x2 = x.reshape((-1, n)) if lead else x.reshape((rows, n))
    re, im, total = rows_to_padded_planes(x2, block_rows)
    ore, oim = fft_rows_pallas(re, im, block_rows=block_rows, inverse=inverse,
                               interpret=interpret, vmem_limit_bytes=limit)
    with obs.scope(obs.JOIN):
        out = jax.lax.complex(ore[:total], oim[:total]).astype(
            jnp.result_type(x, jnp.complex64))
        return out.reshape(lead + (rows, n)) if lead else out.reshape((rows, n))
