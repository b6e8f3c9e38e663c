"""Whether a transform came out right: a float64 reference and the
numbers compared with it.

The reference is the 2-D DFT in complex128 by SciPy's pocketfft on the
host, independent of the program (which runs MXU DFT products or XLA's
TPU FFT in complex64).  It runs after the window has closed, on the
input the window transformed and on the output of its last call, and
covers every element:

* ``max_rel_err`` = max |out - ref| / rms(ref): one wrong bin, row or
  element shows here.
* ``rms_rel_err`` = ||out - ref|| / ||ref||: a loss of precision spread
  over the whole transform (as a lower-precision product would cause)
  shows here.

Each has a limit in ``bench/workloads/<cell>.json``, set from readings
of sound runs and of the control; ``PERF.md`` gives them.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = ["CHECKS", "compare", "reference_fft2"]

CHECKS = ("max_rel_err", "rms_rel_err")
_BLOCK_ROWS = 512


def _threads() -> int:
    return os.cpu_count() or 1


def reference_fft2(x: np.ndarray) -> np.ndarray:
    """float64 2-D DFT of ``x`` (the caller drops its copy of ``x``)."""
    import scipy.fft
    ref = np.empty(x.shape, np.complex128)

    def widen(lo):
        ref[lo:lo + _BLOCK_ROWS] = x[lo:lo + _BLOCK_ROWS]

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(widen, range(0, x.shape[0], _BLOCK_ROWS)))
    return scipy.fft.fftn(ref, axes=(0, 1), overwrite_x=True,
                          workers=_threads())


def compare(out: np.ndarray, ref: np.ndarray) -> dict[str, float]:
    """The numbers in ``CHECKS``, over row blocks on every core; NaN or
    inf anywhere in ``out`` reads as inf."""
    if out.shape != ref.shape:
        return {name: float("inf") for name in CHECKS}

    def block(lo):
        r = ref[lo:lo + _BLOCK_ROWS]
        d = out[lo:lo + _BLOCK_ROWS] - r          # complex128
        e2 = d.real ** 2 + d.imag ** 2
        return e2.max(), e2.sum(), np.sum(r.real ** 2 + r.imag ** 2)

    with ThreadPoolExecutor(_threads()) as pool:
        parts = np.array(list(pool.map(block, range(0, ref.shape[0],
                                                     _BLOCK_ROWS))))
    max_sq = float(np.max(parts[:, 0]))  # NaN propagates, unlike max()
    err_sq, ref_sq = float(parts[:, 1].sum()), float(parts[:, 2].sum())
    if not (np.isfinite(max_sq) and np.isfinite(err_sq)):
        return {name: float("inf") for name in CHECKS}
    rms_ref = np.sqrt(ref_sq / ref.size)
    return {"max_rel_err": float(np.sqrt(max_sq) / rms_ref),
            "rms_rel_err": float(np.sqrt(err_sq / ref_sq))}
