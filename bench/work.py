"""The work a transform needs, and the peaks it is measured against.

Counted from the transform's shape alone, never from the program that
runs it, so every implementation of a row FFT (a Pallas kernel, XLA's
``fft``, a later kernel) is held to the same operations and bytes.

* Operations: ``5 * L * log2(L)`` real flops per complex row of length
  L (the benchFFT convention; the paper's ``fft_flops`` counts half,
  2.5).  A 2-D N x N transform runs N rows in each of its 2 phases.
* Bytes: each phase reads and writes the whole signal once, so a
  complex64 N x N transform moves ``2 * 2 * 8 * N**2 = 32 N**2`` bytes.
* On a mesh each device does its share, ``1 / devices`` of both.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

__all__ = ["UnknownDeviceKind", "peaks_for", "rowfft_work", "roofline_s"]

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class UnknownDeviceKind(KeyError):
    """The peaks table has no entry for this ``device_kind``."""


def peaks_for(kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind raises."""
    table = json.loads(Path(path).read_text())
    try:
        return table[kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no peaks for device kind {kind!r} in {Path(path).name}; "
            f"known: {sorted(table)}") from None


def rowfft_work(n: int, *, phases: int = 2, itemsize: int = 8,
                devices: int = 1) -> dict:
    """Flops and bytes of the row-FFT phases of one N x N transform, on
    the whole transform and on each of ``devices`` equal shares."""
    flops = phases * n * 5 * n * math.log2(n)
    nbytes = phases * 2 * n * n * itemsize
    return {"flops": flops, "bytes": nbytes,
            "device_flops": flops / devices, "device_bytes": nbytes / devices}


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
