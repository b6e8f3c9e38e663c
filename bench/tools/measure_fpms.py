#!/usr/bin/env python3
"""Measure the speed functions (FPMs) a configuration plans with, once,
on the chip, and write them where the configuration reads them.

    python3 bench/tools/measure_fpms.py --n 8192 --p 4 \
        --out bench/configs/pfft2-c64-1chip.fpm.npz

Each of the ``p`` abstract processors times XLA row-FFT batches on the
grid x in {N/16, N/4, N} rows by y in {N/2, N, 2N} lengths (three timed
calls after a warm one), with ``repro.core.build_fpm``; the processors
are alike, so only measurement noise tells them apart.  Refuses to run
anywhere but a TPU.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--p", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from repro.core import FPMSet, build_fpm
    from repro.core.fpm import save_fpms

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"measure_fpms: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    fft = jax.jit(lambda a: jnp.fft.fft(a, axis=-1))

    def timer(x: int, y: int) -> float:
        m = jnp.ones((x, y), jnp.complex64)
        fft(m).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            fft(m).block_until_ready()
        return (time.perf_counter() - t0) / 3

    n = args.n
    fpms = FPMSet([build_fpm((n // 16, n // 4, n), (n // 2, n, 2 * n), timer,
                             name=f"P{i}") for i in range(args.p)])
    save_fpms(args.out, fpms)
    for f in fpms:
        print(f"{f.name} speed_flops_per_s={f.speed.tolist()}")
    print(f"wrote {args.out} on {dev.device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
