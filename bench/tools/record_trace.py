#!/usr/bin/env python3
"""Record a small profiler trace on the chip, for the tests of
``bench/trace.py`` and the row-FFT matcher.

    python3 bench/tools/record_trace.py --n 512 --out bench/tests/data/tpu_small.xplane.pb

Inside one ``bench.window`` host span it runs, three times each, a
planned N x N transform on the Pallas row-FFT kernel
(``PlanConfig(radix=4)``) and XLA's own 2-D FFT, and copies the
``.xplane.pb`` to ``--out``.  ``--dump`` also writes a text listing of
the trace's planes, lines and event names.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from collections import Counter
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))


def dump(path, out) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    with open(out, "w") as fh:
        for plane in data.planes:
            fh.write(f"PLANE {plane.name!r}\n")
            for line in plane.lines:
                evs = list(line.events)
                names = Counter(e.name for e in evs)
                fh.write(f"  LINE {line.name!r} events={len(evs)}\n")
                for name, k in names.most_common(40):
                    fh.write(f"    {k:5d} {name}\n")
                for e in evs[:3]:
                    fh.write(f"    sample {e.name} start={e.start_ns} "
                             f"dur={e.duration_ns} stats={list(e.stats)}\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--out", required=True)
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from repro.core import PlanConfig, plan_pfft
    from bench.inputs import complex_normal
    from bench.trace import WINDOW_SPAN, find_xplane

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    n = args.n
    plan = plan_pfft(n, method="lb", p=1, config=PlanConfig(radix=4))
    xla = jax.jit(jnp.fft.fft2)
    x = complex_normal(n, 0, jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    plan.execute(x).block_until_ready()
    xla(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation(WINDOW_SPAN):
            for fn in (plan.execute, xla):
                for _ in range(3):
                    fn(x).block_until_ready()
        jax.profiler.stop_trace()
        src = find_xplane(tmp)
        shutil.copyfile(src, args.out)
        if args.dump:
            dump(src, args.dump)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {args.out} ({Path(args.out).stat().st_size} B), "
          f"kernel config [{plan.config.describe()}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
