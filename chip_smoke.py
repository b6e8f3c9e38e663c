#!/usr/bin/env python3
"""Smoke test: the planned 2-D FFT runs on a TPU chip and gets it right.

    python chip_smoke.py             # one chip: every single-chip phase
    python chip_smoke.py --chips 4   # four chips: the distributed phases only

Drives the library through the entry points a user calls (``build_fpm``,
``plan_pfft``, ``plan_pfft3``, ``FFTService``) at the sizes users run,
checks every result against float64 numpy, and prints one line per phase
(max error against its tolerance).  The last line
of standard output is one JSON object naming the device.  The script runs
in one process, exits non-zero on the first failure and refuses to run
anywhere but a TPU.  JAX's compile cache is placed by
``repro.launch.compile_cache``.

Tolerances are on ``max |out - ref| / rms(ref)``: complex64/float32 carry
a 2^-24 unit roundoff, and a length-N transform accumulates O(log N) to
O(sqrt N) roundoffs per output, ~1e-6 to 1e-5 of the output rms at the
sizes here; 1e-4 keeps a 10x margin, while a wrong bin, twiddle or
permutation errs by O(1).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-4          # see the module docstring
PARSEVAL_TOL = 1e-5  # energy is a sum of N^2 squares: roundoff averages out
SEED = 0


def _rel_err(out, ref) -> float:
    out = np.asarray(out)
    return float(np.max(np.abs(out - ref)) / np.sqrt(np.mean(np.abs(ref) ** 2)))


def _complex_signal(rng, shape) -> np.ndarray:
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _report(phase: str, err: float, tol: float, **fields) -> None:
    if not err <= tol:
        raise AssertionError(f"{phase}: error {err:.3e} exceeds {tol:.1e}")
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"phase={phase} ok max_err={err:.3e} tol={tol:.0e} {extra}",
          flush=True)


def run_compiled(fn, x):
    """AOT-compile ``fn`` for ``x`` and run it once: returns (out,
    holds_kernel) — ``holds_kernel`` is whether the compiled program
    contains a Pallas kernel (``tpu_custom_call``)."""
    import jax
    compiled = jax.jit(fn).lower(x).compile()
    out = jax.block_until_ready(compiled(x))
    return out, "tpu_custom_call" in compiled.as_text()


def build_fpms(n: int, p: int = 2):
    """Speed functions from timed XLA row-FFT batches on the chip, on a
    small grid around ``n`` (as ``examples/quickstart.py`` does)."""
    import jax
    import jax.numpy as jnp
    from repro.core import FPMSet, build_fpm

    fft = jax.jit(lambda a: jnp.fft.fft(a, axis=-1))

    def timer(x: int, y: int) -> float:
        m = jnp.ones((x, y), jnp.complex64)
        fft(m).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            fft(m).block_until_ready()
        return (time.perf_counter() - t0) / 3

    xs = (n // 16, n // 4, n)
    ys = (n // 2, n, 2 * n)
    return FPMSet([build_fpm(xs, ys, timer, name=f"P{i}") for i in range(p)])


def phase_planned_complex(n: int, fpms) -> None:
    """``plan_pfft(method="fpm", tune="estimate")`` plus the two explicit
    kernel configs, against one float64 reference."""
    from repro.core import PlanConfig, plan_pfft
    x = _complex_signal(np.random.default_rng(SEED), (n, n))
    ref = np.fft.fft2(x.astype(np.complex128))
    cases = [("planned", dict(fpms=fpms, method="fpm", tune="estimate")),
             ("radix4", dict(method="lb", p=1, config=PlanConfig(radix=4))),
             ("fused", dict(method="lb", p=1,
                            config=PlanConfig(radix=4, fused=True)))]
    for name, kw in cases:
        plan = plan_pfft(n, **kw)
        out, kernel = run_compiled(plan.execute, x)
        if not kernel:
            raise AssertionError(f"complex-{name} n={n}: no tpu_custom_call "
                                 f"in the program of [{plan.config.describe()}]")
        _report(f"complex2d-{name}", _rel_err(out, ref), TOL, n=n,
                config=f"[{plan.config.describe()}]",
                source=plan.tuning["source"], tpu_custom_call=kernel)
        del out


def phase_czt(n: int, fpms) -> None:
    """A paper-sweep length that is not a power of two, planned exactly."""
    from repro.core import plan_pfft
    x = _complex_signal(np.random.default_rng(SEED + 1), (n, n))
    ref = np.fft.fft2(x.astype(np.complex128))
    plan = plan_pfft(n, fpms=fpms, method="fpm-czt", tune="estimate")
    out, kernel = run_compiled(plan.execute, x)
    _report("czt", _rel_err(out, ref), TOL, n=n,
            config=f"[{plan.config.describe()}]", tpu_custom_call=kernel)


def _direct_bins(x: np.ndarray, bins) -> np.ndarray:
    """X[k1, k2] = sum x[a, b] exp(-2 pi i (a k1 + b k2) / n) in float64,
    streamed over row chunks."""
    n = x.shape[0]
    idx = np.arange(n)
    out = np.zeros(len(bins), np.complex128)
    for lo in range(0, n, 1024):
        chunk = x[lo:lo + 1024].astype(np.complex128)
        rows = idx[lo:lo + 1024]
        for i, (k1, k2) in enumerate(bins):
            e2 = np.exp(-2j * np.pi * (idx * k2 % n) / n)
            e1 = np.exp(-2j * np.pi * (rows * k1 % n) / n)
            out[i] += e1 @ (chunk @ e2)
    return out


def phase_largest(n: int) -> None:
    """The largest power of two whose signal, result and planes fit one
    chip, checked by Parseval's identity and a few direct bins."""
    import jax
    from repro.core import plan_pfft
    x = _complex_signal(np.random.default_rng(SEED + 2), (n, n))
    plan = plan_pfft(n, method="lb", p=1, tune="estimate")
    out, kernel = run_compiled(plan.execute, x)
    out = np.asarray(out)
    e_in = e_out = 0.0
    for lo in range(0, n, 1024):
        e_in += float(np.sum(np.abs(x[lo:lo + 1024].astype(np.complex128)) ** 2))
        e_out += float(np.sum(np.abs(out[lo:lo + 1024].astype(np.complex128)) ** 2))
    parseval = abs(e_out / (n * n * e_in) - 1.0)
    bins = [(0, 0), (1, 0), (0, n - 1), (n // 2, n // 3), (12345 % n, 777 % n)]
    direct = _direct_bins(x, bins)
    got = np.array([out[k1, k2] for k1, k2 in bins])
    bin_err = float(np.max(np.abs(got - direct)) / np.sqrt(e_out / (n * n)))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    _report("largest-parseval", parseval, PARSEVAL_TOL, n=n)
    _report("largest-bins", bin_err, TOL, n=n,
            config=f"[{plan.config.describe()}]", tpu_custom_call=kernel,
            peak_bytes_in_use=peak)


def phase_real(n: int) -> None:
    from repro.core import plan_pfft
    x = np.random.default_rng(SEED + 3).standard_normal((n, n)).astype(np.float32)
    ref = np.fft.rfft2(x.astype(np.float64))
    plan = plan_pfft(n, method="rfft-lb", p=1, dtype="float32",
                     tune="estimate")
    out, kernel = run_compiled(plan.execute, x)
    _report("real2d", _rel_err(out, ref), TOL, n=n,
            config=f"[{plan.config.describe()}]", tpu_custom_call=kernel)


def phase_served(sizes=(1024, 2048, 4096)) -> None:
    """Mixed complex/real requests through one ``FFTService``."""
    from repro.launch.serve_fft import FFTService
    rng = np.random.default_rng(SEED + 4)
    jobs = [(sizes[i % len(sizes)], "lb" if i % 2 == 0 else "rfft-lb")
            for i in range(8)]
    reqs = [(n, meth, _complex_signal(rng, (n, n)) if meth == "lb"
             else rng.standard_normal((n, n)).astype(np.float32))
            for n, meth in jobs]
    svc = FFTService(tune="estimate")

    async def serve():
        async with svc:
            return await asyncio.gather(
                *(svc.submit(m, method=meth) for _, meth, m in reqs))

    t0 = time.perf_counter()
    outs = asyncio.run(serve())
    wall = time.perf_counter() - t0
    err = 0.0
    for (n, meth, m), out in zip(reqs, outs):
        ref = (np.fft.fft2(m.astype(np.complex128)) if meth == "lb"
               else np.fft.rfft2(m.astype(np.float64)))
        err = max(err, _rel_err(out, ref))
    s = svc.stats()
    if s["failed"] or s["served"] != len(reqs):
        raise AssertionError(f"served: {s['served']}/{len(reqs)} served, "
                             f"{s['failed']} failed")
    _report("served", err, TOL, requests=len(reqs), served=s["served"],
            dispatches=s["dispatches"], wall_s=f"{wall:.2f}")


def _assert_spans(out, n_dev: int, what: str) -> None:
    import jax
    devs = out.sharding.device_set
    if len(devs) != n_dev:
        raise AssertionError(f"{what}: output on {len(devs)} devices, "
                             f"expected {n_dev}")
    for d in jax.devices()[:n_dev]:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        if used <= 0:
            raise AssertionError(f"{what}: device {d.id} holds no bytes")
    shard = out.addressable_shards[0].data.nbytes
    if shard * n_dev != out.nbytes:
        raise AssertionError(f"{what}: shards of {shard} B do not split "
                             f"{out.nbytes} B evenly")


def phase_distributed(n2d: int = 16384, n3d: int = 512) -> None:
    """The mesh paths, on four chips: the 2-D slab pipeline (one
    all_to_all per phase) and the 3-D pencil (two rounds)."""
    import jax
    from repro.core import plan_pfft
    from repro.core.api import plan_pfft3
    from repro.launch.mesh import make_fft_mesh, make_pfft3_mesh
    n_dev = 4
    x = _complex_signal(np.random.default_rng(SEED + 5), (n2d, n2d))
    plan = plan_pfft(n2d, method="lb", mesh=make_fft_mesh(n_dev),
                     tune="estimate")
    t0 = time.perf_counter()
    out = jax.block_until_ready(plan.execute(x))
    first_s = time.perf_counter() - t0
    _assert_spans(out, n_dev, "dist2d")
    err = _rel_err(out, np.fft.fft2(x.astype(np.complex128)))
    _report("dist2d", err, TOL, n=n2d, devices=n_dev,
            first_call_s=f"{first_s:.2f}",
            config=f"[{plan.config.describe()}]")
    del out, x

    c = _complex_signal(np.random.default_rng(SEED + 6), (n3d,) * 3)
    plan3 = plan_pfft3(n3d, mesh=make_pfft3_mesh(2, 2))
    t0 = time.perf_counter()
    out = jax.block_until_ready(plan3.execute(c))
    first_s = time.perf_counter() - t0
    _assert_spans(out, n_dev, "pencil3d")
    err = _rel_err(out, np.fft.fftn(c.astype(np.complex128)))
    _report("pencil3d", err, TOL, n=n3d, devices=n_dev,
            first_call_s=f"{first_s:.2f}",
            config=f"[{plan3.config.describe()}]")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip distributed phases")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import CompileCounter, use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    counter = CompileCounter()
    print(f"device={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache_dir}", flush=True)

    t_all = time.perf_counter()
    if args.chips == 4:
        phase_distributed()
    else:
        t0 = time.perf_counter()
        fpms = build_fpms(8192)
        print(f"setup build_fpm grid=3x3 p={fpms.p} "
              f"time_s={time.perf_counter() - t0:.2f}", flush=True)
        phase_planned_complex(8192, fpms)
        phase_czt(9984, fpms)
        phase_largest(16384)
        phase_real(8192)
        phase_served()
    print(f"total_s={time.perf_counter() - t_all:.1f} "
          f"persistent_cache hits={counter.hits} "
          f"cold_compiles={counter.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
