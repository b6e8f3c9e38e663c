#!/usr/bin/env python3
"""Read a cell's check numbers on many seeds in one process: the
program's, and its control's.  This is how the limits in
``bench/workloads/<cell>.json`` were set (``PERF.md`` gives the
readings); the benchmark's own runs never run it.

    python3 bench/tools/control.py --workload <cell> --seconds 1 \
        --program-seeds 1,2,3 --control-seeds 4,5,6

Each seed is a whole run of the cell (``bench.run.run_cell``): plan,
input from the seed, a short closed-loop window at the cell's own load,
and the check of the window's last output.

The control is the reference put in the program's place and computed
one precision step below what the configuration states: the program
runs its row-FFT products at ``precision=HIGHEST`` (six bf16 passes on
the MXU), so the control computes the same 2-D DFT with ``high``
products, three bf16 passes (``hi*hi + hi*lo + lo*hi``, written out so
that they read the same on every backend; Pallas on the TPU refuses
``Precision.HIGH`` itself).  Each row DFT is a four-step product (n1 x
n1 column DFT, twiddle, n2 x n2 row DFT, n2 = 128) as in the program's
kernel, and the transposes between the two row phases are left to XLA,
which exchanges over the mesh where the input is sharded.  One JSON
line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import run  # noqa: E402


def _bf16x3(spec: str, a, b):
    """``einsum(spec, a, b)`` of f32 operands in three bf16 passes.

    ``reduce_precision`` rounds to bf16 explicitly: a round trip through
    the bf16 type alone may be elided by XLA on the TPU, which allows
    excess precision, leaving the low parts zero."""
    import jax
    import jax.numpy as jnp

    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(u, v):
        return jnp.einsum(spec, u, v, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _cdot(spec: str, a, b):
    import jax
    re = _bf16x3(spec, a.real, b.real) - _bf16x3(spec, a.imag, b.imag)
    im = _bf16x3(spec, a.real, b.imag) + _bf16x3(spec, a.imag, b.real)
    return jax.lax.complex(re, im)


def _cis(num, den):
    return np.exp(-2j * np.pi * (num % den) / den).astype(np.complex64)


def dft_rows(x):
    """Forward DFT of each row of a (rows, n) complex64 array, n a power
    of two, by four-step products in three bf16 passes."""
    rows, n = x.shape
    n2 = min(n, 128)
    n1 = n // n2
    i1, i2 = np.arange(n1), np.arange(n2)
    y = x.reshape(rows, n1, n2)
    if n1 > 1:
        y = _cdot("ka,rab->rkb", _cis(np.outer(i1, i1), n1), y)
        y = y * _cis(np.outer(i1, i2), n)
    z = _cdot("rkb,bc->rkc", y, _cis(np.outer(i2, i2), n2))
    return z.transpose(0, 2, 1).reshape(rows, n)


def _rows(y, chunk: int = 1024):
    """``dft_rows`` over blocks of ``chunk`` rows, to bound temporaries."""
    import jax
    rows, n = y.shape
    if rows <= chunk:
        return dft_rows(y)
    return jax.lax.map(dft_rows, y.reshape(rows // chunk, chunk, n)
                       ).reshape(rows, n)


def control_fft2(sharding):
    """The control's 2-D DFT, jitted, for an input laid out by
    ``sharding``: one device, or rows split over a mesh axis, with the
    transposes between the phases as tiled all_to_all exchanges."""
    import jax
    from jax.sharding import NamedSharding
    if not isinstance(sharding, NamedSharding):
        return jax.jit(lambda x: _rows(_rows(x).T).T)
    axis = sharding.spec[0]

    def local(xb):
        def a2a(y):
            return jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                                      tiled=True)
        y = a2a(_rows(xb))          # (N, N/p): all rows, my columns
        return a2a(_rows(y.T)).T    # my rows of the transform

    return jax.jit(jax.shard_map(local, mesh=sharding.mesh,
                                 in_specs=sharding.spec,
                                 out_specs=sharding.spec))


def in_programs_place(call):
    """``run_cell``'s ``wrap``: the control in place of ``plan.execute``."""
    fns = {}

    def control(x):
        if x.sharding not in fns:
            fns[x.sharding] = control_fft2(x.sharding)
        return fns[x.sharding](x)

    return control


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--program-seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: needs {cell.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform!r}", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    run.use_compile_cache()
    for mode, seeds, wrap in (
            ("program", args.program_seeds, None),
            ("control_high", args.control_seeds, in_programs_place)):
        for seed in seeds:
            res, _ = run.run_cell(cell, seed=seed, seconds=args.seconds,
                                  trace=False, devices=devices, wrap=wrap,
                                  log=lambda s: None)
            print(json.dumps({
                "mode": mode, "seed": seed, "correct": res["correct"],
                **{k: c["value"] for k, c in res["checks"].items()},
                "transform_ms": res["metrics"]["transform_ms"]["value"],
                "setup_s": res["metrics"]["setup_s"]["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
