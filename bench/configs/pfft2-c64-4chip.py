"""pfft2-c64-4chip: the slab pipeline over the 4 chips of one host.

``plan_pfft(N, method="lb", mesh=make_fft_mesh(4), tune="estimate")``:
each chip transforms N/4 rows, an all_to_all exchange carries the
transpose between the two row phases, and the estimate picks the row
FFT and how many panels overlap the exchange with compute.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIG = json.loads((HERE / "pfft2-c64-4chip.json").read_text())


def plan(n: int, devices):
    from repro.core import plan_pfft
    from repro.launch.mesh import make_fft_mesh
    p = CONFIG["plan"]
    mesh = make_fft_mesh(len(devices), axis_name=p["axis"])
    return plan_pfft(n, method=p["method"], mesh=mesh, axis_name=p["axis"],
                     tune=p["tune"])


def make_input(n: int, seed: int, plan, devices):
    from jax.sharding import NamedSharding, PartitionSpec
    from bench.inputs import complex_normal
    sharding = NamedSharding(plan.mesh, PartitionSpec(plan.axis_name, None))
    return complex_normal(n, seed, sharding)
