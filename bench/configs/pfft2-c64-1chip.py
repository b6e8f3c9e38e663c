"""pfft2-c64-1chip: the paper's PFFT-FPM on one chip.

``plan_pfft(N, method="fpm", p=4, fpms=<committed file>,
tune="estimate")``: the FPMs split the N rows into 4 groups and the
cost model picks the row-FFT variant, from committed files alone.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIG = json.loads((HERE / "pfft2-c64-1chip.json").read_text())


def plan(n: int, devices):
    from repro.core import plan_pfft
    from repro.core.fpm import load_fpms
    p = CONFIG["plan"]
    return plan_pfft(n, method=p["method"], p=p["p"],
                     fpms=load_fpms(str(HERE / p["fpms"])), tune=p["tune"])


def make_input(n: int, seed: int, plan, devices):
    from jax.sharding import SingleDeviceSharding
    from bench.inputs import complex_normal
    return complex_normal(n, seed, SingleDeviceSharding(devices[0]))
