"""The four-chip slab deployment (``bench/configs/pfft2-c64-4chip``) on
four virtual CPU devices: the transform its benchmark cell times, the
exchange counters the program reads from its own executable, and one
whole run of the cell."""

import json
import os

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
N = 512
P = 4
# what each device sends off the device: 2 transposes, (p-1)/p of its
# N^2/p complex64 elements each
BYTES = 2 * (P - 1) * 8 * N * N // (P * P)

# The config the planner chose for the cell on a 2x2 v5e at N = 32768
# (``plan config=[radix=4,batched,panels=8]``); at N = 512 the 8 panels
# hold 16 of a device's 128 rows each.
PLAN = """
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from repro import obs
from repro.core import plan_pfft
from repro.launch.mesh import make_fft_mesh
from repro.plan.config import PlanConfig
N = {n}
mesh = make_fft_mesh(4, axis_name="fft")


def planned(panels):
    return plan_pfft(N, method="lb", mesh=mesh, axis_name="fft",
                     config=PlanConfig(radix=4, batched=True,
                                       pipeline_panels=panels))
"""


def test_chip_config_matches_float64_numpy(dist_subprocess):
    proc = dist_subprocess(PLAN.format(n=N) + """
plan = planned(8)
assert plan.config.describe() == "radix=4,batched,panels=8"
rng = np.random.default_rng(2**33 + 7)
x = rng.standard_normal((N, N)) + 1j * rng.standard_normal((N, N))
xs = jax.device_put(jnp.asarray(x, jnp.complex64),
                    NamedSharding(mesh, PartitionSpec("fft", None)))
out = np.asarray(plan.execute(xs)).astype(np.complex128)
ref = np.fft.fft2(x)
rms = np.sqrt(np.mean(np.abs(ref) ** 2))
print(json.dumps({"max_rel": float(np.abs(out - ref).max() / rms),
                  "rms_rel": float(np.linalg.norm(out - ref)
                                   / np.linalg.norm(ref))}))
print("OK")
""")
    got = json.loads(proc.stdout.strip().splitlines()[-2])
    # complex64 rounds each operation to 2**-24 (~6e-8); two length-512
    # DFT phases of float32 products read ~4e-7 rms and ~2e-6 at the
    # worst bin here.  The limits leave 5x of room; bf16 products
    # (2**-9) or a lost twiddle or exchange miss them by far.
    assert got["max_rel"] < 1e-5
    assert got["rms_rel"] < 2e-6


@pytest.mark.parametrize("panels", [8, 1], ids=["pipelined", "monolithic"])
def test_exchange_counters_of_the_executable(dist_subprocess, panels):
    proc = dist_subprocess(PLAN.format(n=N) + f"""
obs.reset()
plan = planned({panels})
got = plan.counters()
assert obs.counters() == {{}}                # reading records nothing
assert obs.live_counters() == got
assert obs.counters() == got
obs.reset()
assert obs.counters() == {{}}
print(json.dumps(got))
print("OK")
""")
    got = json.loads(proc.stdout.strip().splitlines()[-2])
    assert got == {"pfft.exchange.collectives": 2 * panels,
                   "pfft.exchange.bytes": BYTES}


def test_cell_runs_correct_on_four_cpu_devices(dist_subprocess):
    proc = dist_subprocess(f"""
import dataclasses, json, sys
sys.path.insert(0, {ROOT!r})
import jax
from bench import run
cell = run.load_cell("pfft2-c64-4chip.n32768")
assert cell.chips == 4 and cell.mix["n"] == 32768
cell = dataclasses.replace(cell, mix={{**cell.mix, "n": 256}})
res, lines = run.run_cell(cell, seed=2**40 + 17, seconds=0.2, trace=False,
                          devices=jax.devices()[:4], log=lambda s: None)
print(json.dumps(res))
print("OK")
""")
    res = json.loads(proc.stdout.strip().splitlines()[-2])
    assert res["correct"] is True and res["attempted"] >= 1
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"setup_s", "transform_ms",
                                   "transform_p95_ms"}
    for c in res["checks"].values():
        assert 0 < c["value"] < c["limit"]
