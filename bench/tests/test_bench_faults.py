"""The comparison that decides ``correct`` fails what it must.

Each test skips the harness's look for a chip and drives the rest of a
run (``run_cell``) on the CPU at a small N, with the timed path broken
underneath: the check has to read ``correct`` false.  The exchange
fault needs four devices, so it runs in a child process with four
virtual CPU devices.  The control (``bench/tools/control.py``: the
reference in the program's place, its products in three bf16 passes)
reads the same here as on the chip; its chip readings are in
``PERF.md``.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
N = 256


def _small(cell_name: str, n: int = N):
    cell = run.load_cell(cell_name)
    return dataclasses.replace(cell, mix={**cell.mix, "n": n})


def _state_unchanged(call):
    return lambda x: x


def _half_left_out(call):
    # half the rows dropped, the rest doubled: the mean over what is left
    return lambda x: 2 * call(x.at[x.shape[0] // 2:].set(0))


def _answer_altered(call):
    return lambda x: call(x).at[1, 2].add(x.shape[0])


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.fixture(scope="module")
def cell():
    return _small("pfft2-c64-1chip.n8192")


def _run(cell, wrap=None):
    import jax
    res, lines = run.run_cell(cell, seed=2**35 + 11, seconds=0.2,
                              trace=False, devices=jax.devices()[:1],
                              wrap=wrap, log=lambda s: None)
    return res


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True
    for c in res["checks"].values():
        assert 0 < c["value"] < c["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_not_correct(cell, fault):
    res = _run(cell, FAULTS[fault])
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


EXCHANGE_CHILD = textwrap.dedent("""
    import dataclasses, json, sys
    import jax, jax.numpy as jnp
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import run
    base = run.load_cell("pfft2-c64-1chip.n8192")
    cfg = run.BENCH / "configs" / "pfft2-c64-4chip.json"
    cell = dataclasses.replace(
        base, chips=4, config=json.loads(cfg.read_text()),
        config_mod=run.load_module(cfg.with_suffix(".py")),
        mix={{**base.mix, "n": {n}}})
    devices = jax.devices()[:4]

    def once():
        res, _ = run.run_cell(cell, seed=3, seconds=0.2, trace=False,
                              devices=devices, log=lambda s: None)
        return res["correct"]

    sound = once()

    def no_exchange(x, axis_name, split_axis, concat_axis, tiled=False,
                    **kw):
        # what all_to_all returns, from the local block alone
        parts = jnp.split(x, 4, axis=split_axis)
        return jnp.concatenate(parts, axis=concat_axis)

    jax.lax.all_to_all = no_exchange
    jax.clear_caches()
    print(json.dumps({{"sound": sound, "no_exchange": once()}}))
""")


def test_exchange_left_out_reads_not_correct():
    code = EXCHANGE_CHILD.format(root=str(ROOT), src=str(ROOT / "src"), n=N)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}


def test_control_reads_not_correct(cell):
    from bench.tools.control import in_programs_place
    sound = _run(cell)["checks"]
    control = _run(cell, in_programs_place)
    assert control["correct"] is False
    for name, c in control["checks"].items():
        assert c["value"] > 3 * sound[name]["value"]
