"""Tuner cases shared by ``test_plan.py``'s golden and policy tests.

``golden()`` runs every family tuner in estimate mode and returns
``{case: {"winner": ..., "info": ...}}`` in JSON form (tuples become
lists).  The cases run under the v5e cost constants with
``kernel_exclusions`` answering as it does on a v5e (the kernels the
chip's compiler refuses are dropped), so they pin the programs the
planner picks on the chip.  ``plan_golden.json`` beside this file holds
the choices recorded before the tuners were folded into one loop.

``policy()`` runs each family in measure mode with a deterministic fake
timer in place of the measure harness and returns what the loop did:
the finalists it raced, the winner, the comm sample, and what a failing
measurement leaves behind.

The mesh cases need eight devices: run this where ``XLA_FLAGS`` forces
``--xla_force_host_platform_device_count=8`` before jax is imported.
To record the golden choices from a source tree::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/plan_cases.py tests/plan_golden.json
"""

from __future__ import annotations

import json
import os
import sys
import zlib
from unittest import mock

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "plan_golden.json")
FPM_1CHIP = os.path.join(HERE, "..", "bench", "configs",
                         "pfft2-c64-1chip.fpm.npz")


def _hetero_fpms(n, p=3, slow_factor=8.0):
    """One slow processor and p-1 fast ones whose speeds favour padding
    n to the next power of two (``test_schedule.hetero_fpms``)."""
    from repro.core import FPMSet, SpeedFunction
    xs = np.array(sorted({1, max(n // 4, 1), max(n // 2, 1), n}))
    npow2 = 1 << int(np.ceil(np.log2(n)))
    ys = np.array(sorted({n, npow2, 2 * npow2}))
    base = np.outer(np.maximum(xs, 1), np.log2(np.maximum(ys, 2))) + 5.0
    return FPMSet([SpeedFunction(xs, ys, base / (slow_factor if i == 0
                                                 else 1.0), name=f"P{i}")
                   for i in range(p)])


def _constant(*_a, **_k):
    return 1.0


def _patched(attrs, thunk):
    import repro.plan.tune as t
    with mock.patch.multiple(t, **attrs):
        return thunk()


def _jsonable(obj):
    if hasattr(obj, "to_dict"):
        obj = obj.to_dict()
    return json.loads(json.dumps(obj))


def _golden_cases(v5e):
    """{case: thunk -> (winner, info)}; every thunk runs in estimate mode."""
    import repro.plan.tune as t
    from repro.core.fpm import load_fpms
    from repro.launch.mesh import make_fft_mesh, make_pfft3_mesh

    est = {"mode": "estimate", "params": v5e}
    cell_d = np.array([1992, 2093, 2043, 2064])
    hetero_d = np.array([16, 16, 16])
    hetero_pads = np.array([48, 64, 64], dtype=np.int64)
    grouped_pads = np.array([48, 64, 48, 64])

    def pfft3(n, mesh, **kw):
        cfg, axes, info = t.tune_pfft3(n, mesh, **est, **kw)
        return (cfg, list(axes) if axes is not None else None), info

    return {
        # The one-chip cell: plan_pfft(8192, method="fpm", p=4) with the
        # committed FPMs partitions the rows as cell_d.
        "cell-1chip-n8192": lambda: t.tune_schedule(
            8192, d=cell_d, fpms=load_fpms(FPM_1CHIP), **est),
        # The four-chip cell: plan_pfft(32768, method="lb", mesh=4 chips).
        "cell-4chip-n32768": lambda: t.tune_dist_schedule(
            32768, make_fft_mesh(4), **est),
        "config-lb-n1024": lambda: t.tune_config(
            1024, d=np.array([512, 512]), **est),
        "config-panels-n4096": lambda: t.tune_config(
            4096, panels=(1, 2, 4), comm_bytes=1e6, **est),
        "schedule-czt-n96": lambda: t.tune_schedule(
            96, d=np.array([32, 32, 32]), pad="czt",
            pad_lengths=np.array([192, 256, 192]), **est),
        "schedule-fpm-pad-n1000": lambda: t.tune_schedule(
            1000, d=np.array([500, 300, 200]), pad="fpm",
            pad_lengths=np.array([1000, 1024, 1024]),
            fpms=_hetero_fpms(1000), **est),
        "schedule-hetero-n48": lambda: t.tune_schedule(
            48, d=hetero_d, pad_lengths=hetero_pads, fpms=_hetero_fpms(48),
            pad="fpm", **est),
        # Tie: both estimates equal, so the race's candidate order
        # decides (the heterogeneous schedule wins).
        "schedule-tie": lambda: _patched(
            {"estimate_cost": _constant, "estimate_schedule_cost": _constant},
            lambda: t.tune_schedule(48, d=hetero_d, pad_lengths=hetero_pads,
                                    fpms=_hetero_fpms(48), pad="fpm", **est)),
        "dist-config-n1024": lambda: t.tune_dist_config(
            1024, make_fft_mesh(4), **est),
        "dist-config-hier-n64": lambda: t.tune_dist_config(
            64, make_fft_mesh(hosts=2, local=2), **est),
        "dist-schedule-grouped-n48": lambda: t.tune_dist_schedule(
            48, make_fft_mesh(4), pad="fpm", pad_lengths=grouped_pads, **est),
        # Tie: the homogeneous schedule wins.
        "dist-schedule-tie": lambda: _patched(
            {"estimate_grouped_cost": _constant},
            lambda: t.tune_dist_schedule(48, make_fft_mesh(4), pad="fpm",
                                         pad_lengths=grouped_pads, **est)),
        "pfft3-single-n64": lambda: pfft3(64, None),
        "pfft3-rect-n64": lambda: pfft3(64, make_pfft3_mesh(2, 4)),
        "pfft3-hier-n64": lambda: pfft3(64, make_pfft3_mesh(4, 2, hosts=2)),
        "pfft1-large-n1048576": lambda: t.tune_pfft1_large(1 << 20, **est),
        "pfft1-large-n360": lambda: t.tune_pfft1_large(360, **est),
        "rfft-n8192": lambda: t.tune_rfft(8192, d=cell_d, **est),
        "rfft-fpm-n1000": lambda: t.tune_rfft(
            1000, d=np.array([500, 300, 200]), pad="fpm",
            pad_lengths=np.array([1000, 1024, 1024]),
            fpms=_hetero_fpms(1000), **est),
        "rfft-dist-n4096": lambda: t.tune_rfft_dist(
            4096, make_fft_mesh(4), **est),
    }


def _v5e_exclusions(real):
    """``kernel_exclusions`` as it answers on a v5e."""
    import jax

    def excl(n):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return real(n)
    return excl


def golden(names=None) -> dict:
    import repro.plan.tune as t
    from repro.plan.cost import V5E_KIND, CostParams

    v5e = CostParams.for_backend("tpu", device_kind=V5E_KIND)
    cases = _golden_cases(v5e)
    out = {}
    with mock.patch.object(t, "kernel_exclusions",
                           _v5e_exclusions(t.kernel_exclusions)):
        for name in names or cases:
            winner, info = cases[name]()
            if isinstance(winner, tuple):
                winner = [_jsonable(w) for w in winner]
            out[name] = {"winner": _jsonable(winner), "info": _jsonable(info)}
    return out


def golden_names() -> list[str]:
    with open(GOLDEN) as f:
        return sorted(json.load(f))


# -------------------------------------------------------------- policy

# Families in measure mode, each with the ``top_k`` it races.  The real
# pots race one finalist, so the best of the other family must join it.
POLICY = {"config": 3, "schedule": 2, "dist_config": 2, "dist_schedule": 2,
          "pfft3": 2, "pfft3_single": 2, "pfft1_large": 2, "rfft": 1,
          "rfft_dist": 1}
REAL_POTS = ("rfft", "rfft_dist")
# Local programs a comm sample subtracts, per family that takes one.
COMM_PHASES = {"dist_config": 2.0, "pfft3": 3.0, "pfft3_single": 3.0,
               "rfft_dist": 1.0}


def _fake_seconds(item) -> float:
    """A deterministic stand-in for a measured time."""
    return 1e-3 * (1 + zlib.crc32(repr(item).encode()) % 997)


def _fake_harness(build, items, *_a, **_k):
    return {item: _fake_seconds(item) for item in items}


def _failing_harness(*_a, **_k):
    raise RuntimeError("device lost")


def _policy_cases():
    """{family: (tuner call, how its winner shows in the measured list,
    the info key of that list, whether it takes ``measure_retries``, the
    info key of its comm sample)}."""
    import repro.plan.tune as t
    from repro.launch.mesh import make_fft_mesh, make_pfft3_mesh
    from repro.plan.cost import V5E_KIND, CostParams

    mesh = make_fft_mesh(4)
    v5e = CostParams.for_backend("tpu", device_kind=V5E_KIND)

    def cfg(c):
        return c.to_dict()

    def common(s):
        return s.common_config.to_dict()

    def pencil(out):
        c, axes, info = out
        return [c.to_dict(), list(axes) if axes is not None else None], info

    return {
        # Every padded length is 96, not a power of two: the radices run
        # one program, so only the two batched variants are distinct.
        "config": (lambda **kw: t.tune_config(
            64, d=np.array([24, 20, 20]), pad="fpm",
            pad_lengths=np.array([96, 96, 96]), reps=1, **kw),
            cfg, "measured", False, None),
        "schedule": (lambda **kw: t.tune_schedule(
            48, d=np.array([16, 16, 16]),
            pad_lengths=np.array([48, 64, 64]), fpms=_hetero_fpms(48),
            pad="fpm", reps=1, **kw),
            lambda s: s.describe(), "measured", False, None),
        "dist_config": (lambda **kw: t.tune_dist_config(
            64, mesh, reps=1, **kw),
            cfg, "measured", True, "dist"),
        "dist_schedule": (lambda **kw: t.tune_dist_schedule(
            48, mesh, pad="fpm", pad_lengths=np.array([48, 64, 48, 64]),
            params=v5e, reps=1, **kw),
            lambda s: s.describe(), "grouped_measured", True, None),
        "pfft3": (lambda **kw: pencil(t.tune_pfft3(
            16, make_pfft3_mesh(1, 4), reps=1, **kw)),
            lambda w: w, "measured", True, "pfft3"),
        "pfft3_single": (lambda **kw: pencil(t.tune_pfft3(
            16, None, reps=1, **kw)),
            lambda w: w, "measured", True, "pfft3"),
        "pfft1_large": (lambda **kw: t.tune_pfft1_large(
            1024, reps=1, **kw),
            cfg, "measured", False, None),
        "rfft": (lambda **kw: t.tune_rfft(64, reps=1, **kw),
                 common, "measured", False, None),
        "rfft_dist": (lambda **kw: t.tune_rfft_dist(
            64, mesh, reps=1, **kw),
            common, "measured", True, "dist"),
    }


def policy(local_s: float) -> dict:
    """{family: facts} of each family's measure mode under the fake
    timer, every local-phase probe reading ``local_s``."""
    import repro.plan.tune as t

    out = {}
    for family, (run, shown, measured_as, retries, stats_key) in \
            _policy_cases().items():
        facts: dict = {}
        with mock.patch.multiple(
                t, _time_programs=_fake_harness,
                _measure_local_phase=lambda *a, **k: local_s,
                _measure_local_real_phases=lambda *a, **k: local_s):
            winner, info = run(mode="measure", top_k=POLICY[family])
        facts["winner"] = _jsonable(shown(winner))
        facts["measured"] = _jsonable(info[measured_as])
        facts["time_s"] = info["time_s"]
        if stats_key is not None:
            facts["stats"] = _jsonable(info[stats_key])
        facts["estimate"] = _jsonable(shown(run(mode="estimate",
                                                top_k=POLICY[family])[0]))
        with mock.patch.object(t, "_time_programs", _failing_harness):
            try:
                run(mode="measure", top_k=POLICY[family])
            except RuntimeError as err:
                facts["raised"] = str(err)
            if retries:
                winner, info = run(mode="measure", top_k=POLICY[family],
                                   measure_retries=1)
                facts["fallback"] = info.get("measure_fallback")
                facts["fallback_winner"] = _jsonable(shown(winner))
        out[family] = facts
    return out


def run_all(path: str) -> None:
    """Write the golden and policy results of this tree to ``path``."""
    result = {"golden": golden(),
              "policy": {"clamped": policy(10.0), "small": policy(1e-5)}}
    with open(path, "w") as f:
        json.dump(result, f)


def write_golden(recorded: dict, path: str) -> None:
    """One case a line, keys sorted."""
    with open(path, "w") as f:
        f.write("{\n" + ",\n".join(
            f"{json.dumps(k)}: {json.dumps(v, sort_keys=True)}"
            for k, v in sorted(recorded.items())) + "\n}\n")


if __name__ == "__main__":
    recorded = golden()
    write_golden(recorded, sys.argv[1])
    print(f"{len(recorded)} cases")
