"""The harness's entry point: it refuses what is not a TPU, and finds a
cell, configuration, mix and metric by name from files alone."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)
    return env


def _run(args, cwd, **extra):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=_cpu_env(**extra), capture_output=True,
                          text=True, timeout=300)


def test_exits_nonzero_on_cpu():
    proc = _run(["--workload", "pfft2-c64-1chip.n8192", "--seed",
                 str(2**31 + 5), "--seconds", "1", "--trace", "0"], ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(["--workload", "pfft2-c64-1chip.n8192", "--seed", "1",
                 "--seconds", "1"], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_workload_exits_nonzero():
    proc = _run(["--workload", "nope", "--seed", "1", "--seconds", "1"], ROOT)
    assert proc.returncode == 2 and "no workload" in proc.stderr


def test_manifest_names_existing_files():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert set(cell.limits) == {"max_rel_err", "rms_rel_err"}
        assert {m["name"] for m in cell.end_to_end} == {
            "setup_s", "transform_ms", "transform_p95_ms"}
        names = {m["name"] for m in cell.per_layer}
        assert names <= set(cell.metrics)
        assert ("exchange_exposed_ms" in names) == (cell.chips > 1)
        assert all(callable(m.read) for m in cell.metrics.values())


NEW_METRIC = '''
"""calls_seen: how many calls the traced window made."""


def read(ctx):
    return float(ctx.calls)
'''


def _add_cell(root: Path) -> str:
    """A new configuration, mix, cell and metric, by new files and new
    entries in a copy of the manifest."""
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "pfft2-c64-1chip.json").read_text())
    cfg["name"] = "pfft2-c64-lb1"
    (bench / "configs" / "pfft2-c64-lb1.json").write_text(json.dumps(cfg))
    (bench / "configs" / "pfft2-c64-lb1.py").write_text(
        "def plan(n, devices):\n"
        "    from repro.core import plan_pfft\n"
        "    return plan_pfft(n, method='lb', p=1)\n\n"
        "def make_input(n, seed, plan, devices):\n"
        "    from jax.sharding import SingleDeviceSharding\n"
        "    from bench.inputs import complex_normal\n"
        "    return complex_normal(n, seed, SingleDeviceSharding(devices[0]))\n")
    (bench / "traffic" / "closed_n128.json").write_text(json.dumps(
        {"driver": "closed_loop", "n": 128, "clients": 1,
         "warmup_calls": 1, "trace_calls": 2}))
    (bench / "workloads" / "pfft2-c64-lb1.n128.json").write_text(json.dumps(
        {"limits": {"max_rel_err": 1e-4, "rms_rel_err": 1e-5}}))
    (bench / "metrics" / "calls_seen.py").write_text(NEW_METRIC)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "pfft2-c64-lb1", "source": "x",
                                "file": "bench/configs/pfft2-c64-lb1.json",
                                "reduced": [], "why": "x"})
    manifest["workloads"].append({"name": "pfft2-c64-lb1.n128",
                                  "config": "pfft2-c64-lb1",
                                  "traffic": "closed_n128", "chips": 1,
                                  "why": "x"})
    manifest["per_layer"].append({"name": "calls_seen", "unit": "calls",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "device", "moves": "transform_ms",
                                  "workloads": ["pfft2-c64-lb1.n128"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return "pfft2-c64-lb1.n128"


def test_new_cell_and_metric_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    name = _add_cell(tmp_path)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    cell = run.load_cell(name, root=tmp_path)
    assert cell.mix["n"] == 128 and cell.driver.__name__.endswith(
        "closed_loop_py")
    assert [m["name"] for m in cell.per_layer] == ["calls_seen"]
    ctx = run._Context(cell, None, 7, {}, {}, {})
    assert cell.metrics["calls_seen"].read(ctx) == 7.0
    old = run.load_cell("pfft2-c64-1chip.n8192", root=tmp_path)
    assert "calls_seen" not in {m["name"] for m in old.per_layer}


def test_new_cell_runs_on_cpu_once_the_chip_check_is_skipped(tmp_path):
    import jax
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cell = run.load_cell(_add_cell(tmp_path), root=tmp_path)
    res, lines = run.run_cell(cell, seed=2**40 + 3, seconds=0.2, trace=False,
                              devices=jax.devices()[:1], log=lambda s: None)
    assert res["correct"] is True and res["attempted"] >= 1
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(res["metrics"]) == {"setup_s", "transform_ms",
                                   "transform_p95_ms"}
    assert [ln.split()[1] for ln in lines] == ["max_rel_err", "rms_rel_err"]
