#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell names a configuration and a traffic mix; the
configuration's JSON file has a module beside it (same stem, ``.py``)
that plans the transform through the program's public entry point and
makes the input on the device from the seed; the mix
``bench/traffic/<traffic>.json`` names its driver
``bench/traffic/<driver>.py``; each per-layer metric is read by
``bench/metrics/<metric>.py``; the correctness limits of the cell are in
``bench/workloads/<cell>.json``.  Adding a cell, configuration, mix or
metric adds files and edits none.

A run: set-up (plan, input, first call, warm-up calls) is ``setup_s``;
then a closed-loop window of ``--seconds`` (``--trace 0``) or a traced
window of the mix's ``trace_calls`` calls (``--trace 1``); then the
device's peak memory is read, the device arrays are freed and the last
output is checked against a float64 reference (``bench/check.py``).
The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error
and the last key of that object.  Anything but a TPU with enough chips
exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Any, Callable  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

__all__ = ["Cell", "load_cell", "load_module", "main", "run_cell"]


def load_module(path: Path) -> ModuleType:
    """Import the file at ``path`` (names may hold ``-`` and ``.``)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    name = "bench_dyn_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(path.anchor)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration's JSON file
    config_mod: ModuleType    # plan(n, devices), make_input(n, seed, plan, devices)
    mix: dict                 # the traffic mix's JSON file
    driver: ModuleType        # run(call, x, mix, seconds=|calls=) -> Window
    limits: dict[str, float]  # check name -> limit
    end_to_end: list[dict]
    per_layer: list[dict]
    metrics: dict[str, ModuleType]  # every reader in bench/metrics, by name


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with every file it
    names loaded; raises ``KeyError`` or ``FileNotFoundError``."""
    root = Path(root)
    bench = root / "bench"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    cfg_file = root / entry["file"]
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench / "workloads" / f"{name}.json").read_text())
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    metrics = {p.stem: load_module(p)
               for p in sorted((bench / "metrics").glob("*.py"))}
    return Cell(name=name, chips=int(w["chips"]),
                config=json.loads(cfg_file.read_text()),
                config_mod=load_module(cfg_file.with_suffix(".py")),
                mix=mix,
                driver=load_module(bench / "traffic" / f"{mix['driver']}.py"),
                limits=limits["limits"], end_to_end=e2e, per_layer=per_layer,
                metrics=metrics)


class _CompileEvents:
    """Counts JAX's trace and backend-compile events, and persistent
    cache hits and misses (one listener per process: make one)."""

    def __init__(self) -> None:
        import jax.monitoring
        self.counts = {"trace": 0, "compile": 0, "cache_hit": 0,
                       "cache_miss": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["trace"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.counts["compile"] += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hit"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.counts["cache_miss"] += 1

    def compiles(self) -> int:
        return self.counts["trace"] + self.counts["compile"]


# JAX's listeners cannot be unregistered, so one counter serves the process.
_EVENTS: _CompileEvents | None = None


def _events() -> _CompileEvents:
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = _CompileEvents()
    return _EVENTS


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``; every program is
    cached, so a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _Context:
    """What a per-layer metric reader sees."""

    def __init__(self, cell: Cell, trace, calls: int, counters: dict,
                 work: dict, peaks: dict) -> None:
        self.cell, self.trace, self.calls = cell, trace, calls
        self.counters, self.work, self.peaks = counters, work, peaks

    def metric(self, name: str) -> ModuleType:
        return self.cell.metrics[name]


def _breakdown(trace) -> dict:
    """Top device ops (by instruction name, mean seconds per device) and
    the longest idle gaps of the idlest device, by what the host was
    doing."""
    per_op: dict[str, float] = {}
    for dev in trace.devices:
        for name, s, e in dev.ops:
            short = name.split(" = ", 1)[0]
            per_op[short] = per_op.get(short, 0.0) + (e - s)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idlest = min(trace.devices, key=trace.busy_ns)
    gaps = sorted(trace.idle_gaps(idlest), key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, t / len(trace.devices) / 1e9] for n, t in ops],
            "idle_gaps": [[trace.host_activity(g), (g[1] - g[0]) / 1e9]
                          for g in gaps]}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices: list, wrap: Callable | None = None,
             log: Callable[[str], Any] = print) -> tuple[dict, list[str]]:
    """One run of ``cell`` on ``devices``; returns (result, check lines).

    ``wrap`` replaces the timed call ``plan.execute`` by ``wrap(plan.execute)``
    (the fault tests break the timed path with it)."""
    import jax
    import numpy as np
    from bench import check, trace as trace_mod
    from bench.stats import percentiles
    from bench.work import peaks_for, rowfft_work

    events = _events()
    n = int(cell.mix["n"])
    t0 = time.perf_counter()
    plan = cell.config_mod.plan(n, devices)
    plan_s = time.perf_counter() - t0
    call = plan.execute if wrap is None else wrap(plan.execute)
    x = jax.block_until_ready(cell.config_mod.make_input(n, seed, plan,
                                                         devices))
    t0 = time.perf_counter()
    jax.block_until_ready(call(x))
    compile_s = time.perf_counter() - t0
    for _ in range(int(cell.mix["warmup_calls"])):
        jax.block_until_ready(call(x))
    setup_s = time.perf_counter() - T_START
    log(f"plan config=[{plan.config.describe()}] "
        f"source={plan.tuning.get('source')} n={n} devices={len(devices)} "
        f"compile_cache={jax.config.jax_compilation_cache_dir} "
        f"cache_hits={events.counts['cache_hit']} "
        f"cache_misses={events.counts['cache_miss']}")

    before = events.compiles()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        try:
            win = cell.driver.run(call, x, cell.mix,
                                  calls=int(cell.mix["trace_calls"]))
        finally:
            jax.profiler.stop_trace()
    else:
        win = cell.driver.run(call, x, cell.mix, seconds=seconds)
    in_window = events.compiles() - before
    log(f"window calls={len(win.latencies_s)} window_s={win.window_s} "
        f"compiles_in_window={in_window}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    metrics: dict[str, dict] = {}
    breakdown = None
    if trace:
        try:
            tr = trace_mod.load(trace_mod.find_xplane(trace_dir),
                                calls=len(win.latencies_s))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = _Context(cell, tr, tr.calls,
                       {"plan_s": plan_s, "compile_s": compile_s},
                       rowfft_work(n, devices=len(devices),
                                   **cell.config["work"]),
                       peaks_for(dev.device_kind))
        for spec in cell.per_layer:
            got = cell.metrics[spec["name"]].read(ctx)
            if got is None:
                continue
            entry = dict(got) if isinstance(got, dict) else {"value": got}
            metrics[spec["name"]] = {"value": float(entry.pop("value")),
                                     "unit": spec["unit"], **entry}
        device["busy_s"] = (sum(tr.busy_ns(d) for d in tr.devices)
                            / len(tr.devices) / 1e9)
        device["window_s"] = tr.window_ns / 1e9
        breakdown = _breakdown(tr)
    else:
        lat = win.latencies_s
        e2e = {"setup_s": (setup_s, "s"),
               "transform_ms": (1e3 * win.window_s / len(lat), "ms"),
               "transform_p95_ms": (1e3 * percentiles(lat)["p95"], "ms")}
        for spec in cell.end_to_end:
            value, unit = e2e[spec["name"]]
            metrics[spec["name"]] = {"value": value, "unit": unit}

    out_h = np.asarray(win.out)
    x_h = np.asarray(x)
    calls = len(win.latencies_s)
    del win, x, call, plan
    ref = check.reference_fft2(x_h)
    del x_h
    numbers = check.compare(out_h, ref)
    del ref, out_h
    checks = {k: {"value": numbers[k], "limit": float(cell.limits[k])}
              for k in check.CHECKS}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": calls, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    lines = [f"check {k} {c['value']!r} limit {c['limit']!r}"
             for k, c in checks.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError) as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    src = str(ROOT / "src")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # libtpu logs under /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found platform "
              f"{devices[0].platform!r} ({len(devices)} device(s))",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    use_compile_cache()
    result, lines = run_cell(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace),
                             devices=devices[:cell.chips],
                             log=lambda s: print(s, flush=True))
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
