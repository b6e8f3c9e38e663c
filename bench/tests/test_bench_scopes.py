"""The readers of the program's own names: device time by the scope the
program gives each instruction (``split_ms``, ``join_ms``,
``unscoped_ms``, ``exchange_ms``) and its plan-lifecycle spans
(``plan_partition_s``, ``plan_schedule_s``), on traces written by hand
and on a CPU profiler trace."""

import sys

import pytest

from bench import run, trace
from bench.work import peaks_for, rowfft_work

SCOPES = {"split.1": "pfft.split", "rowfft.2": "pfft.rowfft",
          "join.3": "pfft.join", "a2a.4": "pfft.exchange",
          "stray.5": None}


def _op(name, start, end):
    return (f"%{name} = f32[8,8]{{1,0}} fusion(f32[8,8] %x)", start, end)


class _Plan:
    """Stands in for a live plan of the program."""

    def __init__(self, found):
        self.found = found

    def scope_map(self):
        return self.found


@pytest.fixture
def live_plan():
    from repro import obs
    plan = _Plan(SCOPES)
    obs.register(plan)
    yield plan
    del plan


def _ctx(devices, calls=2):
    t = trace.Trace(devices, [], calls=calls)
    return run._Context(run.load_cell("pfft2-c64-1chip.n8192"), t, calls,
                        {"plan_s": 1.0, "compile_s": 0.5},
                        rowfft_work(512), peaks_for("TPU v5 lite"))


def _read(ctx, name):
    return ctx.cell.metrics[name].read(ctx)


def test_scope_readers_take_the_worst_device(live_plan):
    d0 = trace.DeviceOps("/device:TPU:0", [
        _op("split.1", 0e6, 2e6), _op("rowfft.2", 2e6, 10e6),
        _op("join.3", 10e6, 13e6), _op("stray.5", 13e6, 13.5e6)],
        (0.0, 20e6))
    d1 = trace.DeviceOps("/device:TPU:1", [
        _op("split.1", 0e6, 3e6), _op("rowfft.2", 3e6, 10e6),
        _op("join.3", 10e6, 12e6)], (0.0, 20e6))
    ctx = _ctx([d0, d1])
    assert _read(ctx, "split_ms") == pytest.approx(3 / 2)
    assert _read(ctx, "join_ms") == pytest.approx(3 / 2)
    assert _read(ctx, "unscoped_ms") == pytest.approx(0.5 / 2)
    assert _read(ctx, "exchange_ms") is None        # no exchange ran


def test_exchange_counts_only_what_no_other_scope_covers(live_plan):
    dev = trace.DeviceOps("/device:TPU:0", [
        _op("a2a.4", 0.0, 10e6), _op("rowfft.2", 2e6, 5e6),
        _op("stray.5", 9e6, 12e6), _op("a2a.4", 14e6, 16e6)],
        (0.0, 20e6))
    assert _read(_ctx([dev]), "exchange_ms") == pytest.approx((10 - 3 - 1 + 2)
                                                              / 2)


def test_scope_with_no_op_reads_nothing(live_plan):
    dev = trace.DeviceOps("/device:TPU:0", [_op("rowfft.2", 0.0, 5e6)],
                          (0.0, 10e6))
    ctx = _ctx([dev])
    assert _read(ctx, "split_ms") is None
    assert _read(ctx, "join_ms") is None
    assert _read(ctx, "unscoped_ms") == 0.0         # every op is named


def test_an_op_no_plan_names_is_unscoped(live_plan):
    dev = trace.DeviceOps("/device:TPU:0", [
        _op("rowfft.2", 0.0, 5e6), _op("elsewhere.9", 5e6, 6e6)],
        (0.0, 10e6))
    assert _read(_ctx([dev]), "unscoped_ms") == pytest.approx(0.5)


def test_a_program_without_names_reads_nothing(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    dev = trace.DeviceOps("/device:TPU:0", [_op("split.1", 0.0, 5e6)],
                          (0.0, 10e6))
    ctx = _ctx([dev])
    for name in ("split_ms", "join_ms", "unscoped_ms", "exchange_ms",
                 "plan_partition_s", "plan_schedule_s"):
        assert _read(ctx, name) is None, name


def test_plan_spans_read_the_first_plan():
    from repro import obs
    from repro.core import plan_pfft
    obs.reset()
    ctx = _ctx([])
    assert _read(ctx, "plan_partition_s") is None
    plan_pfft(64, method="lb", p=2)
    first = obs.snapshot()
    plan_pfft(128, method="lb", p=2)
    assert _read(ctx, "plan_partition_s") == \
        first["pfft.plan.partition"]["first_s"] > 0
    assert _read(ctx, "plan_schedule_s") == \
        first["pfft.plan.schedule"]["first_s"] > 0


def test_execute_span_lies_on_the_host_line_the_reduction_keeps(tmp_path):
    """``bench.trace.load`` keeps the host line that holds the harness's
    ``bench.*`` spans; the program's ``pfft.execute`` must be on it, inside
    each ``bench.dispatch``."""
    import jax
    from jax.profiler import ProfileData
    from repro.core import plan_pfft
    cell = run.load_cell("pfft2-c64-1chip.n8192")
    plan = plan_pfft(64, method="lb", p=2)
    x = jax.numpy.ones((64, 64), jax.numpy.complex64)
    plan.execute(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        cell.driver.run(plan.execute, x, cell.mix, calls=2)
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(trace.find_xplane(tmp_path)))
    (host,) = [p for p in data.planes if p.name == trace._HOST_PLANE]
    kept = [ln.name for ln in host.lines
            if any(ev.name.startswith("bench.") for ev in ln.events)]
    events = [ev for name in kept for ev in trace._events(host, name)]
    execute = [(s, e) for name, s, e in events if name == "pfft.execute"]
    dispatch = [(s, e) for name, s, e in events
                if name == trace.DISPATCH_SPAN]
    assert len(execute) == len(dispatch) == 2
    for (s, e), (ds, de) in zip(sorted(execute), sorted(dispatch)):
        assert ds <= s <= e <= de
