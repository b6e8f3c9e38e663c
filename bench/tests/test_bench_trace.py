"""The trace reduction and the per-layer readers, on a trace recorded on
a TPU v5e (``bench/tools/record_trace.py --n 512``: three calls of a
planned 512 x 512 transform on the Pallas kernel, then three of XLA's
``fft2``) and on intervals written by hand."""

import re
from pathlib import Path

import pytest

from bench import run, trace
from bench.work import peaks_for, rowfft_work

DATA = Path(__file__).with_name("data") / "tpu_small.xplane.pb"
CALLS = 6


@pytest.fixture(scope="module")
def tr():
    return trace.load(DATA, calls=CALLS)


def _cell():
    return run.load_cell("pfft2-c64-1chip.n8192")


def _ctx(t, n=512, devices=1):
    return run._Context(_cell(), t, t.calls, {"plan_s": 1.5, "compile_s": 2.5},
                        rowfft_work(n, devices=devices),
                        peaks_for("TPU v5 lite"))


def test_interval_algebra():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert trace.measure(merged) == 6
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert trace.subtract([(0, 3), (5, 8)], []) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 3)], [(-1, 4)]) == []


def test_window_holds_whole_calls(tr):
    assert tr.calls == CALLS - 1
    (dev,) = tr.devices
    assert dev.name == "/device:TPU:0"
    lo, hi = dev.window
    assert all(lo <= s <= e <= hi for _, s, e in dev.ops)
    assert 0 < tr.window_ns == hi - lo


def test_busy_is_the_union_and_gaps_fill_the_rest(tr):
    (dev,) = tr.devices
    busy = tr.busy_ns(dev)
    total = sum(e - s for _, s, e in dev.ops)
    assert 0 < busy <= total and busy < tr.window_ns
    gaps = tr.idle_gaps(dev)
    assert trace.measure(gaps) == pytest.approx(tr.window_ns - busy)
    assert all(e > s for s, e in gaps)


def test_rowfft_matcher_catches_the_pallas_kernel(tr):
    rowfft = _cell().metrics["rowfft_ms"]
    (dev,) = tr.devices
    names = {name.split(" = ")[0] for name, _, _ in dev.ops
             if any(re.search(p, name)
                    for p in rowfft.PATTERNS)}
    assert names == {"%fft_rows_op.2", "%fft_rows_op.3"}
    assert trace.measure(trace.matching(dev, rowfft.PATTERNS)) > 0


def test_rowfft_matcher_catches_an_xla_fft_and_no_operand():
    rowfft = _cell().metrics["rowfft_ms"]
    dev = trace.DeviceOps("/device:TPU:0", [
        ("%fft.3 = c64[512,512]{1,0} fft(c64[512,512]{1,0} %param.1), "
         "fft_type=FFT, fft_length={512}", 0.0, 10.0),
        ("%fft_rows_transpose_op.2 = (f32[8,8]{1,0}) custom-call(f32[8,8] "
         "%custom-call.1), custom_call_target=\"tpu_custom_call\"", 20.0, 25.0),
        ("%multiply_add_fusion = f32[8,8]{1,0} fusion(f32[8,8]{1,0} "
         "%pallas_call.7, f32[8,8] %fft.3), kind=kLoop", 30.0, 40.0)],
        (0.0, 50.0))
    assert trace.matching(dev, rowfft.PATTERNS) == [(0.0, 10.0), (20.0, 25.0)]


def test_readers_on_the_recorded_trace(tr):
    ctx = _ctx(tr)
    cell = ctx.cell
    rowfft_ms = cell.metrics["rowfft_ms"].read(ctx)
    assert rowfft_ms > 0
    roof = cell.metrics["rowfft_roofline"].read(ctx)
    assert roof["bound"] == "bytes" and 0 < roof["value"] <= 100
    nonfft = cell.metrics["nonfft_ms"].read(ctx)
    idle = cell.metrics["idle_share"].read(ctx)
    assert nonfft > 0 and 0 < idle < 100
    (dev,) = tr.devices
    busy_ms = tr.busy_ns(dev) / tr.calls / 1e6
    assert rowfft_ms + nonfft == pytest.approx(busy_ms)
    assert cell.metrics["exchange_exposed_ms"].read(ctx) is None
    assert cell.metrics["plan_s"].read(ctx) == 1.5
    assert cell.metrics["compile_s"].read(ctx) == 2.5


def test_roofline_by_hand(tr):
    ctx = _ctx(tr)
    rowfft_ms = ctx.cell.metrics["rowfft_ms"].read(ctx)
    got = ctx.cell.metrics["rowfft_roofline"].read(ctx)["value"]
    assert got == pytest.approx(100 * (32 * 512 * 512 / 819e9)
                                / (rowfft_ms / 1e3))


def test_exchange_exposed_counts_only_uncovered_collective_time():
    dev = trace.DeviceOps("/device:TPU:0", [
        ("%all-to-all.1 = f32[8,8] all-to-all(f32[8,8] %a)", 0.0, 10e6),
        ("%fusion.2 = f32[8,8] fusion(f32[8,8] %all-to-all.1)", 2e6, 5e6),
        ("%fft_rows_op.1 = (f32[8,8]) custom-call(f32[8,8] %b)", 9e6, 12e6)],
        (0.0, 20e6))
    t = trace.Trace([dev], [], calls=2)
    ctx = _ctx(t)
    assert ctx.cell.metrics["exchange_exposed_ms"].read(ctx) == \
        pytest.approx((10 - 3 - 1) / 2)
    assert ctx.cell.metrics["nonfft_ms"].read(ctx) == pytest.approx(0.0)
    assert ctx.cell.metrics["idle_share"].read(ctx) == pytest.approx(40.0)


def test_breakdown_names_ops_and_gaps(tr):
    b = run._breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(" = " not in name for name, _ in b["device_ops"])
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0


def test_host_activity_is_the_innermost_span():
    t = trace.Trace([], [("bench.window", 0, 100), ("bench.wait", 10, 50),
                         ("ReadSyncFlag", 20, 30)], calls=1)
    assert t.host_activity((22, 26)) == "ReadSyncFlag"
    assert t.host_activity((40, 46)) == "bench.wait"
    assert t.host_activity((200, 210)) == "no host span"


def test_load_refuses_calls_that_do_not_divide_the_programs():
    with pytest.raises(ValueError, match="programs ran"):
        trace.load(DATA, calls=4)
    with pytest.raises(ValueError, match="at least 2"):
        trace.load(DATA, calls=1)
