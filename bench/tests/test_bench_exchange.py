"""The four-chip cell's readers: the exchange's share of the ICI roofline
on traces written by hand, the program's exchange counter, and the
accepted per-layer metrics read in the four-chip cell by their names."""

import pytest

from bench import run, trace
from bench.work import peaks_for, rowfft_work

CELL = "pfft2-c64-4chip.n32768"
N, P = 32768, 4
# the floor: 2 * 3/4 * 8 N^2/4 bytes a device at 1600 Gbit/s
FLOOR_NS = 2 * 3 / 4 * 8 * N * N / 4 / 200e9 * 1e9


def _ctx(devices, calls=2, cell=CELL):
    tr = None if devices is None else trace.Trace(devices, [], calls=calls)
    c = run.load_cell(cell)
    return run._Context(c, tr, calls, {}, rowfft_work(N, devices=c.chips),
                        peaks_for("TPU v5 lite"))


def _read(ctx, name):
    return ctx.cell.metrics[name].read(ctx)


def _dev(ops, end=100e6):
    return trace.DeviceOps("/device:TPU:0", ops, (0.0, end))


def _sync(i, start, end):
    return (f"%all-to-all.{i} = f32[8,8]{{1,0}} all-to-all(f32[8,8] %x), "
            "replica_groups={{0,1,2,3}}", start, end)


def _async(i, start, end, *, launch=0.1e6):
    """An async pair launched at ``start``, completed at ``end``, with a
    compute op between its two events."""
    return [(f"%all-to-all-start.{i} = ((f32[8,8]{{1,0}}), f32[8,8]{{1,0}}) "
             "all-to-all-start(f32[8,8] %x), replica_groups={{0,1,2,3}}",
             start, start + launch),
            (f"%fusion.{i} = f32[8,8]{{1,0}} fusion(f32[8,8] %y)",
             start + launch, end - launch),
            (f"%all-to-all-done.{i} = f32[8,8]{{1,0}} all-to-all-done("
             f"((f32[8,8]{{1,0}}), f32[8,8]{{1,0}}) %all-to-all-start.{i})",
             end - launch, end)]


@pytest.mark.parametrize("form", ["sync", "async"])
def test_exchange_roofline_reads_one_floor_as_100(form):
    ops = []
    for i, start in enumerate((0.0, 40e6)):       # one collective a call
        end = start + FLOOR_NS
        ops += [_sync(i, start, end)] if form == "sync" else _async(i, start,
                                                                     end)
    got = _read(_ctx([_dev(ops)]), "exchange_roofline")
    assert got["bound"] == "ici"
    assert got["value"] == pytest.approx(100.0)
    assert got["value"] <= 100.0 + 1e-9


def test_exchange_roofline_takes_the_device_with_the_most():
    fast = _dev([_sync(0, 0.0, 2 * FLOOR_NS)])
    slow = _dev(_async(0, 0.0, 4 * FLOOR_NS))
    assert _read(_ctx([fast, slow]), "exchange_roofline")["value"] == \
        pytest.approx(50.0)


def test_exchange_roofline_reads_nothing_without_an_exchange():
    assert _read(_ctx(None), "exchange_roofline") is None
    plain = _dev([("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8] %y)", 0, 5e6)])
    assert _read(_ctx([plain]), "exchange_roofline") is None
    one_chip = _ctx([_dev([_sync(0, 0.0, 5e6)])],
                    cell="pfft2-c64-1chip.n8192")
    assert _read(one_chip, "exchange_roofline") is None


def test_exchange_gb_reads_the_program_counter(monkeypatch):
    from repro import obs
    ctx = _ctx(None)
    monkeypatch.setattr(obs, "live_counters",
                        lambda: {obs.EXCHANGE_BYTES: 3221225472})
    assert _read(ctx, "exchange_gb") == pytest.approx(3.221225472)
    monkeypatch.setattr(obs, "live_counters", lambda: {})
    assert _read(ctx, "exchange_gb") is None
    monkeypatch.delattr(obs, "live_counters")      # a program without
    assert _read(ctx, "exchange_gb") is None


# the per-layer metrics the one-chip cell had before this cell came
ACCEPTED = ("plan_s", "compile_s", "rowfft_ms", "rowfft_roofline",
            "nonfft_ms", "idle_share", "split_ms", "join_ms", "unscoped_ms",
            "plan_partition_s", "plan_schedule_s")


@pytest.mark.parametrize("name", ACCEPTED)
def test_four_chip_cell_reads_the_accepted_metric_by_its_name(name):
    one = run.load_cell("pfft2-c64-1chip.n8192")
    four = run.load_cell(CELL)
    assert [m for m in four.per_layer if m["name"] == name] == \
        [m for m in one.per_layer if m["name"] == name] != []


def test_row_fft_and_glue_read_the_four_chip_trace():
    assert _read(_ctx(None), "rowfft_ms") is None
    ops = [("%fft_rows_op.3 = f32[8,8]{1,0} custom-call(f32[8,8] %x)",
            0.0, 30e6),
           ("%fusion.4 = f32[8,8]{1,0} fusion(f32[8,8] %y)", 30e6, 42e6),
           _sync(5, 42e6, 60e6)]
    ctx = _ctx([_dev(ops)])
    assert _read(ctx, "rowfft_ms") == pytest.approx(15.0)   # 30 ms, 2 calls
    assert _read(ctx, "nonfft_ms") == pytest.approx(6.0)    # the fusion
    # a quarter of the transform's bytes a device, at 819 GB/s
    floor_s = 2 * 2 * N * N * 8 / P / 819e9
    got = _read(ctx, "rowfft_roofline")
    assert got["bound"] == "bytes"
    assert got["value"] == pytest.approx(100.0 * floor_s / 15e-3, rel=1e-3)
