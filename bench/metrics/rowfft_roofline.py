"""rowfft_roofline: the least time the chip could take over the row
phases, as a share of ``rowfft_ms``.

The work is counted by ``bench.work.rowfft_work`` from N alone (5 N
log2 N flops per row, one read and one write of the signal per phase),
so an MXU DFT that does far more flops than the FFT needs reads low,
and no implementation can read over 100%.  At every N here the bytes
set the bound; the result names it.
"""

from bench.work import roofline_s


def read(ctx):
    if ctx.trace is None:
        return None
    rowfft_ms = ctx.metric("rowfft_ms").read(ctx)
    if not rowfft_ms:
        return None
    t_min, bound = roofline_s(ctx.work["device_flops"],
                              ctx.work["device_bytes"], ctx.peaks)
    return {"value": 100.0 * t_min / (rowfft_ms / 1e3), "bound": bound}
