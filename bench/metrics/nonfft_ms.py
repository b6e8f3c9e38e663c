"""nonfft_ms: device time per transform in which an op runs that is
neither a row FFT nor a collective: the phase glue (complex/plane split
and join, transposes, copies), on the device with the most."""

from bench.trace import matching, measure, subtract, union


def read(ctx):
    if ctx.trace is None:
        return None
    rowfft = ctx.metric("rowfft_ms").PATTERNS
    coll = ctx.metric("exchange_exposed_ms").PATTERNS
    worst = 0.0
    for dev in ctx.trace.devices:
        busy = union((s, e) for _, s, e in dev.ops)
        rest = subtract(subtract(busy, matching(dev, rowfft)),
                        matching(dev, coll))
        worst = max(worst, measure(rest))
    return worst / ctx.calls / 1e6
