"""idle_share: 1 - busy / window over the traced window, busy being the
union of the device's op intervals, averaged over the devices used."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    tr = ctx.trace
    busy = sum(tr.busy_ns(d) for d in tr.devices) / len(tr.devices)
    return 100.0 * (1.0 - busy / tr.window_ns)
