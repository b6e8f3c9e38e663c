"""unscoped_ms: device time per transform of the ops the program gives
no ``pfft.*`` scope, on the device with the most: the guard on the
scope readers' coverage, about 0 when every phase is named.  0 is a
reading here (every op named); nothing to read where the program names
no scopes at all (see ``split_ms``)."""

from bench.trace import measure, union


def read(ctx):
    per_dev = ctx.metric("split_ms").device_scopes(ctx)
    if per_dev is None:
        return None
    worst = max(measure(union((s, e) for sc, s, e in ops if sc is None))
                for ops in per_dev)
    return worst / ctx.calls / 1e6
