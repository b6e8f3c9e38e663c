"""exchange_ms: device time per transform in which an op the program
names ``pfft.exchange`` runs and no op of another scope (or of none)
does, on the device with the most: the exchange the row phases do not
hide.  Nothing to read where no exchange ran (one chip) or the program
names no scopes (see ``split_ms``)."""

from bench.trace import measure, subtract, union

SCOPE = "pfft.exchange"


def read(ctx):
    per_dev = ctx.metric("split_ms").device_scopes(ctx)
    if per_dev is None:
        return None
    exposed = [measure(subtract(
        union((s, e) for sc, s, e in ops if sc == SCOPE),
        union((s, e) for sc, s, e in ops if sc != SCOPE)))
        for ops in per_dev if any(sc == SCOPE for sc, _, _ in ops)]
    return max(exposed) / ctx.calls / 1e6 if exposed else None
