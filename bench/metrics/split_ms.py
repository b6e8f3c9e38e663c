"""split_ms: device time per transform of the ops the program names
``pfft.split`` (complex to f32 planes, row padding), on the device with
the most.

The program names its own phases (``repro.obs``): each live plan maps
the instructions of its executable to a scope, and the trace names a
device op by its instruction.  ``scopes`` and ``scope_ms`` serve the
other scope readers too.  Nothing to read where the program names no
scopes (it has no ``repro.obs``) or no op of the scope ran.
"""

from bench.trace import measure, union

SCOPE = "pfft.split"


def scopes():
    """``{instruction name: scope or None}`` of the program's live
    plans, or None where the program names no scopes."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.live_scope_map() or None


def instruction(event: str) -> str:
    """The instruction an op event of the trace is named by."""
    return event.split(" = ", 1)[0].lstrip("%")


def device_scopes(ctx):
    """Per device, ``[(scope or None, start, end)]`` of its ops; None
    where there is no trace or the program names no scopes."""
    found = scopes() if ctx.trace is not None else None
    if found is None:
        return None
    return [[(found.get(instruction(name)), s, e) for name, s, e in dev.ops]
            for dev in ctx.trace.devices]


def scope_ms(ctx, scope):
    """Device time per transform of ``scope``'s ops, on the device with
    the most; None where none ran."""
    per_dev = device_scopes(ctx)
    if per_dev is None:
        return None
    worst = max(measure(union((s, e) for sc, s, e in ops if sc == scope))
                for ops in per_dev)
    return worst / ctx.calls / 1e6 if worst > 0 else None


def read(ctx):
    return scope_ms(ctx, SCOPE)
