"""exchange_roofline: the least time the deployment's exchange could
take, as a share of the device time its collectives take, per
transform, on the device with the most.

The least time is counted from N, p and the itemsize alone, never from
the program: each of the transform's transposes leaves (p - 1)/p of a
device's N²/p elements to the other devices, so each device sends
``phases * (p - 1)/p * itemsize * N**2 / p`` bytes, at the chip's ICI
peak (``ici_bits_per_s`` / 8 in ``bench/peaks.json``).  A collective
takes the time from its launch to its completion: a sync op its own
event, an async pair from the ``-start`` event's beginning to the
``-done`` event's end (the op events alone would leave out the time in
between).  Nothing to read on one chip or where no collective ran.
"""

import re

from bench.trace import measure, union

# the instruction an async -done completes: its first operand
_OPERAND = re.compile(r"%([\w.\-]+)")


def exchange_bytes(n: int, p: int, itemsize: int, phases: int) -> float:
    """Bytes each of ``p`` devices sends off the device in one
    transform of the row-sharded N x N signal."""
    return phases * (p - 1) / p * itemsize * n * n / p


def collective_spans(dev, pattern) -> list[tuple[float, float]]:
    """Merged launch-to-completion intervals of ``dev``'s collectives."""
    starts: dict[str, tuple[float, float]] = {}
    spans = []
    for name, s, e in sorted(dev.ops, key=lambda op: op[1]):
        m = pattern.search(name)
        if m is None:
            continue
        if m.group(2) == "-start":
            starts[name.split(" = ", 1)[0].lstrip("%")] = (s, e)
        elif m.group(2) == "-done":
            ref = _OPERAND.search(name, m.end())
            spans.append((starts.pop(ref.group(1), (s, e))[0] if ref else s,
                          e))
        else:
            spans.append((s, e))
    return union(spans + list(starts.values()))


def read(ctx):
    p = ctx.cell.chips
    if ctx.trace is None or p < 2:
        return None
    pattern = re.compile(ctx.metric("exchange_exposed_ms").PATTERNS[0])
    worst = max(measure(collective_spans(dev, pattern))
                for dev in ctx.trace.devices)
    if worst <= 0:
        return None
    work = ctx.cell.config["work"]
    t_min = exchange_bytes(int(ctx.cell.mix["n"]), p, work["itemsize"],
                           work["phases"]) / (ctx.peaks["ici_bits_per_s"] / 8)
    return {"value": 100.0 * t_min / (worst / ctx.calls / 1e9),
            "bound": "ici"}
