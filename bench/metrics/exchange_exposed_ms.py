"""exchange_exposed_ms: device time of the collective ops in which no
other op runs on that device, per transform, on the device with the
most.  Nothing to read where the trace has no collective op."""

from bench.trace import matching, measure, subtract

# the opcode, not an operand's name
PATTERNS = (r"(?<![%\w.-])(all-to-all|all-gather|all-reduce|reduce-scatter"
            r"|collective-permute)(-start|-done)?\(",)


def read(ctx):
    if ctx.trace is None:
        return None
    exposed = [measure(subtract(coll, matching(dev, PATTERNS, invert=True)))
               for dev in ctx.trace.devices
               if (coll := matching(dev, PATTERNS))]
    return max(exposed) / ctx.calls / 1e6 if exposed else None
