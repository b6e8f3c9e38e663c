"""rowfft_ms: device time of the row-FFT ops per transform, on the
device with the most.

An op counts as a row FFT when its event name (the HLO instruction's
text) matches one of ``PATTERNS``, whatever implements it: a Pallas
row-FFT kernel, whose instruction takes its op wrapper's name
(``fft_rows_op``, ``fft_rows_transpose_op``, ``rfft_rows_op``,
``rfft_rows_transpose_op``), or XLA's ``fft`` instruction.  Only the
instruction's own name and opcode are matched, never its operands.
On the TPU, XLA expands its ``fft`` into convolution fusions with
generic names, which no pattern here can tell from other fusions.
"""

from bench.trace import matching, measure

PATTERNS = (r"^%?r?fft_rows(_transpose)?_op(\.\d+)? ",
            r"(?<![%\w.-])fft\(")


def device_ns(ctx):
    """Row-FFT device time of each device in the window, in ns."""
    return [measure(matching(dev, PATTERNS)) for dev in ctx.trace.devices]


def read(ctx):
    if ctx.trace is None:
        return None
    worst = max(device_ns(ctx), default=0.0)
    return worst / ctx.calls / 1e6 if worst > 0 else None
