"""Closed loop with one caller: the input stays on the device, each call
ends in ``block_until_ready``, and the next is issued when it returns.

A mix file names this driver and gives ``n`` (the transform size),
``clients`` (1: the loop has one caller), ``warmup_calls`` (calls made
in set-up after the first, compiling one) and ``trace_calls`` (calls in
the traced window).  Each call's output is dropped before the next is
issued, so one output is alive at a time, and the last one is returned
for the check.

The host spans ``bench.window``, ``bench.dispatch`` and ``bench.wait``
mark the window and each call in a profiler trace; they cost about a
microsecond when no trace is taken.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

__all__ = ["Window", "run"]


@dataclasses.dataclass
class Window:
    out: Any                 # the last call's output
    latencies_s: list[float]  # per call, dispatch to ready
    window_s: float          # first dispatch to last ready


def run(call: Callable, x, mix: dict, *, seconds: float | None = None,
        calls: int | None = None) -> Window:
    """Call ``call(x)`` until ``seconds`` have passed or ``calls`` calls
    have completed, whichever is given."""
    from jax.profiler import TraceAnnotation
    if int(mix.get("clients", 1)) != 1:
        raise ValueError("closed_loop drives one caller")
    if (seconds is None) == (calls is None):
        raise ValueError("give seconds or calls")
    lat: list[float] = []
    out = None
    with TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        while True:
            out = None
            t0 = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                out = call(x)
            with TraceAnnotation("bench.wait"):
                out.block_until_ready()
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            if calls is not None and len(lat) >= calls:
                break
            if seconds is not None and t1 - t_start >= seconds:
                break
    return Window(out, lat, t1 - t_start)
