"""Reduce a JAX profiler trace to device op intervals, busy time and idle
gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` (nothing but JAX).  Each TPU device plane
(``/device:TPU:<i>``) gives the programs it ran (its ``XLA Modules``
line) and their ops (its ``XLA Ops`` line, one event per HLO
instruction, named by the instruction's text); the ``/host:CPU`` plane
gives the spans of the host thread that ran the loop (the line that
holds the harness's ``bench.*`` spans: Python calls and JAX's dispatch
among them).  Times are nanoseconds.

The window is taken on each device's own clock, which the trace does
not align with the host's to better than a millisecond or two: with K
calls traced and m programs per call, it runs from the start of the
first call's first program to the start of the last call's first
program, so it holds K - 1 whole calls, each with the idle gap that
follows it.  Host spans are shifted onto the device clock by aligning
the end of the first ``bench.dispatch`` span with the start of the
first program, which is good to the tens of microseconds a launch takes.

Intervals are ``(start, end)`` pairs; ``union`` merges them, ``measure``
sums a merged list, ``subtract`` removes one merged list from another.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

__all__ = ["DeviceOps", "Trace", "find_xplane", "load", "matching",
           "measure", "subtract", "union"]

DISPATCH_SPAN = "bench.dispatch"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class DeviceOps:
    """One device's ops in its window: ``(name, start, end)``."""
    name: str
    ops: list[tuple[str, float, float]]
    window: tuple[float, float]


@dataclasses.dataclass
class Trace:
    devices: list[DeviceOps]
    host: list[tuple[str, float, float]]  # on device 0's clock
    calls: int                             # whole calls in the window

    @property
    def window_ns(self) -> float:
        """The windows' mean length."""
        return sum(d.window[1] - d.window[0]
                   for d in self.devices) / len(self.devices)

    @staticmethod
    def busy_ns(dev: DeviceOps) -> float:
        return measure(union((s, e) for _, s, e in dev.ops))

    @staticmethod
    def idle_gaps(dev: DeviceOps) -> list[tuple[float, float]]:
        """Intervals of the window in which ``dev`` ran no op."""
        return subtract([dev.window], union((s, e) for _, s, e in dev.ops))

    def host_activity(self, gap: tuple[float, float]) -> str:
        """What the host was doing in ``gap``: the innermost host span
        covering its midpoint, or ``"no host span"``."""
        mid = 0.5 * (gap[0] + gap[1])
        best = None
        for name, s, e in self.host:
            if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "no host span"


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a, b) -> list[tuple[float, float]]:
    """``a`` minus ``b``; both merged (``union``) lists."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur = s
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def matching(dev: DeviceOps, patterns, *, invert: bool = False
             ) -> list[tuple[float, float]]:
    """Merged intervals of ``dev``'s ops whose name matches a pattern
    (``invert``: matches none)."""
    regs = [re.compile(p) for p in patterns]
    return union((s, e) for name, s, e in dev.ops
                 if any(r.search(name) for r in regs) != invert)


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(plane, line_name=None):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for line in plane.lines
            if line_name is None or line.name == line_name
            for ev in line.events if ev.duration_ns > 0]


def load(path, calls: int) -> Trace:
    """The trace at ``path`` of ``calls`` traced calls, reduced to each
    device's window (see the module docstring)."""
    from jax.profiler import ProfileData
    if calls < 2:
        raise ValueError("a window needs at least 2 traced calls")
    data = ProfileData.from_file(str(path))
    found, host = [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            found.append((int(m.group(1)), plane))
        elif plane.name == _HOST_PLANE:
            # the thread that ran the loop: its line holds the bench spans
            lines = [ln.name for ln in plane.lines
                     if any(ev.name.startswith("bench.") for ev in ln.events)]
            host = [ev for name in lines for ev in _events(plane, name)]
    devices = []
    first_start = None
    for _, plane in sorted(found, key=lambda f: f[0]):
        modules = sorted(s for _, s, _ in _events(plane, "XLA Modules"))
        if not modules or len(modules) % calls:
            raise ValueError(f"{plane.name}: {len(modules)} programs ran "
                             f"in {calls} calls")
        per_call = len(modules) // calls
        window = (modules[0], modules[-per_call])
        if first_start is None:
            first_start = modules[0]
        ops = [(name, max(s, window[0]), min(e, window[1]))
               for name, s, e in _events(plane, "XLA Ops")
               if e > window[0] and s < window[1]]
        devices.append(DeviceOps(plane.name, sorted(ops, key=lambda o: o[1]),
                                 window))
    if not devices:
        raise ValueError(f"trace {path} has no TPU device plane")
    dispatch = sorted(e for name, _, e in host if name == DISPATCH_SPAN)
    shift = first_start - dispatch[0] if dispatch else 0.0
    host = [(name, s + shift, e + shift) for name, s, e in host]
    return Trace(devices, host, calls - 1)
