"""Names for the program's phases: device scopes and host spans.

Device scopes.  ``scope(name)`` is ``jax.named_scope(name)``: it only
changes the HLO metadata (``op_name``) of what is traced inside it, so
it costs nothing at run time.  The program puts one around the work of
each phase, where the work happens:

* ``pfft.split``     complex -> f32 (re, im) planes, row padding
* ``pfft.rowfft``    the row FFTs (Pallas kernels, XLA's ``fft``,
  Stockham, Bluestein) and the segment gather/scatter around them
* ``pfft.join``      f32 planes -> complex
* ``pfft.transpose`` layout moves between phases (transposes, panel
  cuts and re-interleaves)
* ``pfft.exchange``  the collectives of a distributed transpose

Attribution rule for a compiled instruction: the innermost ``pfft.*``
component of its ``op_name`` wins (a fusion carries the ``op_name`` XLA
copies from its root).  One exception: the TPU compiler lowers
complex <-> f32-plane conversions to ``X64SplitLow``/``X64SplitHigh``
and ``X64Combine`` custom calls that take their operand's metadata, not
the scope they were written in; they are the split and the join by
definition and map to ``pfft.split`` / ``pfft.join`` wherever they
appear.  An instruction the compiler or a lowering made on its own (a
copy, a hoisted constant, a loop's initial state: no ``op_name``, or one
that ends in a call such as ``jit(f)`` or ``shard_map``) takes the one
scope all of its users share; the program's result, which nothing uses,
takes the one scope its operands share.  ``scope_map`` applies the rule
to compiled HLO text; a plan's ``scope_map()`` applies it to its own
executable, and so names each op of an xprof trace of that plan (the
trace names a device op by its instruction) by program phase.

Host spans.  ``span(name)`` enters ``jax.profiler.TraceAnnotation`` (the
profiler's host plane and clock) and adds its host-clock duration to a
process-wide table read by ``snapshot()`` and cleared by ``reset()``:
``{name: {"count", "total_s", "first_s"}}``.  The program's spans are
``pfft.plan.partition``, ``pfft.plan.schedule`` and ``pfft.execute``
(the only one per call: one annotation and two clock reads).

Counters.  A process-wide table ``{name: count}`` beside the span
table, read by ``counters()`` and cleared by ``reset()``; ``count``
records one.  The program's counters describe one transform of a live
plan's executable: a plan's ``counters()`` reads them from the same
compiled text as its ``scope_map()`` (``exchange_counts``), never from
tuning candidates and never per call, and ``live_counters()`` merges
those of every live plan into the table.

* ``pfft.exchange.collectives``  the ``all-to-all`` instructions the
  executable holds (a sync op or an async ``-done``; the TPU compiler
  carries a complex exchange as two f32 collectives, re and im)
* ``pfft.exchange.bytes``  the bytes each device sends off the device
  through them: a collective over a group of g devices keeps 1/g of its
  result where it is
* ``pfft.transpose.sparse_tiles``  the instructions of a ``pfft.*``
  scope whose result, 1 MiB or more, is laid out in tiles one row high
  (``T(1,...)``): on a TPU such an array fills one sublane of the eight
  of each vreg, so moving it costs up to eight times its bytes

All three count instructions, not executions: they are per transform
only while every exchange runs once a call, as in the slab pipeline,
whose panel loop is unrolled.  A plan whose executable holds no
``all-to-all`` (one chip) has no exchange counters; an executable whose
text carries no tiled layouts (the CPU's) has no ``sparse_tiles``.
"""

from __future__ import annotations

import math
import re
import threading
import time
import weakref

import jax
from jax.profiler import TraceAnnotation

__all__ = ["COLLECTIVES", "EXCHANGE", "EXCHANGE_BYTES", "JOIN", "ROWFFT",
           "SCOPES", "SPARSE_TILES", "SPLIT", "TRANSPOSE", "compiled_text",
           "count", "counters", "exchange_counts", "live_counters",
           "live_scope_map", "register", "reset", "scope", "scope_map",
           "snapshot", "span", "sparse_tile_counts"]

SPLIT, ROWFFT, JOIN, TRANSPOSE, EXCHANGE = SCOPES = (
    "pfft.split", "pfft.rowfft", "pfft.join", "pfft.transpose",
    "pfft.exchange")

_LOWERED = {"X64SplitLow": SPLIT, "X64SplitHigh": SPLIT,
            "X64Combine": JOIN}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_PARAM = re.compile(r"\bparameter\(")
_CALLS = ("shard_map",)

COLLECTIVES, EXCHANGE_BYTES = ("pfft.exchange.collectives",
                               "pfft.exchange.bytes")
_A2A = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) all-to-all(-start|-done)?\(")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8, "c64": 8, "c128": 16}
SPARSE_TILES = "pfft.transpose.sparse_tiles"
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) [\w\-]+\(")
_SPARSE_MIN_BYTES = 1 << 20
_GROUPS = re.compile(r"replica_groups=(?:\{\{([\d,]*)\}|\[\d+,(\d+)\])")
_PARTITIONS = re.compile(r"\bnum_partitions=(\d+)")


def scope(name: str):
    """``jax.named_scope(name)``; ``name`` is one of ``SCOPES``."""
    return jax.named_scope(name)


def scope_map(hlo_text: str) -> dict[str, str | None]:
    """``{instruction name: scope or None}`` of every instruction in
    compiled HLO text, by the attribution rule above."""
    out: dict[str, str | None] = {}
    block: list[tuple[str, str]] = []
    for line in hlo_text.splitlines() + [""]:
        m = _INSTR.match(line)
        if m is not None:
            block.append((m.group(1), line))
        elif block:
            _attribute(block, out)
            block = []
    return out


def _attribute(block: list[tuple[str, str]], out: dict) -> None:
    """Scopes of one computation's instructions (in text order, which
    puts every user after its operands)."""
    names = {name for name, _ in block}
    operands: dict[str, list[str]] = {}
    users: dict[str, list[str]] = {name: [] for name in names}
    made = []
    for name, line in block:
        rhs = line.split(" = ", 1)[1]
        operands[name] = [r for r in _REF.findall(rhs)
                          if r in names and r != name]
        for ref in operands[name]:
            users[ref].append(name)
        target = _TARGET.search(line)
        found = _LOWERED.get(target.group(1)) if target else None
        op = _OP_NAME.search(line)
        parts = op.group(1).split("/") if op else []
        if found is None:
            found = next((p for p in reversed(parts) if p in SCOPES), None)
        out[name] = found
        if found is None and not _PARAM.search(rhs) and (
                not parts or "(" in parts[-1] or parts[-1] in _CALLS):
            made.append(name)
    for name in reversed(made):
        found = {out[u] for u in users[name]}
        if len(found) == 1:
            out[name] = found.pop()
    for name in made:
        if not users[name]:
            found = {out[o] for o in operands[name]}
            if len(found) == 1:
                out[name] = found.pop()


def compiled_text(fn, spec) -> str:
    """The HLO text of the executable ``fn`` (a jitted function) runs
    for inputs like ``spec`` (a ``jax.ShapeDtypeStruct``)."""
    return fn.lower(spec).compile().as_text()


def exchange_counts(hlo_text: str) -> dict[str, int]:
    """``{COLLECTIVES: k, EXCHANGE_BYTES: b}`` of the ``all-to-all``
    instructions in compiled HLO text (see the module docstring), or
    ``{}`` where it has none."""
    found = _PARTITIONS.search(hlo_text)
    default = int(found.group(1)) if found else 1
    started: dict[str, int] = {}     # an async start's group size
    k = sent = 0
    for line in hlo_text.splitlines():
        m = _A2A.match(line)
        if m is None:
            continue
        name, result, kind = m.groups()
        g = _GROUPS.search(line)
        if g is None:
            size = default
        elif g.group(2):
            size = int(g.group(2))
        else:
            size = len(g.group(1).split(","))
        if kind == "-start":
            started[name] = size
            continue
        if kind == "-done":
            ref = _REF.search(line, m.end())
            size = started.get(ref.group(1) if ref else "", default)
        k += 1
        sent += _nbytes(result) * (size - 1) // size
    return {COLLECTIVES: k, EXCHANGE_BYTES: sent} if k else {}


def sparse_tile_counts(hlo_text: str,
                       scopes: dict[str, str | None]) -> dict[str, int]:
    """``{SPARSE_TILES: k}`` of compiled HLO text whose ``scope_map`` is
    ``scopes`` (see the module docstring), or ``{}`` where the text
    carries no tiled layouts."""
    if ":T(" not in hlo_text:
        return {}
    k = 0
    for line in hlo_text.splitlines():
        m = _RESULT.match(line)
        if (m is not None and "T(1," in m.group(2)
                and scopes.get(m.group(1)) is not None
                and _nbytes(m.group(2)) >= _SPARSE_MIN_BYTES):
            k += 1
    return {SPARSE_TILES: k}


def _nbytes(result: str) -> int:
    """The bytes of the arrays an instruction's result type names (a
    type without a size here, such as ``token``, counts none)."""
    return sum(_ITEMSIZE.get(dt, 0)
               * math.prod(int(d) for d in dims.split(",") if d)
               for dt, dims in _ARRAY.findall(result))


# Live plans, by id; a plan leaves when it is collected.  (Plans are
# dataclasses with value equality, hence unhashable: no WeakSet.)
_LIVE: weakref.WeakValueDictionary = weakref.WeakValueDictionary()


def register(plan) -> None:
    """Track ``plan`` (anything with ``scope_map()``) while it lives."""
    _LIVE[id(plan)] = plan


def live_scope_map() -> dict[str, str | None]:
    """The merged ``scope_map()`` of every live plan; an instruction
    name two plans map to different scopes maps to None."""
    merged: dict[str, str | None] = {}
    for plan in list(_LIVE.values()):
        for name, found in plan.scope_map().items():
            merged[name] = (found if merged.get(name, found) == found
                            else None)
    return merged


def live_counters() -> dict[str, int]:
    """``counters()`` after recording the merged ``counters()`` of every
    live plan (where two differ, the later-made plan's)."""
    merged: dict[str, int] = {}
    for plan in list(_LIVE.values()):
        merged.update(getattr(plan, "counters", dict)())
    for name, value in merged.items():
        count(name, value)
    return counters()


_SPANS: dict[str, dict] = {}
_COUNTERS: dict[str, int] = {}
_LOCK = threading.Lock()


class span:
    """Context manager: a profiler host span and a host-clock record."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        with _LOCK:
            rec = _SPANS.get(self.name)
            if rec is None:
                _SPANS[self.name] = {"count": 1, "total_s": dt,
                                     "first_s": dt}
            else:
                rec["count"] += 1
                rec["total_s"] += dt


def snapshot() -> dict[str, dict]:
    """A copy of the span table."""
    with _LOCK:
        return {k: dict(v) for k, v in _SPANS.items()}


def count(name: str, value: int) -> None:
    """Record ``value`` as the counter ``name`` (the latest one stands)."""
    with _LOCK:
        _COUNTERS[name] = value


def counters() -> dict[str, int]:
    """A copy of the counter table."""
    with _LOCK:
        return dict(_COUNTERS)


def reset() -> None:
    """Clear the span and counter tables."""
    with _LOCK:
        _SPANS.clear()
        _COUNTERS.clear()
