"""Device seconds by model part, from the run's trace.

The program traces every operation of its chunk and decode programs
under a ``jax.named_scope`` whose name starts with ``bcg.``
(``bcg_tpu/obs/scopes.py`` names the parts); the scope stack reaches
the compiled operation's ``op_name``.  An operation belongs to the
INNERMOST ``bcg.*`` segment of it (:func:`part`); one that carries
none is unscoped.  The first device's ``XLA Ops`` events are summed by
part over the traced window (``program_spans.Record.window_ns``), the
control-flow shells left out as ``lib/trace.py`` leaves them out of its
by-name sums, so the parts and the unscoped time add up to the window's
operation seconds.

Where an event's ``op_name`` is found: a TPU's ``XLA Ops`` event
carries no ``tf_op`` stat, and its name (the instruction's HLO text)
no metadata; the profiler keeps each program's HLO in the
``/host:metadata`` plane (``Hlo Proto``, keyed by the program's name as
the ``XLA Modules`` line names it), and the event's instruction name is
looked up there, once per (program, event name).

A program without the scopes (a parent commit) leaves every operation
unscoped; the readers then return ``None``.

``SOURCE`` gives a run's rows: the run's own trace (:func:`from_run`),
or whatever a test hands it.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Callable, Optional

from . import program_spans, trace

# The rule of bcg_tpu/obs/scopes.py, restated rather than imported: the
# readers run over program trees that predate that module too.
PREFIX = "bcg."
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_REF = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_PROTO = "Hlo Proto"
_METADATA_PLANE = "/host:metadata"


def part(op_name: Optional[str]) -> Optional[str]:
    """The innermost ``bcg.*`` segment of a name stack, or None."""
    for segment in reversed((op_name or "").split("/")):
        if segment.startswith(PREFIX):
            return segment
    return None


def table(rows: list, window: tuple) -> dict:
    """``{part or None: seconds}`` of ``rows`` (``[event name, start_ns,
    dur_ns, op_name]`` of one device's ``XLA Ops``) inside ``window``
    (``(lo_ns, hi_ns)``), shells left out; whether a name is a shell, and
    the part of each distinct ``op_name``, found once."""
    lo, hi = window
    parts, shells, out = {}, {}, {}
    for name, s, d, op_name in rows:
        if s + d <= lo or s >= hi:
            continue
        shell = shells.get(name)
        if shell is None:
            shell = shells[name] = bool(trace._SHELLS.match(trace.op_name(name)))
        if shell:
            continue
        p = parts.get(op_name)
        if p is None and op_name not in parts:
            p = parts[op_name] = part(op_name)
        out[p] = out.get(p, 0.0) + (min(s + d, hi) - max(s, lo)) * program_spans.NS
    return out


# ------------------------------------------------------- the .xplane.pb

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a protobuf message: varints as ints,
    length-delimited fields as slices (skipped in O(1) when unread)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def hlo_protos(path: str) -> dict:
    """``{program name: HloModuleProto bytes}`` of the ``Hlo Proto``
    stats on the ``/host:metadata`` plane (XSpace.planes = 1; XPlane
    name = 2, event_metadata = 4, stat_metadata = 5; XEventMetadata name
    = 2, stats = 5; XStat metadata_id = 1, bytes_value = 6; HloProto
    hlo_module = 1)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        fields = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == _METADATA_PLANE for f, v in fields):
            continue
        stat_ids = set()
        for f, entry in fields:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                if bytes(meta.get(2, b"")).decode() == _HLO_PROTO:
                    stat_ids.add(meta.get(1))
        out = {}
        for f, entry in fields:
            if f != 4:
                continue
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            for f2, stat in _fields(dict(_fields(entry)).get(2, b"")):
                if f2 != 5:
                    continue
                stat = dict(_fields(stat))
                if stat.get(1) in stat_ids and 6 in stat:
                    module = dict(_fields(stat[6])).get(1)
                    if module is not None:
                        out[bytes(meta.get(2, b"")).decode()] = bytes(module)
        return out
    return {}


def hlo_op_names(module_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of one program's HLO."""
    from jax._src.lib import xla_client

    return op_names(xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string())


def op_names(text: str) -> dict:
    """``{instruction name: op_name or None}`` of a program's HLO text.
    An instruction the compiler made carries no ``op_name`` of its own
    (an async slice's start and done, a layout copy): it takes that of
    what it wraps, the root of the computation it ``calls``, else of the
    first operand that has one; None where nothing it reaches has one."""
    own, refs, roots, computation = {}, {}, {}, None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            header = _COMPUTATION.match(line)
            computation = header.group(1) if header else computation
            continue
        name, op_name = m.group(1), _OP_NAME.search(line)
        # a parameter's op_name is its argument's name, no scope stack
        own[name] = op_name.group(1) if op_name and " parameter(" not in line else None
        refs[name] = (_CALLS.findall(line), _REF.findall(line[m.end():]))
        if line.lstrip().startswith("ROOT"):
            roots[computation] = name
    out = {}

    def resolve(name: str, seen: set) -> Optional[str]:
        if own.get(name) is not None or name in out or name in seen:
            return own.get(name) or out.get(name)
        seen.add(name)
        calls, read = refs[name]
        wrapped = [roots[c] for c in calls if c in roots]
        return next(filter(None, (resolve(r, seen) for r in wrapped + read if r in own)),
                    None)

    for name in own:
        out[name] = resolve(name, set())
    return out


def xplane_rows(path: str) -> list:
    """Rows ``[event name, start_ns, dur_ns, op_name or None]`` of the
    first device's ``XLA Ops`` events.  An event's ``op_name`` is its
    instruction's in the HLO of the program it ran in (the ``XLA
    Modules`` event around it), looked up once per (program, event
    name)."""
    from jax.profiler import ProfileData

    planes = [p for p in ProfileData.from_file(path).planes
              if trace.DEVICE_PLANE.match(p.name)]
    if not planes:
        return []
    first = min(planes, key=lambda p: p.name)
    lines = {line.name: line for line in first.lines}
    modules = sorted((e.start_ns, e.end_ns, e.name)
                     for e in (lines[trace.MODULES_LINE].events
                               if trace.MODULES_LINE in lines else ()))
    starts = [m[0] for m in modules]
    protos, by_module, resolved, rows, names = None, {}, {}, [], {}
    for e in (lines[trace.OPS_LINE].events if trace.OPS_LINE in lines else ()):
        s, name = e.start_ns, e.name
        name = names.setdefault(name, name)   # one string a distinct name
        at = bisect.bisect_right(starts, s) - 1
        module = modules[at][2] if at >= 0 and modules[at][1] >= s else None
        key = (module, name)
        if key not in resolved:
            if module is not None and module not in by_module:
                protos = _guarded(hlo_protos, path) if protos is None else protos
                by_module[module] = (_guarded(hlo_op_names, protos[module])
                                     if module in protos else {})
            resolved[key] = by_module.get(module, {}).get(
                trace.op_name(name).split(" ")[0].lstrip("%"))
        rows.append([name, float(s), float(e.duration_ns), resolved[key]])
    return rows


def _guarded(fn, arg) -> dict:
    """``fn(arg)``, or nothing where the trace's HLO cannot be read:
    the operations then read as unscoped, and the run goes on."""
    try:
        return fn(arg)
    except (ValueError, KeyError, IndexError, UnicodeDecodeError, RuntimeError,
            AttributeError, ImportError):
        return {}


@functools.lru_cache(maxsize=1)
def _xplane_table(path: str, _mtime: float, window: tuple) -> dict:
    return table(xplane_rows(path), window)


def from_run(ctx: dict) -> Optional[dict]:
    """The table of the run whose readers' ``ctx`` this is; None where
    the run left no trace or no window."""
    path = program_spans.newest_xplane(program_spans.TRACE_DIR)
    window = program_spans.record(ctx).window_ns() if path else None
    if window is None:
        return None
    return _xplane_table(path, os.path.getmtime(path), window)


SOURCE: Callable[[dict], Optional[dict]] = from_run


def seconds(ctx: dict) -> Optional[dict]:
    """``{part: seconds}`` of the window, unscoped under ``None``; None
    where no operation carries a part (a program without scopes)."""
    got = SOURCE(ctx)
    if not got or not any(p is not None for p in got):
        return None
    return got
