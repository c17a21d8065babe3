"""From the profiler's trace to numbers: device busy time, time by
operation and by program, idle gaps by what the host was doing.

``load_events`` turns an ``.xplane.pb`` into plain rows ``[plane, line,
name, start_ns, dur_ns]``; everything after works on such rows, so the
reduction is checked on a small recorded trace kept beside the tests
(``tests/data/trace_events.json``) with nothing but Python.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
ROUND_SPAN = "bench.round"
# Control-flow shells whose events contain their bodies' own events: kept
# out of the by-name sums (their time is their children's), not out of
# the busy union (a union is indifferent to nesting).
_SHELLS = re.compile(r"^\S+ (while|conditional|call)( |$)")
# On the TPU an operation's event is named by its HLO text,
#   %closed_call.15 = bf16[10,32,512,128]{3,2,1,0:T(8,128)...} custom-call(...)
# which :func:`op_name` shortens to "closed_call.15 custom-call
# bf16[10,32,512,128]": HLO name, opcode, first result shape.
_HLO_NAME = re.compile(r"^%?(?P<name>\S+) = (?P<rest>.*)$", re.S)
_HLO_OPCODE = re.compile(r"[\s)]([a-z][a-z0-9\-]*)\(")
_HLO_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


def op_name(text: str) -> str:
    m = _HLO_NAME.match(text)
    if m is None:
        return text
    rest = m.group("rest")
    op = _HLO_OPCODE.search(" " + rest)
    shape = _HLO_SHAPE.search(rest)
    return " ".join(filter(None, (m.group("name"), op and op.group(1),
                                  shape and shape.group(0))))


def start(trace_dir: str) -> None:
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # the host's Python frames: not read
    options.host_tracer_level = 2        # TraceAnnotation spans: read
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    name = op_name(e.name) if line.name == OPS_LINE else e.name
                    rows.append([plane.name, line.name, name,
                                 float(e.start_ns), float(e.duration_ns)])
    return rows


def union(intervals: list) -> list:
    """Merged, sorted, non-overlapping ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of a merged ``busy`` list inside ``[lo, hi]``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def innermost_span(spans: list, t: float) -> str:
    """Name of the shortest harness span that holds instant ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len(SPAN_PREFIX):] if best else "outside_spans"


def reduce_events(rows: list) -> dict:
    """Everything the per-layer readers take from a trace, in seconds."""
    spans = [(n, s, s + d) for p, _l, n, s, d in rows
             if not DEVICE_PLANE.match(p) and n.startswith(SPAN_PREFIX)]
    rounds = [(s, e) for n, s, e in spans if n == ROUND_SPAN]
    if not rounds:
        raise RuntimeError("the trace holds no bench.round span")
    lo, hi = min(s for s, _ in rounds), max(e for _, e in rounds)
    devices = sorted({p for p, *_ in rows if DEVICE_PLANE.match(p)})
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    busy_s, ops, modules, idle = [], {}, {}, {}
    for dev in devices:
        op_iv, n_dev = [], len(devices)
        for p, line, name, s, d in rows:
            if p != dev or s + d <= lo or s >= hi:
                continue
            inside = min(s + d, hi) - max(s, lo)
            if line == OPS_LINE:
                op_iv.append((s, s + d))
                if not _SHELLS.match(name):
                    ops[name] = ops.get(name, 0.0) + inside / n_dev
            elif line == MODULES_LINE:
                modules[name] = modules.get(name, 0.0) + inside / n_dev
        busy = clip(union(op_iv), lo, hi)
        busy_s.append(total(busy))
        if dev == devices[0]:
            for s, e in gaps(busy, lo, hi):
                label = innermost_span(spans, (s + e) / 2)
                idle[label] = idle.get(label, 0.0) + (e - s)
    ns = 1e-9
    top = lambda table: [[k, v * ns] for k, v in  # noqa: E731
                         sorted(table.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy_s) / len(busy_s) * ns,
        "devices": len(devices),
        "ops_s": {k: v * ns for k, v in ops.items()},
        "modules_s": {k: v * ns for k, v in modules.items()},
        "idle_s": {k: v * ns for k, v in idle.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)},
    }


def reduce(trace_dir: str) -> dict:
    return reduce_events(load_events(trace_dir))


def seconds_matching(table: dict, pattern: str):
    """Sum of a by-name table's entries whose name matches; ``None``
    where nothing matches (the metric then has nothing to read)."""
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return sum(hits) if hits else None
