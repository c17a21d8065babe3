"""The program's own spans and counters, as the per-layer readers see
them: one :class:`Record` of a traced run.

With ``BCG_TPU_TRACE=1`` the program's tracer (``bcg_tpu/obs/tracer.py``)
mirrors every span into the profiler's trace as ``bcg.<name>``, so the
run's ``.xplane.pb`` carries them on the host plane, on the clock of the
device rows that :func:`lib.trace.load_events` returns.  What the
profiler never sees comes from the tracer's public surface, on the host
clock (``time.perf_counter``): set-up's spans (the profiler starts with
the window), intervals measured after the fact (``jax.trace`` /
``jax.lower`` / ``jax.compile``) and what a span learned by its end
(``steps``).

A program without the mirror (a parent commit) leaves every part empty;
a reader then finds nothing and returns ``None``.

``SOURCE`` is where :func:`record` takes a run's record from: the run
itself (:func:`from_run`), or a file (:func:`from_file`) where there is
no run, as in this directory's tests.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
from typing import Callable, Optional

from . import trace

PREFIX = "bcg."
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         ".trace")
NS = 1e-9


@dataclasses.dataclass
class Record:
    host: list                 # [name, start_ns, dur_ns]: bcg.* and bench.* of the host plane
    device: list               # device rows as lib.trace.load_events gives them
    events: Optional[list]     # tracer intervals [name, t0_s, t1_s, args], host clock
    evicted: int               # events the tracer's ring dropped
    counters: dict             # the program's counters over the window
    setup_end: Optional[float]  # host clock at the end of the first round played

    # ---------------------------------------------------- the profiler's clock

    def window_ns(self) -> Optional[tuple]:
        """The traced window, as ``lib.trace.reduce_events`` takes it:
        from the first ``bench.round`` span's start to the last one's
        end."""
        rounds = [(s, s + d) for n, s, d in self.host if n == trace.ROUND_SPAN]
        if not rounds:
            return None
        return min(s for s, _ in rounds), max(e for _, e in rounds)

    def spans(self, name: str) -> list:
        """``(start_ns, end_ns)`` of the window's ``bcg.<name>`` spans."""
        window = self.window_ns()
        if window is None:
            return []
        lo, hi = window
        return sorted((s, s + d) for n, s, d in self.host
                      if n == PREFIX + name and s >= lo and s + d <= hi)

    def program_spans(self) -> list:
        """``(name, start_ns, end_ns)`` of every ``bcg.*`` span."""
        return [(n, s, s + d) for n, s, d in self.host if n.startswith(PREFIX)]

    # ------------------------------------------------------------ host clock

    def in_setup(self, names) -> Optional[list]:
        """Merged ``(t0, t1)`` seconds of the tracer's intervals of these
        names that ended by the end of the first round the process
        played, where ``setup_s`` ends: set-up's.  A union, because one
        interval may lie inside another (a traced function's inner
        jitted calls fire ``jax.trace`` events of their own)."""
        if self.events is None or self.setup_end is None:
            return None
        if self.evicted:
            raise RuntimeError(
                f"the tracer's ring dropped {self.evicted} event(s): set-up's spans "
                "are no longer whole (raise BCG_TPU_TRACE_RING in the metric's env)")
        return trace.union([(t0, t1) for n, t0, t1, _ in self.events
                            if n in names and t1 <= self.setup_end])


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def host_rows(xplane: str) -> list:
    """``bcg.*`` and ``bench.*`` events of an ``.xplane.pb``'s host
    planes."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(xplane).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((PREFIX, trace.SPAN_PREFIX)):
                    rows.append([e.name, float(e.start_ns), float(e.duration_ns)])
    return rows


@functools.lru_cache(maxsize=1)
def _trace_rows(trace_dir: str, xplane: str, _mtime: float) -> tuple:
    """``(host, device)`` rows of the trace under ``trace_dir``, parsed
    once for all of a run's readers; no device rows where the program
    mirrors nothing."""
    host = host_rows(xplane)
    if not any(n.startswith(PREFIX) for n, _s, _d in host):
        return host, []
    return host, [r for r in trace.load_events(trace_dir)
                  if trace.DEVICE_PLANE.match(r[0])]


def intervals(events: list, epoch: float) -> list:
    """The tracer's B/E pairs and X events as ``[name, t0, t1, args]`` on
    the host clock; a span's args are its B event's and its E event's."""
    out, open_spans = [], {}
    for ph, name, ts, _tid, span_id, _parent, args, dur in events:
        t = epoch + ts * 1e-6
        if ph == "B":
            open_spans[span_id] = (t, dict(args or {}))
        elif ph == "E" and span_id in open_spans:
            t0, begin_args = open_spans.pop(span_id)
            out.append([name, t0, t, dict(begin_args, **(args or {}))])
        elif ph == "X":
            out.append([name, t, t + dur * 1e-6, dict(args or {})])
    return out


def tracer_intervals() -> tuple:
    """``(intervals, evicted)`` of the program's tracer, through its
    public surface; ``(None, 0)`` where it is off, or has no public
    epoch to place its events by."""
    from bcg_tpu.obs import tracer as obs_tracer

    tracer = obs_tracer.get_tracer()
    if tracer is None or not hasattr(tracer, "epoch_perf_counter"):
        return None, 0
    return intervals(tracer.events(), tracer.epoch_perf_counter()), tracer.evicted()


def from_run(ctx: dict) -> Record:
    """The record of the run whose readers' ``ctx`` this is."""
    xplane = newest_xplane(TRACE_DIR)
    host, device = _trace_rows(TRACE_DIR, xplane, os.path.getmtime(xplane)) \
        if xplane else ([], [])
    events, evicted = tracer_intervals()
    return Record(host=host, device=device, events=events, evicted=evicted,
                  counters=ctx["counters"], setup_end=ctx["boot"].get("setup_end"))


def from_file(path: str) -> Record:
    with open(path) as f:
        return Record(**json.load(f))


SOURCE: Callable[[dict], Record] = from_run


def record(ctx: dict) -> Record:
    return SOURCE(ctx)


# ------------------------------------------------------- on a record's rows

def covered(spans: list, inner: list) -> float:
    """Nanoseconds of ``spans`` that ``inner`` spans cover."""
    merged = trace.union(inner)
    return sum(trace.total(trace.clip(merged, s, e)) for s, e in spans)


def innermost(spans: list, t: float) -> Optional[str]:
    """Name of the shortest ``(name, start, end)`` span holding ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best and best[0]


def idle_gaps(rec: Record) -> list:
    """The first device's idle ``(start_ns, end_ns)`` gaps inside the
    window, as ``lib.trace.reduce_events`` finds them."""
    lo, hi = rec.window_ns()
    first = min(r[0] for r in rec.device)
    busy = trace.clip(trace.union(
        [(s, s + d) for p, line, _n, s, d in rec.device
         if p == first and line == trace.OPS_LINE]), lo, hi)
    return trace.gaps(busy, lo, hi)


def program_time_inside(rec: Record, pattern: str, span: str) -> Optional[float]:
    """Share of the device time of the programs whose name matches
    ``pattern`` that falls inside ``bcg.<span>`` spans: the check that
    the program's spans and the device rows are on one clock."""
    import re

    rx = re.compile(pattern)
    runs = [(s, s + d) for _p, line, n, s, d in rec.device
            if line == trace.MODULES_LINE and rx.search(n)]
    if not runs:
        return None
    return covered(runs, rec.spans(span)) / trace.total(runs)
