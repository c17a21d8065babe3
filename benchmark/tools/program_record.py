#!/usr/bin/env python3
"""What the program's own spans say of the last traced run of this
checkout, read off the chip once the run has ended: the shared-clock
check, the inside-against-outside comparison, and a small record of the
run for this directory's tests.

    BCG_TPU_TRACE_OUT=chiprun_out/tracer.json python3 benchmark/run.py ... --trace 1
    python3 benchmark/tools/program_record.py chiprun_out/tracer.json \
        [--dump chiprun_out/program_record.json] [--rows chiprun_out/trace_events.json]

The run leaves its ``.xplane.pb`` under ``benchmark/.trace``; the
tracer's own events (set-up's spans, ``jax.trace`` / ``jax.lower`` /
``jax.compile``, counters) are in the export that ``BCG_TPU_TRACE_OUT``
makes the program write when it exits.  ``--dump`` keeps the record
small: of the device's operations only the merged busy intervals, of the
tracer's intervals only those of a millisecond and more (a shorter one
inside a longer one adds nothing to a union).  ``--rows`` keeps a small
trace for ``tests/data/trace_events.json``: the harness's spans, the
programs, and the window's first ``ROWS_KEPT`` device operations.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))

from lib import program_spans, trace  # noqa: E402


def from_export(path: str) -> program_spans.Record:
    """The record of the run that wrote the tracer export at ``path``
    and the trace under ``benchmark/.trace``."""
    with open(path) as f:
        data = json.load(f)
    other = data["otherData"]
    events = [(e["ph"], e["name"], e["ts"], e["tid"], e["args"]["span_id"],
               e["args"].get("parent_id"),
               {k: v for k, v in e["args"].items() if k not in ("span_id", "parent_id")},
               e.get("dur"))
              for e in data["traceEvents"] if e["ph"] in "BEX"]
    intervals = program_spans.intervals(events, other["epoch_perf_counter"])
    host = program_spans.host_rows(program_spans.newest_xplane(program_spans.TRACE_DIR))
    device = [r for r in trace.load_events(program_spans.TRACE_DIR)
              if trace.DEVICE_PLANE.match(r[0])]
    # Set-up ends with the first round the process played.
    rounds = sorted(t1 for n, _t0, t1, _a in intervals if n == "round")
    return program_spans.Record(
        host=host, device=device, events=intervals,
        evicted=other["evicted_events"], counters=other["counters"],
        setup_end=rounds[0] if rounds else None)


def thin(rec: program_spans.Record) -> dict:
    first = min(r[0] for r in rec.device)
    ops = trace.union([(s, s + d) for p, line, _n, s, d in rec.device
                       if p == first and line == trace.OPS_LINE])
    device = [r for r in rec.device if r[0] == first and r[1] == trace.MODULES_LINE]
    device += [[first, trace.OPS_LINE, "busy", s, e - s] for s, e in ops]
    keep = ("engine.decode.", "engine.prefill.", "engine.hostsync.total", "game.retry.")
    return {
        "host": rec.host, "device": sorted(device, key=lambda r: r[3]),
        "events": [e for e in rec.events if e[2] - e[1] >= 1e-3],
        "evicted": rec.evicted,
        "counters": {k: v for k, v in rec.counters.items() if k.startswith(keep)},
        "setup_end": rec.setup_end,
    }


ROWS_KEPT = 3000


def small_trace(rows: list) -> list:
    """Rows as ``lib.trace.load_events`` gives them, cut to a size a
    test can hold."""
    t0 = min(s for _p, _l, n, s, _d in rows if n == trace.ROUND_SPAN)
    ops = sorted((r for r in rows if r[1] == trace.OPS_LINE and r[3] >= t0),
                 key=lambda r: r[3])[:ROWS_KEPT]
    return [r for r in rows if r[1] != trace.OPS_LINE] + ops


def mean_s(spans: list) -> float:
    return sum(e - s for s, e in spans) * program_spans.NS / max(1, len(spans))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("export")
    ap.add_argument("--dump")
    ap.add_argument("--rows")
    ap.add_argument("--config", default="benchmark/configs/qwen3-8b-int8.json")
    args = ap.parse_args()
    rec = from_export(args.export)
    with open(args.config) as f:
        names = json.load(f)["trace_names"]
    out = {
        "prefill_program_inside_engine_prefill": program_spans.program_time_inside(
            rec, names["prefill_program"], "engine.prefill"),
        "decode_program_inside_engine_decode": program_spans.program_time_inside(
            rec, names["decode_program"], "engine.decode"),
        "span_mean_s": {n: mean_s(rec.spans(n)) for n in (
            "round", "engine.call", "engine.guides", "engine.prefill",
            "engine.tokenize", "engine.decode", "engine.detokenize")},
        "span_count": {n: len(rec.spans(n)) for n in ("round", "engine.call")},
        "tracer_events": len(rec.events), "evicted": rec.evicted,
        "program_s": {},
        "setup_s_by_name": {},
    }
    for _p, line, n, _s, d in rec.device:
        if line == trace.MODULES_LINE:
            name = n.split("(")[0]
            out["program_s"][name] = out["program_s"].get(name, 0.0) + d * program_spans.NS
    by_name: dict = {}
    for n, t0, t1, _a in rec.events:
        if t1 <= rec.setup_end:
            by_name.setdefault(n, []).append((t0, t1))
    out["setup_s_by_name"] = {n: [len(v), trace.total(trace.union(v))]
                              for n, v in sorted(by_name.items())}
    print(json.dumps(out, indent=1))
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump(thin(rec), f, separators=(",", ":"))
    if args.rows:
        with open(args.rows, "w") as f:
            json.dump(small_trace(trace.load_events(program_spans.TRACE_DIR)), f,
                      separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
