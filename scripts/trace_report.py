#!/usr/bin/env python
"""Latency report from an exported Chrome trace (bcg_tpu.obs.tracer).

``python scripts/trace_report.py TRACE.json [--top N]``

Prints a per-span-name latency table (count / total / p50 / p95, sorted
hottest-first) rebuilt from the trace's B/E and X events, followed by
the top counters the exporter embedded under ``otherData.counters``
(compile/retrace accounting, serve linger buckets).  Self-contained —
no bcg_tpu import — so a trace copied off a TPU host can be read
anywhere; the in-process equivalent is ``tracer.summarize()``.

Note one deliberate asymmetry: ``summarize()`` covers the whole run
(its accumulator is not ring-evicted), while this report sees only the
events that survived the ``BCG_TPU_TRACE_RING`` window.  Unbalanced
events at the ring edge (a B whose E was evicted, or vice versa) are
dropped and counted in the footer rather than silently merged.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Tuple


def load_trace(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):  # bare event-array form is also legal
        return {"traceEvents": data, "otherData": {}}
    return data


def span_durations(events: List[dict]) -> Tuple[Dict[str, List[float]], int]:
    """{name: [duration_us, ...]} from B/E pairs (per-thread stacks) and
    X events; returns (durations, dropped_unbalanced)."""
    durations: Dict[str, List[float]] = defaultdict(list)
    stacks: Dict[int, List[dict]] = defaultdict(list)
    dropped = 0
    for ev in events:
        ph = ev.get("ph")
        if ph == "X":
            if "dur" in ev:
                durations[ev["name"]].append(float(ev["dur"]))
            continue
        if ph == "B":
            stacks[ev.get("tid", 0)].append(ev)
        elif ph == "E":
            stack = stacks[ev.get("tid", 0)]
            # Pop to the matching B (tolerate ring-evicted partners).
            while stack and stack[-1]["name"] != ev["name"]:
                stack.pop()
                dropped += 1
            if not stack:
                dropped += 1
                continue
            begin = stack.pop()
            durations[ev["name"]].append(
                float(ev["ts"]) - float(begin["ts"])
            )
    dropped += sum(len(s) for s in stacks.values())  # Bs without an E
    return durations, dropped


def _percentile(ordered: List[float], q: float) -> float:
    if not ordered:
        return 0.0
    idx = max(0, min(len(ordered) - 1, int(round(q * (len(ordered) - 1)))))
    return ordered[idx]


def render_report(trace: dict, top: int = 20) -> str:
    events = trace.get("traceEvents", [])
    durations, dropped = span_durations(
        [e for e in events if e.get("ph") in ("B", "E", "X")]
    )
    lines: List[str] = []
    rows = []
    for name, durs in durations.items():
        ordered = sorted(durs)
        total = sum(durs)
        rows.append((
            name, len(durs), total / 1e3,
            _percentile(ordered, 0.50) / 1e3,
            _percentile(ordered, 0.95) / 1e3,
        ))
    rows.sort(key=lambda r: -r[2])
    if rows:
        name_w = max(len("span"), max(len(r[0]) for r in rows))
        lines.append("== span latency (hottest first) ==")
        lines.append(
            f"{'span':<{name_w}}  {'count':>7}  {'total_ms':>10}  "
            f"{'p50_ms':>9}  {'p95_ms':>9}"
        )
        for name, count, total, p50, p95 in rows:
            lines.append(
                f"{name:<{name_w}}  {count:>7}  {total:>10.3f}  "
                f"{p50:>9.3f}  {p95:>9.3f}"
            )
    else:
        lines.append("== span latency: no spans in trace ==")
    if dropped:
        lines.append(
            f"(dropped {dropped} unbalanced event(s) at the ring edge)"
        )
    counters = (trace.get("otherData") or {}).get("counters") or {}
    # engine.hlo.*, hbm.*, engine.hostsync.*, and the compile-cost
    # families (engine.compile_ms.* histograms, engine.retrace_cause.*
    # taxonomy counters, engine.compile_obs.* cumulative totals) get
    # their own sections below, and so do histogram families (the flat
    # .bucket.le_* / .sum / .count entries) — ranked by raw value (op
    # counts, FLOPs, byte totals, cumulative bucket counts, per-span
    # sync tallies, millisecond totals) they would crowd every actual
    # event counter out of the top-N list.
    hist_names = histogram_families(counters)
    ranked = sorted(
        ((k, v) for k, v in counters.items()
         if not k.startswith(("engine.hlo.", "hbm.", "engine.hostsync.",
                              "engine.compile_ms.",
                              "engine.retrace_cause.",
                              "engine.compile_obs.", "alert."))
         and _histogram_owner(k, hist_names) is None),
        key=lambda kv: (-kv[1], kv[0]),
    )[:max(0, top)]
    if ranked:
        lines.append("")
        lines.append(f"== top counters (of {len(counters)}) ==")
        val_w = max(len(f"{v}") for _, v in ranked)
        for name, value in ranked:
            lines.append(f"{value:>{val_w}}  {name}")
    spec_line = spec_acceptance(counters)
    if spec_line:
        lines.append("")
        lines.append(spec_line)
    prefill_line = prefill_positions(counters)
    if prefill_line:
        lines.append("")
        lines.append(prefill_line)
    hist = histogram_table(counters, hist_names)
    if hist:
        lines.append("")
        lines.append(hist)
    hbm = hbm_ledger_section(counters)
    if hbm:
        lines.append("")
        lines.append(hbm)
    census = hlo_census_table(counters)
    if census:
        lines.append("")
        lines.append(census)
    fused = fused_sampler_section(counters)
    if fused:
        lines.append("")
        lines.append(fused)
    hostsync = hostsync_section(counters)
    if hostsync:
        lines.append("")
        lines.append(hostsync)
    compile_time = compile_time_section(counters)
    if compile_time:
        lines.append("")
        lines.append(compile_time)
    causes = retrace_cause_section(counters)
    if causes:
        lines.append("")
        lines.append(causes)
    alert_line = alerts_section(counters)
    if alert_line:
        lines.append("")
        lines.append(alert_line)
    return "\n".join(lines)


def histogram_families(counters: Dict[str, float]) -> List[str]:
    """Histogram base names reconstructed from the registry's flat form
    (``<name>.bucket.le_<bound>`` siblings of ``<name>.sum`` /
    ``<name>.count``), longest-first so nested prefixes resolve to the
    most specific owner."""
    names = {
        k.split(".bucket.le_", 1)[0]
        for k in counters if ".bucket.le_" in k
    }
    return sorted(names, key=len, reverse=True)


def _histogram_owner(key: str, families: List[str]) -> str:
    """The histogram family ``key`` belongs to, or None — used both to
    keep raw bucket/sum/count entries out of the ranked counter list and
    to rebuild per-family quantiles."""
    for name in families:
        if (key.startswith(name + ".bucket.le_")
                or key == name + ".sum" or key == name + ".count"):
            return name
    return None


def _parse_bound(label: str) -> float:
    """``le_`` label -> float bound (``25`` -> 25.0, ``2_5`` -> 2.5 —
    the registry's bound_label encoding, reimplemented here to keep the
    report bcg_tpu-import-free)."""
    return float(label.replace("_", "."))


def _quantile_from_cumulative(
    buckets: List[Tuple[float, float]], total: float, q: float
) -> float:
    """Prometheus histogram_quantile over cumulative (bound, count)
    pairs: linear interpolation inside the target bucket, clamped to the
    highest finite bound for overflow-bucket ranks."""
    if total <= 0:
        return 0.0
    target = q * total
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in buckets:
        if cum >= target and cum > prev_cum:
            frac = (target - prev_cum) / (cum - prev_cum)
            return prev_bound + (bound - prev_bound) * max(0.0, min(1.0, frac))
        prev_bound, prev_cum = bound, cum
    return buckets[-1][0] if buckets else 0.0


def histogram_table(counters: Dict[str, float],
                    families: List[str]) -> str:
    """Per-histogram quantile table (count / p50 / p95 / p99, bucket-
    interpolated) rebuilt from the flat registry entries, or '' when the
    export carries no histograms."""
    if not families:
        return ""
    rows = []
    for name in sorted(families):
        prefix = name + ".bucket.le_"
        buckets = sorted(
            (_parse_bound(k[len(prefix):]), v)
            for k, v in counters.items() if k.startswith(prefix)
        )
        total = counters.get(name + ".count", buckets[-1][1] if buckets else 0)
        rows.append((
            name, int(total),
            _quantile_from_cumulative(buckets, total, 0.50),
            _quantile_from_cumulative(buckets, total, 0.95),
            _quantile_from_cumulative(buckets, total, 0.99),
        ))
    name_w = max(len("histogram"), max(len(r[0]) for r in rows))
    lines = ["== histogram quantiles (bucket-interpolated) =="]
    lines.append(
        f"{'histogram':<{name_w}}  {'count':>7}  {'p50':>9}  "
        f"{'p95':>9}  {'p99':>9}"
    )
    for name, count, p50, p95, p99 in rows:
        lines.append(
            f"{name:<{name_w}}  {count:>7}  {p50:>9.3f}  "
            f"{p95:>9.3f}  {p99:>9.3f}"
        )
    return "\n".join(lines)


def hbm_ledger_section(counters: Dict[str, float]) -> str:
    """Compact hbm.* gauge listing (bcg_tpu/obs/ledger.py accounts), or
    '' when the export carries none."""
    rows = sorted(
        (k, v) for k, v in counters.items() if k.startswith("hbm.")
    )
    if not rows:
        return ""
    name_w = max(len(k) for k, _ in rows)
    lines = ["== hbm ledger gauges =="]
    for name, value in rows:
        lines.append(f"{name:<{name_w}}  {value:>16.0f}")
    return "\n".join(lines)


def hlo_census_table(counters: Dict[str, float]) -> str:
    """Per-jit-entry kernel-census table rebuilt from the exported
    ``engine.hlo.<entry>.<metric>`` gauges (bcg_tpu/obs/hlo.py), or ''
    when the export carries none.  Kept bcg_tpu-import-free like the
    rest of this report: the gauge names alone define the schema."""
    rows: Dict[str, Dict[str, float]] = {}
    for name, value in counters.items():
        if not name.startswith("engine.hlo."):
            continue
        rest = name[len("engine.hlo."):]
        entry, _, metric = rest.rpartition(".")
        if entry:
            rows.setdefault(entry, {})[metric] = value
    if not rows:
        return ""
    cols = ("fusions", "custom_calls", "collectives", "step_ops",
            "step_fusions", "total_ops", "flops", "bytes_accessed")
    name_w = max(len("jit entry"), max(len(e) for e in rows))
    lines = ["== hlo kernel census (engine.hlo.* gauges) =="]
    lines.append(
        f"{'jit entry':<{name_w}}  " + "  ".join(f"{c:>14}" for c in cols)
    )
    for entry in sorted(rows):
        vals = []
        for c in cols:
            v = rows[entry].get(c)
            vals.append("-" if v is None else f"{v:.0f}")
        lines.append(
            f"{entry:<{name_w}}  " + "  ".join(f"{v:>14}" for v in vals)
        )
    return "\n".join(lines)


def fused_sampler_section(counters: Dict[str, float]) -> str:
    """Per-decode-loop-family fused-sampler comparison rebuilt from the
    TPU cross-lowering twin gauges (``engine.hlo.tpu_<family>.*`` vs
    ``engine.hlo.tpu_fused_<family>.*``): one step-custom-call line per
    family showing the per-decode-step op count moving DOWN under the
    fused kernel — the at-a-glance form of the census acceptance
    inequality; '' when the export carries no twin pair."""
    prefix = "engine.hlo.tpu_fused_"
    families = sorted({
        name[len(prefix):].split(".")[0]
        for name in counters if name.startswith(prefix)
    })
    rows = []
    for fam in families:
        xla_ops = counters.get(f"engine.hlo.tpu_{fam}.step_ops")
        fused_ops = counters.get(f"{prefix}{fam}.step_ops")
        if xla_ops is None or fused_ops is None:
            continue
        cc = counters.get(f"{prefix}{fam}.step_custom_calls", 0)
        rows.append((fam, xla_ops, fused_ops, cc))
    if not rows:
        return ""
    name_w = max(len("decode-loop family"), max(len(r[0]) for r in rows))
    lines = ["== fused guided sampler (TPU cross-lowering twins) =="]
    lines.append(
        f"{'decode-loop family':<{name_w}}  {'step_ops xla':>12}  "
        f"{'step_ops fused':>14}  {'step custom-calls':>17}"
    )
    for fam, xla_ops, fused_ops, cc in rows:
        lines.append(
            f"{fam:<{name_w}}  {xla_ops:>12.0f}  {fused_ops:>14.0f}  "
            f"{cc:>17.0f}"
        )
    return "\n".join(lines)


def hostsync_section(counters: Dict[str, float]) -> str:
    """Host-syncs-by-span attribution table rebuilt from the exported
    ``engine.hostsync.span.*`` counters (bcg_tpu/obs/hostsync.py), with
    a totals footer (attributed/total coverage), or '' when the export
    carries no audit.  Kept bcg_tpu-import-free like the rest of this
    report: the counter names alone define the schema."""
    prefix = "engine.hostsync.span."
    rows = sorted(
        ((k[len(prefix):], v) for k, v in counters.items()
         if k.startswith(prefix)),
        key=lambda kv: (-kv[1], kv[0]),
    )
    total = counters.get("engine.hostsync.total", 0)
    if not rows and not total:
        return ""
    lines = ["== host syncs by span (engine.hostsync.*) =="]
    if rows:
        name_w = max(len("span"), max(len(r[0]) for r in rows))
        lines.append(f"{'span':<{name_w}}  {'syncs':>8}")
        for name, value in rows:
            lines.append(f"{name:<{name_w}}  {value:>8.0f}")
    attributed = counters.get("engine.hostsync.attributed", 0)
    coverage = f" ({100.0 * attributed / total:.1f}% attributed)" if total else ""
    lines.append(
        f"total {total:.0f} sync(s), {attributed:.0f} attributed{coverage}"
    )
    return "\n".join(lines)


def compile_time_section(counters: Dict[str, float]) -> str:
    """'compile time by entry' table rebuilt from the exported
    ``engine.compile_ms.<entry>`` histogram flats plus the
    ``engine.compile.<entry>`` / ``engine.retrace.<entry>`` counters
    (bcg_tpu/obs/compile.py), hottest first by total ms, or '' when the
    export carries no compile observability.  Kept bcg_tpu-import-free
    like the rest of this report: the counter names alone define the
    schema (``scripts/compile_report.py`` is the standalone form)."""
    prefix = "engine.compile_ms."
    rows: Dict[str, Dict[str, float]] = {}
    for name, value in counters.items():
        if not name.startswith(prefix):
            continue
        rest = name[len(prefix):]
        if rest.endswith(".sum"):
            rows.setdefault(rest[:-len(".sum")], {})["total_ms"] = value
        elif rest.endswith(".count"):
            rows.setdefault(rest[:-len(".count")], {})["count"] = value
    if not rows:
        return ""
    name_w = max(len("jit entry"), max(len(e) for e in rows))
    lines = ["== compile time by entry (engine.compile_ms.*) =="]
    lines.append(
        f"{'jit entry':<{name_w}}  {'compiles':>8}  {'retraces':>8}  "
        f"{'timed':>6}  {'total_ms':>10}"
    )
    for entry, row in sorted(rows.items(),
                             key=lambda kv: -kv[1].get("total_ms", 0.0)):
        compiles = counters.get(f"engine.compile.{entry}", 0)
        retraces = counters.get(f"engine.retrace.{entry}", 0)
        lines.append(
            f"{entry:<{name_w}}  {compiles:>8.0f}  {retraces:>8.0f}  "
            f"{row.get('count', 0):>6.0f}  {row.get('total_ms', 0.0):>10.1f}"
        )
    first = counters.get("engine.compile_obs.first_compile_ms", 0)
    retrace_ms = counters.get("engine.compile_obs.retrace_ms", 0)
    aot = counters.get("engine.compile_obs.aot_ms", 0)
    lines.append(
        f"cumulative: {first:.1f} ms first-compile, {retrace_ms:.1f} ms "
        f"retrace, {aot:.1f} ms census-AOT; "
        f"{counters.get('engine.compile_obs.cache_entries', 0):.0f} "
        "trace-cache entries"
    )
    return "\n".join(lines)


def retrace_cause_section(counters: Dict[str, float]) -> str:
    """'retraces by cause' table from the exported
    ``engine.retrace_cause.<kind>`` taxonomy counters, or '' when the
    export carries none."""
    prefix = "engine.retrace_cause."
    rows = sorted(
        ((k[len(prefix):], v) for k, v in counters.items()
         if k.startswith(prefix)),
        key=lambda kv: (-kv[1], kv[0]),
    )
    if not rows:
        return ""
    name_w = max(len("cause"), max(len(r[0]) for r in rows))
    lines = ["== retraces by cause (engine.retrace_cause.*) =="]
    lines.append(f"{'cause':<{name_w}}  {'retraces':>8}")
    for name, value in rows:
        lines.append(f"{name:<{name_w}}  {value:>8.0f}")
    return "\n".join(lines)


def spec_acceptance(counters: Dict[str, float]) -> str:
    """One-line draft acceptance summary when the export carries
    speculative-decoding counters (engine.spec.*); '' otherwise."""
    drafted = counters.get("engine.spec.drafted")
    if not drafted:
        return ""
    accepted = counters.get("engine.spec.accepted", 0)
    return (
        f"== speculative decoding: {accepted}/{drafted} draft tokens "
        f"accepted ({100.0 * accepted / drafted:.1f}%) =="
    )


def prefill_positions(counters: Dict[str, float]) -> str:
    """One-line real-vs-padded prefill position summary
    (engine.prefill.positions_*); '' when the export carries neither.
    Real positions are actual prompt-token work — prefix-cache savings
    show up here without pad noise; the padded total is the windows'
    size.  Where the export carries ``positions_run`` (what went through
    the model: the padded total less the all-pad chunks a chunked
    prefill passed over) it is what the FLOP bill sees, and the share is
    of it."""
    padded = counters.get("engine.prefill.positions_padded")
    if not padded:
        return ""
    real = counters.get("engine.prefill.positions_real", 0)
    run = counters.get("engine.prefill.positions_run")
    if not run:
        return (
            f"== prefill positions: {int(real)} real / {int(padded)} padded "
            f"({100.0 * real / padded:.1f}% real work) =="
        )
    return (
        f"== prefill positions: {int(real)} real / {int(run)} run / "
        f"{int(padded)} padded ({100.0 * real / run:.1f}% real work, "
        f"{100.0 * (1 - run / padded):.1f}% of the window skipped) =="
    )


def alerts_section(counters: Dict[str, float]) -> str:
    """One-line alert-plane summary when the export carries alert.*
    transition counters (BCG_TPU_ALERTS); '' otherwise.  The alert.*
    family is excluded from the ranked top-counter list above — its
    evaluation counter grows once per cycle and would crowd real event
    counters out — so this line is where the plane surfaces."""
    evaluations = counters.get("alert.evaluations")
    if not evaluations:
        return ""
    fired = int(counters.get("alert.fired", 0))
    resolved = int(counters.get("alert.resolved", 0))
    flaps = int(counters.get("alert.flaps", 0))
    firing = sorted(
        k[len("alert.firing."):] for k, v in counters.items()
        if k.startswith("alert.firing.") and v
    )
    line = (
        f"== alerts: {fired} fired / {resolved} resolved over "
        f"{int(evaluations)} evaluation(s), {flaps} flap(s)"
    )
    line += f"; firing: {', '.join(firing)} ==" if firing else " =="
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Latency table + top counters from a bcg_tpu Chrome "
        "trace export (BCG_TPU_TRACE_OUT / tracer.export())."
    )
    parser.add_argument("trace", help="path to the exported trace JSON")
    parser.add_argument("--top", type=int, default=20,
                        help="counters to show (default 20)")
    args = parser.parse_args(argv)
    try:
        trace = load_trace(args.trace)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"trace_report: cannot read {args.trace}: {exc}",
              file=sys.stderr)
        return 1
    print(render_report(trace, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
