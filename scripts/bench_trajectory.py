#!/usr/bin/env python
"""Cross-run bench trajectory: outages vs regressions, per-metric
trends vs best-known-good.

``python scripts/bench_trajectory.py BENCH_r*.json [--threshold 0.7]``
``python scripts/bench_trajectory.py <dir>``  (globs BENCH_r*.json)

The cautionary tale this script exists for: three runs that found no
accelerator once recorded ``value: 0.0`` / ``vs_baseline: 0.0`` with
rc=0 and read as catastrophic regressions until a human noticed the
``error`` field (those records are gone from the tree; ``bench.py`` now
exits non-zero instead of printing such a line).  This script makes the
distinction mechanical for whatever records remain:

* **outage** — the run measured NOTHING: no parsed payload (driver
  crash, rc != 0 with an empty ``parsed``), an ``error`` field, or a
  null ``vs_baseline`` (the post-PR-6 outage marker).  Outages are
  REPORTED and EXCLUDED from regression analysis — an outage is not a
  0%-of-baseline measurement.
* **measured** — a real number.  The newest measured run is compared
  against the best-known-good (the max over every EARLIER measured
  run) per metric; a drop below ``--threshold`` (default 0.7) of
  best-known-good is a REGRESSION: named per metric on stderr, exit
  status 2 (pipefail-composable, the perf_gate contract).

Accepted file shape: the driver record ``{n, cmd, rc, tail, parsed}``
with the bench payload in ``parsed``, or a bare bench JSON (the
``parsed`` payload itself).  Runs order by the driver round number
``n`` when present, else by filename.

Self-contained — no bcg_tpu import — so a results directory copied off
a TPU host can be analyzed anywhere.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

# Metrics trended when present: (label, extractor) over the parsed
# payload.  `value` (decisions/sec) is the primary regression metric;
# the others trend informationally (vs_baseline moves with the
# denominator model class, so it trends but never gates alone).
TREND_METRICS = (
    ("decisions_per_sec", lambda p: p.get("value")),
    ("vs_baseline", lambda p: p.get("vs_baseline")),
    ("rounds_per_sec", lambda p: (p.get("extra") or {}).get("rounds_per_sec")),
    ("prefill_mfu", lambda p: (p.get("extra") or {}).get("prefill_mfu")),
    ("decode_gbps", lambda p: (p.get("extra") or {}).get("decode_gbps")),
)
PRIMARY_METRIC = "decisions_per_sec"


class Run:
    """One bench record: identity, classification, metric values."""

    __slots__ = ("label", "order", "rc", "status", "note", "metrics")

    def __init__(self, label: str, order, rc, status: str, note: str,
                 metrics: Dict[str, float]):
        self.label = label
        self.order = order
        self.rc = rc
        self.status = status  # "measured" | "outage"
        self.note = note
        self.metrics = metrics


def classify(parsed: Optional[dict], rc) -> Tuple[str, str]:
    """(status, note) for one run's parsed payload.

    Outage detection is deliberately belt-and-braces: early records
    predate the null-``vs_baseline`` convention (they carry
    ``vs_baseline: 0.0`` WITH an error field), so an ``error`` field
    alone is already an outage; a null ``vs_baseline`` is the later
    marker; an empty payload is a driver crash."""
    if not parsed:
        return "outage", (
            f"no parsed payload (driver rc={rc}) — run crashed before "
            "reporting"
        )
    error = parsed.get("error")
    if error:
        return "outage", str(error)[:120]
    if parsed.get("vs_baseline") is None:
        return "outage", "null vs_baseline — run measured nothing"
    value = parsed.get("value")
    if not isinstance(value, (int, float)) or value <= 0:
        return "outage", f"non-positive value {value!r} without an error field"
    return "measured", ""


def load_run(path: str) -> Run:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and "parsed" in data:
        parsed = data.get("parsed") or {}
        rc = data.get("rc")
        order = data.get("n")
    else:  # bare bench payload
        parsed = data if isinstance(data, dict) else {}
        rc = None
        order = None
    status, note = classify(parsed, rc)
    metrics: Dict[str, float] = {}
    if status == "measured":
        for name, extract in TREND_METRICS:
            value = extract(parsed)
            if isinstance(value, (int, float)):
                metrics[name] = float(value)
    label = os.path.splitext(os.path.basename(path))[0]
    return Run(label, order, rc, status, note, metrics)


def order_runs(runs: List[Run]) -> List[Run]:
    """Driver round number when every run has one, else filename."""
    if all(r.order is not None for r in runs):
        return sorted(runs, key=lambda r: (r.order, r.label))
    return sorted(runs, key=lambda r: r.label)


def find_regressions(runs: List[Run], threshold: float) -> List[str]:
    """The newest MEASURED run's metrics vs best-known-good over every
    earlier measured run; one finding per metric below threshold.
    Fewer than two measured runs ⇒ nothing to compare (outages never
    count as evidence either way)."""
    measured = [r for r in runs if r.status == "measured"]
    if len(measured) < 2:
        return []
    latest = measured[-1]
    earlier = measured[:-1]
    # Only the primary metric gates; the other TREND_METRICS trend
    # informationally (vs_baseline moves with the denominator model
    # class, MFU/GB/s only exist on real backends).
    name = PRIMARY_METRIC
    best = max(
        (r.metrics[name] for r in earlier if name in r.metrics),
        default=None,
    )
    got = latest.metrics.get(name)
    if best is None or got is None or best <= 0:
        return []
    if got >= threshold * best:
        return []
    return [
        f"{name}: {latest.label} measured {got:.4g}, "
        f"best-known-good {best:.4g} "
        f"({100.0 * got / best:.1f}% < {100.0 * threshold:.0f}% "
        "threshold)"
    ]


def render_report(runs: List[Run], threshold: float) -> str:
    lines: List[str] = []
    label_w = max(len("run"), max(len(r.label) for r in runs))
    lines.append("== bench trajectory ==")
    lines.append(
        f"{'run':<{label_w}}  {'status':<8}  {'dec/s':>9}  "
        f"{'vs_base':>8}  note"
    )
    for r in runs:
        dec = r.metrics.get("decisions_per_sec")
        vsb = r.metrics.get("vs_baseline")
        lines.append(
            f"{r.label:<{label_w}}  {r.status:<8}  "
            f"{(f'{dec:.3f}' if dec is not None else '-'):>9}  "
            f"{(f'{vsb:.3f}' if vsb is not None else 'null'):>8}  "
            f"{r.note}"
        )
    measured = [r for r in runs if r.status == "measured"]
    outages = [r for r in runs if r.status == "outage"]
    lines.append("")
    lines.append(
        f"{len(measured)} measured, {len(outages)} outage(s)"
        + (f" ({', '.join(r.label for r in outages)}) — excluded from "
           "regression analysis" if outages else "")
    )
    # Per-metric trend tables over measured runs only.
    for name, _ in TREND_METRICS:
        rows = [(r.label, r.metrics[name]) for r in measured
                if name in r.metrics]
        if not rows:
            continue
        best = max(v for _, v in rows)
        lines.append("")
        lines.append(f"-- {name} (best-known-good {best:.4g}) --")
        for label, value in rows:
            pct = 100.0 * value / best if best else 0.0
            lines.append(f"  {label:<{label_w}}  {value:>10.4g}  "
                         f"{pct:>6.1f}% of best")
    findings = find_regressions(runs, threshold)
    if findings:
        lines.append("")
        for f in findings:
            lines.append(f"REGRESSION: {f}")
    return "\n".join(lines)


def collect_paths(args: List[str]) -> List[str]:
    paths: List[str] = []
    for arg in args:
        if os.path.isdir(arg):
            paths.extend(sorted(glob.glob(os.path.join(arg, "BENCH_r*.json"))))
        else:
            paths.append(arg)
    return paths


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Merge BENCH_r*.json records into per-metric trend "
        "tables; outages (null vs_baseline / error payloads) are "
        "reported, never counted as regressions."
    )
    parser.add_argument("paths", nargs="+",
                        help="bench JSON files, or a directory to glob "
                        "BENCH_r*.json from")
    parser.add_argument("--threshold", type=float, default=0.7,
                        help="regression threshold as a fraction of "
                        "best-known-good (default 0.7)")
    parser.add_argument("--alert-out", metavar="PATH",
                        help="also append each regression as an "
                        "alert-shaped JSONL record (the "
                        "BCG_TPU_ALERT_EVENTS sink schema) so "
                        "cross-run perf regressions merge into one "
                        "scripts/alert_report.py timeline with "
                        "runtime alerts")
    args = parser.parse_args(argv)
    paths = collect_paths(args.paths)
    if not paths:
        print("bench_trajectory: no bench files found", file=sys.stderr)
        return 1
    runs = []
    for path in paths:
        try:
            runs.append(load_run(path))
        except (OSError, json.JSONDecodeError) as exc:
            print(f"bench_trajectory: cannot read {path}: {exc}",
                  file=sys.stderr)
            return 1
    runs = order_runs(runs)
    print(render_report(runs, args.threshold))
    findings = find_regressions(runs, args.threshold)
    for f in findings:
        print(f"BENCH REGRESSION: {f}", file=sys.stderr)
    if findings and args.alert_out:
        try:
            write_alert_records(args.alert_out, findings)
        except OSError as exc:
            print(f"bench_trajectory: cannot write {args.alert_out}: "
                  f"{exc}", file=sys.stderr)
    return 2 if findings else 0


def write_alert_records(path: str, findings: List[str]) -> None:
    """Append the rc-2 verdict in the BCG_TPU_ALERT_EVENTS sink shape
    (manifest header + one firing record per regression) — hand-rolled
    by value, NOT imported from bcg_tpu.obs.export: this script stays
    import-free so it runs on a laptop against scp'd files.  No
    resolved record is ever written: a cross-run perf regression stays
    firing on the alert_report timeline until a newer trajectory run
    clears it (by simply not re-emitting)."""
    now = time.time()
    with open(path, "a", encoding="utf-8") as fh:
        fh.write(json.dumps({
            "ts": now, "event": "manifest", "schema_version": 1,
            "run_id": "bench-trajectory", "kind": "bench",
        }) + "\n")
        for f in findings:
            fh.write(json.dumps({
                "ts": now, "event": "alert", "rule": "bench_regression",
                "severity": "page", "state": "firing", "kind": "trend",
                "value": None, "summary": f,
            }) + "\n")


if __name__ == "__main__":
    sys.exit(main())
