"""Tier-1 smoke for scripts/trace_report.py: a tiny traced FakeEngine
game exports a Chrome trace, and the report CLI renders a non-empty
latency table + counters from it (ISSUE-4 CI satellite)."""

import json
import os
import subprocess
import sys

import pytest

from bcg_tpu.api import run_simulation
from bcg_tpu.engine.fake import FakeEngine
from bcg_tpu.obs import counters as obs_counters, tracer as obs_tracer
from bcg_tpu.serve.engine import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "trace_report.py")


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("BCG_TPU_TRACE", "1")
    monkeypatch.delenv("BCG_TPU_TRACE_OUT", raising=False)
    obs_tracer.reset()
    yield obs_tracer.get_tracer()
    obs_tracer.reset()


def test_report_renders_traced_game(traced, tmp_path):
    before = obs_counters.snapshot()
    serving = ServingEngine(FakeEngine(seed=0, policy="stubborn"),
                            linger_ms=1)
    out = run_simulation(n_agents=3, byzantine_count=0, max_rounds=2,
                         backend="fake", seed=0, engine=serving)
    serving.shutdown()
    assert out["metrics"]["total_rounds"] == 2
    trace_path = tmp_path / "game_trace.json"
    # The registry is the process's: in a worker that ran other files
    # first, their counters fill the report's ranked top-N list and
    # push this game's out of it.  The report is of this game: give it
    # what this game counted.
    data = traced.export()
    data["otherData"]["counters"] = obs_counters.delta(before)
    trace_path.write_text(json.dumps(data))

    proc = subprocess.run(
        [sys.executable, SCRIPT, str(trace_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip(), "report rendered empty"
    # The latency table names the game's spans with real statistics...
    for name in ("round", "decide", "serve.device", "engine.decode"):
        assert name in proc.stdout, f"{name!r} missing from report"
    assert "p50_ms" in proc.stdout and "p95_ms" in proc.stdout
    # ... and the counters section surfaces the serve accounting.
    assert "top counters" in proc.stdout
    assert "serve.requests" in proc.stdout


def test_report_derives_spec_acceptance(tmp_path):
    """engine.spec.* counters in an export turn into a one-line draft
    acceptance rate (and the line is absent without them)."""
    trace = {
        "traceEvents": [],
        "otherData": {"counters": {
            "engine.spec.drafted": 80,
            "engine.spec.accepted": 60,
            "engine.spec.rejected": 20,
        }},
    }
    path = tmp_path / "spec_trace.json"
    path.write_text(json.dumps(trace))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "60/80 draft tokens accepted (75.0%)" in proc.stdout
    # No spec counters -> no spec line.
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"traceEvents": [], "otherData": {}}))
    proc2 = subprocess.run(
        [sys.executable, SCRIPT, str(bare)],
        capture_output=True, text=True, timeout=60,
    )
    assert "speculative" not in proc2.stdout


def test_report_renders_hlo_census_table(tmp_path):
    """engine.hlo.* gauges in an export render as the per-jit-entry
    kernel-census table — still with no bcg_tpu import (the report must
    read a trace copied off a TPU host anywhere)."""
    trace = {
        "traceEvents": [],
        "otherData": {"counters": {
            "engine.hlo.decode_loop.fusions": 114,
            "engine.hlo.decode_loop.custom_calls": 0,
            "engine.hlo.decode_loop.collectives": 0,
            "engine.hlo.decode_loop.step_ops": 297,
            "engine.hlo.decode_loop.step_fusions": 77,
            "engine.hlo.decode_loop.total_ops": 443,
            "engine.hlo.decode_loop.flops": 1750287.0,
            "engine.hlo.decode_loop.bytes_accessed": 4306799.0,
            "engine.hlo.prefill.fusions": 29,
            "engine.hlo.prefill.total_ops": 130,
            "hbm.params_bytes": 1650000000,
            "hbm.total_bytes": 1650000000,
            "serve.requests": 12,
        }},
    }
    path = tmp_path / "census_trace.json"
    path.write_text(json.dumps(trace))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "hlo kernel census" in proc.stdout
    assert "decode_loop" in proc.stdout and "prefill" in proc.stdout
    # hbm gauges get their own section AND stay out of the ranked
    # top-counter list (their byte values would crowd event counters
    # out — serve.requests must survive at the top).
    assert "hbm ledger gauges" in proc.stdout
    assert "hbm.params_bytes" in proc.stdout
    top_section = proc.stdout.split("top counters")[1].split("\n==")[0]
    assert "serve.requests" in top_section
    assert "hbm.params_bytes" not in top_section
    assert "engine.hlo" not in top_section
    # Row values land under their columns (spot-check the step family).
    row = [l for l in proc.stdout.splitlines() if l.startswith("decode_loop")][0]
    assert "297" in row and "77" in row and "114" in row
    # The script itself stays dependency-free.
    src = open(SCRIPT).read()
    assert "import bcg_tpu" not in src and "from bcg_tpu" not in src
    # No census gauges -> no census section.
    bare = tmp_path / "bare2.json"
    bare.write_text(json.dumps({"traceEvents": [], "otherData": {}}))
    proc2 = subprocess.run(
        [sys.executable, SCRIPT, str(bare)],
        capture_output=True, text=True, timeout=60,
    )
    assert "hlo kernel census" not in proc2.stdout


def test_report_renders_histogram_quantile_table(tmp_path):
    """Flat registry-histogram entries (.bucket.le_* / .sum / .count)
    render as a per-family p50/p95/p99 table AND stay out of the ranked
    top-counter list (the hlo/hbm crowding fix applied to histograms)."""
    trace = {
        "traceEvents": [],
        "otherData": {"counters": {
            "serve.e2e_ms.bucket.le_5": 2,
            "serve.e2e_ms.bucket.le_10": 6,
            "serve.e2e_ms.bucket.le_25": 8,
            "serve.e2e_ms.sum": 90.0,
            "serve.e2e_ms.count": 8,
            "game.round_ms.bucket.le_50": 3,
            "game.round_ms.bucket.le_2_5": 1,   # non-integer bound label
            "game.round_ms.sum": 61.0,
            "game.round_ms.count": 4,
            "serve.requests": 12,
        }},
    }
    path = tmp_path / "hist_trace.json"
    path.write_text(json.dumps(trace))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "histogram quantiles" in proc.stdout
    rows = {
        l.split()[0]: l for l in proc.stdout.splitlines()
        if l.startswith(("serve.e2e_ms", "game.round_ms"))
    }
    assert set(rows) == {"serve.e2e_ms", "game.round_ms"}
    # serve.e2e_ms: count 8; median rank 4 lands in the (5,10] bucket.
    e2e = rows["serve.e2e_ms"].split()
    assert e2e[1] == "8"
    assert 5.0 < float(e2e[2]) <= 10.0
    # Raw bucket/sum/count entries never reach the ranked counter list.
    top_section = proc.stdout.split("top counters")[1].split("\n==")[0]
    assert "serve.requests" in top_section
    assert ".bucket.le_" not in top_section
    assert "serve.e2e_ms.count" not in top_section
    assert "game.round_ms.sum" not in top_section
    # No histograms -> no table.
    bare = tmp_path / "bare3.json"
    bare.write_text(json.dumps(
        {"traceEvents": [], "otherData": {"counters": {"serve.requests": 1}}}
    ))
    proc2 = subprocess.run(
        [sys.executable, SCRIPT, str(bare)],
        capture_output=True, text=True, timeout=60,
    )
    assert "histogram quantiles" not in proc2.stdout


def test_report_renders_hostsync_attribution_table(tmp_path):
    """engine.hostsync.* counters in an export render as the
    host-syncs-by-span attribution table with a coverage footer, AND
    stay out of the ranked top-counter list (the hlo/hbm crowding fix
    applied to the audit namespace) — still with no bcg_tpu import."""
    trace = {
        "traceEvents": [],
        "otherData": {"counters": {
            "engine.hostsync.total": 12,
            "engine.hostsync.attributed": 11,
            "engine.hostsync.unattributed": 1,
            "engine.hostsync.span.engine_decode": 6,
            "engine.hostsync.span.jit_decode_loop": 4,
            "engine.hostsync.span.engine_prefill": 1,
            "engine.hostsync.span.unattributed": 1,
            "engine.hostsync.site.decode_readback": 6,
            "engine.hostsync.site.prefill_barrier": 6,
            "serve.requests": 3,
        }},
    }
    path = tmp_path / "hostsync_trace.json"
    path.write_text(json.dumps(trace))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "host syncs by span" in proc.stdout
    # Hottest attribution first; coverage footer derived from totals.
    section = proc.stdout.split("host syncs by span")[1]
    assert section.index("engine_decode") < section.index("jit_decode_loop")
    assert "total 12 sync(s), 11 attributed (91.7% attributed)" in section
    # The audit namespace never crowds the ranked counter list.
    top_section = proc.stdout.split("top counters")[1].split("\n==")[0]
    assert "serve.requests" in top_section
    assert "engine.hostsync" not in top_section
    # No audit counters -> no section.
    bare = tmp_path / "bare4.json"
    bare.write_text(json.dumps(
        {"traceEvents": [], "otherData": {"counters": {"serve.requests": 1}}}
    ))
    proc2 = subprocess.run(
        [sys.executable, SCRIPT, str(bare)],
        capture_output=True, text=True, timeout=60,
    )
    assert "host syncs by span" not in proc2.stdout


def test_report_renders_compile_cost_tables(tmp_path):
    """The compile-cost families (engine.compile_ms.* histograms,
    engine.retrace_cause.* taxonomy counters, engine.compile_obs.*
    cumulative totals — bcg_tpu/obs/compile.py) render as the
    compile-time-by-entry and retraces-by-cause tables AND stay out of
    the ranked top-counter list (the hlo/hbm/hostsync crowding fix
    applied to the compile namespace) — still with no bcg_tpu
    import."""
    trace = {
        "traceEvents": [],
        "otherData": {"counters": {
            "engine.compile.decode_loop": 2,
            "engine.retrace.decode_loop": 1,
            "engine.compile.prefill": 2,
            "engine.retrace.prefill": 1,
            "engine.compile_ms.decode_loop.bucket.le_250": 1,
            "engine.compile_ms.decode_loop.bucket.le_500": 2,
            "engine.compile_ms.decode_loop.sum": 600.0,
            "engine.compile_ms.decode_loop.count": 2,
            "engine.compile_ms.prefill.bucket.le_250": 2,
            "engine.compile_ms.prefill.sum": 320.0,
            "engine.compile_ms.prefill.count": 2,
            "engine.retrace_cause.static_knob": 1,
            "engine.retrace_cause.shape": 1,
            "engine.compile_obs.first_compile_ms": 700.0,
            "engine.compile_obs.retrace_ms": 220.0,
            "engine.compile_obs.aot_ms": 0.0,
            "engine.compile_obs.cache_entries": 4,
            "serve.requests": 3,
        }},
    }
    path = tmp_path / "compile_trace.json"
    path.write_text(json.dumps(trace))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "compile time by entry" in proc.stdout
    section = proc.stdout.split("compile time by entry")[1]
    # Hottest entry (decode_loop, 600 ms) first.
    assert section.index("decode_loop") < section.index("prefill")
    assert "4 trace-cache entries" in section
    assert "700.0 ms first-compile" in section
    assert "retraces by cause" in proc.stdout
    cause = proc.stdout.split("retraces by cause")[1]
    assert "static_knob" in cause and "shape" in cause
    # The compile families never crowd the ranked counter list.
    top_section = proc.stdout.split("top counters")[1].split("\n==")[0]
    assert "serve.requests" in top_section
    for family in ("engine.compile_ms", "engine.retrace_cause",
                   "engine.compile_obs"):
        assert family not in top_section, family
    # No compile counters -> no sections.
    bare = tmp_path / "bare5.json"
    bare.write_text(json.dumps(
        {"traceEvents": [], "otherData": {"counters": {"serve.requests": 1}}}
    ))
    proc2 = subprocess.run(
        [sys.executable, SCRIPT, str(bare)],
        capture_output=True, text=True, timeout=60,
    )
    assert "compile time by entry" not in proc2.stdout
    assert "retraces by cause" not in proc2.stdout


@pytest.mark.parametrize("run, line", [
    (None, "== prefill positions: 21900 real / 40960 padded "
           "(53.5% real work) =="),
    (25600, "== prefill positions: 21900 real / 25600 run / 40960 padded "
            "(85.5% real work, 37.5% of the window skipped) =="),
], ids=["older_export", "positions_run"])
def test_report_renders_prefill_positions(tmp_path, run, line):
    """Positions that went through the model stand beside real and
    padded when the export carries ``engine.prefill.positions_run``; an
    older export keeps its line."""
    counters = {
        "engine.prefill.positions_padded": 40960,
        "engine.prefill.positions_real": 21900,
    }
    if run is not None:
        counters["engine.prefill.positions_run"] = run
    path = tmp_path / "prefill_trace.json"
    path.write_text(json.dumps(
        {"traceEvents": [], "otherData": {"counters": counters}}
    ))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert line in proc.stdout


def test_report_renders_alerts_line(tmp_path):
    """alert.* transition counters in an export render as the one-line
    alert-plane summary with firing rule names, AND stay out of the
    ranked top-counter list (the crowding fix applied to the alert
    namespace) — still with no bcg_tpu import."""
    trace = {
        "traceEvents": [],
        "otherData": {"counters": {
            "alert.evaluations": 40,
            "alert.fired": 2,
            "alert.resolved": 1,
            "alert.flaps": 0,
            "alert.rules": 12,
            "alert.firing.engine_errors": 1,
            "alert.firing.slo_burn": 0,
            "serve.requests": 3,
        }},
    }
    path = tmp_path / "alerts_trace.json"
    path.write_text(json.dumps(trace))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert ("== alerts: 2 fired / 1 resolved over 40 evaluation(s), "
            "0 flap(s); firing: engine_errors ==") in proc.stdout
    # The alert namespace never crowds the ranked counter list.
    top_section = proc.stdout.split("top counters")[1].split("\n==")[0]
    assert "serve.requests" in top_section
    assert "alert." not in top_section
    # No alert counters -> no line; resolved-quiet exports drop the
    # firing suffix.
    bare = tmp_path / "bare6.json"
    bare.write_text(json.dumps(
        {"traceEvents": [], "otherData": {"counters": {"serve.requests": 1}}}
    ))
    proc2 = subprocess.run(
        [sys.executable, SCRIPT, str(bare)],
        capture_output=True, text=True, timeout=60,
    )
    assert "== alerts:" not in proc2.stdout
    quiet = tmp_path / "quiet.json"
    quiet.write_text(json.dumps({
        "traceEvents": [],
        "otherData": {"counters": {"alert.evaluations": 5,
                                   "alert.fired": 1,
                                   "alert.resolved": 1,
                                   "alert.firing.slo_burn": 0}},
    }))
    proc3 = subprocess.run(
        [sys.executable, SCRIPT, str(quiet)],
        capture_output=True, text=True, timeout=60,
    )
    assert ("== alerts: 1 fired / 1 resolved over 5 evaluation(s), "
            "0 flap(s) ==") in proc3.stdout


def test_report_handles_empty_trace(tmp_path):
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": [], "otherData": {}}))
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(empty)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "no spans" in proc.stdout


def test_report_rejects_unreadable_file(tmp_path):
    proc = subprocess.run(
        [sys.executable, SCRIPT, str(tmp_path / "missing.json")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1
    assert "cannot read" in proc.stderr
