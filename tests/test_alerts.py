"""Health & alerting plane (bcg_tpu/obs/alerts.py) in tier-1.

ISSUE-19 contracts asserted here:

* **Rule kinds** — threshold (level, absent-metric never fires),
  delta_rate (window movement, trailing-``*`` family sums,
  ``unless_metric`` suppression), burn_rate (fast+slow dual windows
  against ``budget * burn_factor``), staleness (epoch-ms heartbeat age
  and stalled-value arms); ``for_cycles`` debounce; firing is an edge
  (one episode per condition run, re-fire after resolve = flap).
* **Readiness/health** — pushed component vetoes with a deduped
  bounded transition history, pull probes read at request time,
  ``health()`` wired to page severity only.
* **Endpoints** — ``/healthz`` + ``/readyz`` on the metrics HTTP
  server: JSON bodies, 200/503 verdicts, query strings tolerated,
  ``/metrics`` and 404 behavior unchanged.
* **Zero surface off** — with ``BCG_TPU_ALERTS`` unset nothing is
  registered, no evaluator thread exists, and the Prometheus
  exposition of a serving run minus the alert namespace is
  BYTE-identical to an unalerted run (subprocess pin — registries
  don't unregister in-process).
* **Streams** — the ``BCG_TPU_ALERT_EVENTS`` JSONL sink is
  manifest-headed with one record per transition, and
  ``scripts/alert_report.py`` merges it (with
  ``bench_trajectory --alert-out`` records) into one timeline.
* **Drift gate** — the perf_gate ``alerts`` scenario is green against
  justified ``perf_baseline.json`` entries, ``--inject-regression
  alerts-off`` fails naming the floored metrics, and removing any
  ``alerts.*`` entry resurfaces an unbaselined-metric finding (this
  file is the namespace's registered owner —
  tests/test_perf_gate.py NAMESPACE_OWNERS).
"""

import importlib.util
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from bcg_tpu.obs import alerts as obs_alerts
from bcg_tpu.obs import counters as obs_counters
from bcg_tpu.obs import export as obs_export
from bcg_tpu.runtime import metrics as runtime_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE_SCRIPT = os.path.join(REPO, "scripts", "perf_gate.py")
ALERT_REPORT = os.path.join(REPO, "scripts", "alert_report.py")
TRAJECTORY = os.path.join(REPO, "scripts", "bench_trajectory.py")


def _load_gate():
    spec = importlib.util.spec_from_file_location("perf_gate", GATE_SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine_with(monkeypatch, rules):
    """A standalone AlertEngine over the given rules, installed as the
    module-level engine (so health()/evaluate_now()/the exposition
    provider see it) without touching the read-once env flag."""
    monkeypatch.delenv("BCG_TPU_ALERT_EVENTS", raising=False)
    eng = obs_alerts.AlertEngine(rules=rules, period_ms=3_600_000)
    monkeypatch.setattr(obs_alerts, "_engine", eng)
    monkeypatch.setattr(obs_alerts, "_configured", True)
    return eng


@pytest.fixture
def clean_readiness():
    obs_alerts.reset_readiness()
    yield
    obs_alerts.reset_readiness()


@pytest.fixture
def no_module_engine(monkeypatch):
    """Force the module surface to 'alerting off' regardless of what
    other tests configured, without re-reading the env flag."""
    monkeypatch.setattr(obs_alerts, "_engine", None)
    monkeypatch.setattr(obs_alerts, "_configured", True)
    yield


# ------------------------------------------------------------- rule kinds
class TestRuleValidation:
    def test_bad_name_kind_severity_op_raise(self):
        with pytest.raises(ValueError):
            obs_alerts.AlertRule(name="Bad-Name", kind="threshold")
        with pytest.raises(ValueError):
            obs_alerts.AlertRule(name="x", kind="nope")
        with pytest.raises(ValueError):
            obs_alerts.AlertRule(name="x", kind="threshold",
                                 severity="fatal")
        with pytest.raises(ValueError):
            obs_alerts.AlertRule(name="x", kind="threshold", op="ge")

    def test_staleness_needs_a_window(self):
        with pytest.raises(ValueError):
            obs_alerts.AlertRule(name="x", kind="staleness",
                                 metric="serve.zz")
        obs_alerts.AlertRule(name="x", kind="staleness",
                             metric="serve.zz", stall_cycles=1)

    def test_duplicate_rule_names_raise(self, monkeypatch):
        monkeypatch.delenv("BCG_TPU_ALERT_EVENTS", raising=False)
        r = obs_alerts.AlertRule(name="dup", kind="threshold",
                                 metric="serve.zz")
        with pytest.raises(ValueError):
            obs_alerts.AlertEngine(rules=[r, r], period_ms=3_600_000)

    def test_default_ruleset_is_valid_and_named(self):
        rules = obs_alerts.build_default_rules()
        names = {r.name for r in rules}
        assert len(names) == len(rules)
        for expected in ("slo_burn", "engine_errors", "engine_rebuilt",
                         "dispatch_retries", "heartbeat_stale",
                         "fleet_straggler", "chaos_unrecovered"):
            assert expected in names
        assert {r.severity for r in rules} <= set(obs_alerts.SEVERITIES)


class TestThresholdRule:
    def test_fires_above_resolves_below_and_absent_never_fires(
            self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="t_level", kind="threshold",
            metric="serve.zz_alerts_level", op="gt", value=10,
        )
        eng = _engine_with(monkeypatch, [rule])
        eng.evaluate_once()
        assert eng.firing() == []  # absent metric: absence != breach
        obs_counters.set_gauge("serve.zz_alerts_level", 11)
        eng.evaluate_once()
        assert eng.firing() == ["t_level"]
        assert obs_counters.value("alert.firing.t_level") == 1
        obs_counters.set_gauge("serve.zz_alerts_level", 3)
        eng.evaluate_once()
        assert eng.firing() == []
        assert obs_counters.value("alert.firing.t_level") == 0
        assert (eng.fired, eng.resolved, eng.flaps) == (1, 1, 0)

    def test_lt_op(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="t_floor", kind="threshold",
            metric="serve.zz_alerts_floor", op="lt", value=5,
        )
        eng = _engine_with(monkeypatch, [rule])
        obs_counters.set_gauge("serve.zz_alerts_floor", 7)
        eng.evaluate_once()
        assert eng.firing() == []
        obs_counters.set_gauge("serve.zz_alerts_floor", 2)
        eng.evaluate_once()
        assert eng.firing() == ["t_floor"]

    def test_for_cycles_debounce(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="t_slow", kind="threshold", for_cycles=2,
            metric="serve.zz_alerts_debounce", op="gt", value=0,
        )
        eng = _engine_with(monkeypatch, [rule])
        obs_counters.set_gauge("serve.zz_alerts_debounce", 1)
        eng.evaluate_once()
        eng.evaluate_once()
        assert eng.fired == 0  # held 2 cycles: still within the debounce
        eng.evaluate_once()
        assert eng.firing() == ["t_slow"] and eng.fired == 1
        # A blip that clears before the debounce expires never fires.
        obs_counters.set_gauge("serve.zz_alerts_debounce", 0)
        eng.evaluate_once()
        obs_counters.set_gauge("serve.zz_alerts_debounce", 1)
        eng.evaluate_once()
        obs_counters.set_gauge("serve.zz_alerts_debounce", 0)
        eng.evaluate_once()
        assert eng.fired == 1


class TestDeltaRateRule:
    def test_movement_fires_quiet_resolves_refire_flaps(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="d_move", kind="delta_rate", metric="serve.zz_alerts_errs",
        )
        eng = _engine_with(monkeypatch, [rule])
        obs_counters.inc("serve.zz_alerts_errs", 100)
        eng.evaluate_once()
        # First cycle has no base snapshot: pre-existing counts are NOT
        # movement (a process with history can't page at boot).
        assert eng.firing() == []
        obs_counters.inc("serve.zz_alerts_errs", 2)
        eng.evaluate_once()
        assert eng.firing() == ["d_move"]
        obs_counters.inc("serve.zz_alerts_errs", 1)
        eng.evaluate_once()
        assert eng.fired == 1  # still moving: SAME episode, no re-fire
        eng.evaluate_once()
        assert eng.firing() == [] and eng.resolved == 1
        obs_counters.inc("serve.zz_alerts_errs", 5)
        eng.evaluate_once()
        assert eng.fired == 2 and eng.flaps == 1
        assert obs_counters.value("alert.flaps") >= 1

    def test_wildcard_sums_family(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="d_fam", kind="delta_rate", metric="engine.zz_alerts_re.*",
            value=1,  # more than one retrace per window
        )
        eng = _engine_with(monkeypatch, [rule])
        eng.evaluate_once()
        obs_counters.inc("engine.zz_alerts_re.a", 1)
        eng.evaluate_once()
        assert eng.firing() == []  # family moved by 1: not > 1
        obs_counters.inc("engine.zz_alerts_re.a", 1)
        obs_counters.inc("engine.zz_alerts_re.b", 1)
        eng.evaluate_once()
        assert eng.firing() == ["d_fam"]

    def test_unless_metric_suppresses_recovered_movement(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="d_unless", kind="delta_rate",
            metric="chaos.zz_alerts_inj",
            unless_metric="serve.zz_alerts_rec",
        )
        eng = _engine_with(monkeypatch, [rule])
        eng.evaluate_once()
        obs_counters.inc("chaos.zz_alerts_inj", 1)
        obs_counters.inc("serve.zz_alerts_rec", 1)
        eng.evaluate_once()
        assert eng.firing() == []  # injected WITH recovery: suppressed
        obs_counters.inc("chaos.zz_alerts_inj", 1)
        eng.evaluate_once()
        assert eng.firing() == ["d_unless"]  # injected, no recovery


class TestBurnRateRule:
    RULE = dict(
        name="b_slo", kind="burn_rate", metric="serve.zz_alerts_viol",
        requests_metric="serve.zz_alerts_req", budget=0.05,
        burn_factor=2.0, fast_cycles=1, slow_cycles=3,
    )

    def test_burn_above_budget_fires_and_recovery_resolves(
            self, monkeypatch):
        eng = _engine_with(monkeypatch, [obs_alerts.AlertRule(**self.RULE)])
        eng.evaluate_once()
        obs_counters.inc("serve.zz_alerts_req", 100)
        obs_counters.inc("serve.zz_alerts_viol", 50)
        eng.evaluate_once()
        # 50% violation fraction > 0.05 * 2 in both windows (slow
        # clamps to since-start early in a run).
        assert eng.firing() == ["b_slo"]
        obs_counters.inc("serve.zz_alerts_req", 100)
        eng.evaluate_once()
        assert eng.firing() == []  # fast window clean: burn over

    def test_within_budget_never_fires(self, monkeypatch):
        eng = _engine_with(monkeypatch, [obs_alerts.AlertRule(**self.RULE)])
        eng.evaluate_once()
        for _ in range(4):
            obs_counters.inc("serve.zz_alerts_req", 100)
            obs_counters.inc("serve.zz_alerts_viol", 1)  # 1% < 10% burn
            eng.evaluate_once()
        assert eng.fired == 0

    def test_no_denominator_movement_no_fire(self, monkeypatch):
        eng = _engine_with(monkeypatch, [obs_alerts.AlertRule(**self.RULE)])
        eng.evaluate_once()
        obs_counters.inc("serve.zz_alerts_viol", 50)
        eng.evaluate_once()
        assert eng.fired == 0  # violations without traffic: no fraction


class TestStalenessRule:
    def test_heartbeat_age_fires_and_fresh_beat_resolves(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="s_hb", kind="staleness", metric="fleet.zz_alerts_hb",
            max_age_ms=15_000.0,
        )
        eng = _engine_with(monkeypatch, [rule])
        t0 = 1_000_000_000_000.0  # synthetic epoch-ms clock
        obs_counters.set_gauge("fleet.zz_alerts_hb", t0)
        eng.evaluate_once(now_ms=t0 + 1_000)
        assert eng.firing() == []
        eng.evaluate_once(now_ms=t0 + 20_000)
        assert eng.firing() == ["s_hb"]
        obs_counters.set_gauge("fleet.zz_alerts_hb", t0 + 20_000)
        eng.evaluate_once(now_ms=t0 + 21_000)
        assert eng.firing() == [] and eng.resolved == 1

    def test_stalled_value_fires_and_movement_resolves(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="s_wm", kind="staleness", metric="fleet.zz_alerts_wm",
            stall_cycles=2,
        )
        eng = _engine_with(monkeypatch, [rule])
        obs_counters.set_gauge("fleet.zz_alerts_wm", 5)
        eng.evaluate_once()  # first sight: nothing to compare
        eng.evaluate_once()  # unchanged x1
        assert eng.firing() == []
        eng.evaluate_once()  # unchanged x2: stalled
        assert eng.firing() == ["s_wm"]
        obs_counters.set_gauge("fleet.zz_alerts_wm", 6)
        eng.evaluate_once()
        assert eng.firing() == []

    def test_absent_metric_never_stalls(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="s_gone", kind="staleness",
            metric="fleet.zz_alerts_never_registered", stall_cycles=1,
        )
        eng = _engine_with(monkeypatch, [rule])
        for _ in range(4):
            eng.evaluate_once()
        assert eng.fired == 0


# ------------------------------------------------- readiness & health state
class TestReadiness:
    def test_push_veto_and_recovery(self, clean_readiness):
        ok, detail = obs_alerts.readiness()
        assert ok and detail["reasons"] == {}
        obs_alerts.mark_unready("engine", "device call hung")
        ok, detail = obs_alerts.readiness()
        assert not ok and detail["reasons"] == {"engine": "device call hung"}
        assert detail["status"] == "unready"
        obs_alerts.mark_ready("engine")
        ok, _ = obs_alerts.readiness()
        assert ok

    def test_transition_history_dedupes_and_bounds(self, clean_readiness):
        obs_alerts.mark_ready("scheduler")
        obs_alerts.mark_ready("scheduler")  # no state change: no record
        obs_alerts.mark_unready("engine", "hang")
        obs_alerts.mark_unready("engine", "hang")  # dedup
        obs_alerts.mark_ready("engine")
        hist = obs_alerts.readiness_history()
        assert [h["ready"] for h in hist] == [True, False, True]
        assert hist[1]["reasons"] == {"engine": "hang"}
        assert all("ts" in h for h in hist)

    def test_probes_read_at_request_time(self, clean_readiness):
        state = {"why": "queue over watermark"}
        obs_alerts.register_readiness_probe(
            "backpressure", lambda: state["why"]
        )
        ok, detail = obs_alerts.readiness()
        assert not ok
        assert detail["reasons"]["backpressure"] == "queue over watermark"
        state["why"] = None  # probe clears WITHOUT any push call
        ok, _ = obs_alerts.readiness()
        assert ok
        obs_alerts.clear_readiness("backpressure")
        state["why"] = "stale probe must be gone"
        ok, _ = obs_alerts.readiness()
        assert ok

    def test_health_wired_to_page_severity_only(self, monkeypatch,
                                                clean_readiness):
        page = obs_alerts.AlertRule(
            name="h_page", kind="threshold", severity="page",
            metric="serve.zz_alerts_page", op="gt", value=0,
        )
        warn = obs_alerts.AlertRule(
            name="h_warn", kind="threshold", severity="warn",
            metric="serve.zz_alerts_warn", op="gt", value=0,
        )
        eng = _engine_with(monkeypatch, [page, warn])
        obs_counters.set_gauge("serve.zz_alerts_page", 0)
        obs_counters.set_gauge("serve.zz_alerts_warn", 1)
        eng.evaluate_once()
        ok, detail = obs_alerts.health()
        assert ok and detail["page_firing"] == []  # warn is not a page
        obs_counters.set_gauge("serve.zz_alerts_page", 1)
        eng.evaluate_once()
        ok, detail = obs_alerts.health()
        assert not ok and detail["page_firing"] == ["h_page"]
        assert detail["status"] == "failing"

    def test_health_ok_with_alerting_off(self, no_module_engine,
                                         clean_readiness):
        ok, detail = obs_alerts.health()
        assert ok and detail["page_firing"] == []


# ----------------------------------------------------------- HTTP endpoints
def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture
def http_port():
    server, port = obs_export.start_http_server(0)
    yield port
    server.shutdown()


class TestEndpoints:
    def test_readyz_flips_with_pushed_state(self, http_port,
                                            clean_readiness,
                                            no_module_engine):
        code, body = _get(http_port, "/readyz")
        assert code == 200
        assert json.loads(body) == {"reasons": {}, "status": "ready"}
        obs_alerts.mark_unready("engine", "device call hung")
        code, body = _get(http_port, "/readyz")
        assert code == 503
        detail = json.loads(body)
        assert detail["status"] == "unready"
        assert detail["reasons"]["engine"] == "device call hung"
        obs_alerts.mark_ready("engine")
        code, _ = _get(http_port, "/readyz?verbose=1")  # query tolerated
        assert code == 200

    def test_healthz_flips_with_page_alert(self, http_port, monkeypatch,
                                           clean_readiness):
        rule = obs_alerts.AlertRule(
            name="h_http", kind="threshold", severity="page",
            metric="serve.zz_alerts_http", op="gt", value=0,
        )
        eng = _engine_with(monkeypatch, [rule])
        obs_counters.set_gauge("serve.zz_alerts_http", 0)
        eng.evaluate_once()
        code, body = _get(http_port, "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        obs_counters.set_gauge("serve.zz_alerts_http", 1)
        eng.evaluate_once()
        code, body = _get(http_port, "/healthz")
        assert code == 503
        assert json.loads(body)["page_firing"] == ["h_http"]

    def test_healthz_ok_without_alerting(self, http_port,
                                         no_module_engine,
                                         clean_readiness):
        code, body = _get(http_port, "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

    def test_metrics_and_404_unchanged(self, http_port):
        code, body = _get(http_port, "/metrics")
        assert code == 200
        code, _ = _get(http_port, "/nope")
        assert code == 404


class TestExpositionFamily:
    def test_labeled_firing_family_rendered_while_engine_live(
            self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="x_expo", kind="threshold",
            metric="serve.zz_alerts_expo", op="gt", value=0,
        )
        eng = _engine_with(monkeypatch, [rule])
        obs_export.set_extra_blocks_provider(obs_alerts._firing_blocks)
        try:
            expo = obs_export.render_prometheus()
            assert "# HELP bcg_alert_firing" in expo
            assert "# TYPE bcg_alert_firing gauge" in expo
            assert 'bcg_alert_firing{rule="x_expo"} 0' in expo
            obs_counters.set_gauge("serve.zz_alerts_expo", 2)
            eng.evaluate_once()
            expo = obs_export.render_prometheus()
            assert 'bcg_alert_firing{rule="x_expo"} 1' in expo
        finally:
            obs_export.set_extra_blocks_provider(None)
        # Provider gone: the LABELED family disappears (the unlabeled
        # alert.firing.* registry gauges legitimately persist —
        # registries don't unregister).
        assert "bcg_alert_firing{" not in obs_export.render_prometheus()


# ------------------------------------------------------------ event stream
class TestEventStream:
    def _drive(self, monkeypatch, tmp_path):
        path = tmp_path / "alerts.jsonl"
        monkeypatch.setenv("BCG_TPU_ALERT_EVENTS", str(path))
        rules = [
            obs_alerts.AlertRule(
                name="e_page", kind="threshold", severity="page",
                metric="serve.zz_alerts_evt", op="gt", value=0,
                summary="synthetic page",
            ),
        ]
        eng = obs_alerts.AlertEngine(rules=rules, period_ms=3_600_000)
        obs_counters.set_gauge("serve.zz_alerts_evt", 1)
        eng.evaluate_once()
        obs_counters.set_gauge("serve.zz_alerts_evt", 0)
        eng.evaluate_once()
        eng.stop()  # closes + drains the sink
        return path

    def test_manifest_headed_transition_records(self, monkeypatch,
                                                tmp_path):
        path = self._drive(monkeypatch, tmp_path)
        recs = [json.loads(line) for line in
                path.read_text().splitlines() if line.strip()]
        assert recs[0]["event"] == "manifest"
        assert recs[0]["kind"] == "alert"
        assert "run_id" in recs[0] and "flags" in recs[0]
        alerts = [r for r in recs if r["event"] == "alert"]
        assert [(r["rule"], r["state"]) for r in alerts] == [
            ("e_page", "firing"), ("e_page", "resolved"),
        ]
        assert alerts[0]["severity"] == "page"
        assert alerts[0]["kind"] == "threshold"
        assert alerts[0]["value"] == 1
        assert alerts[0]["summary"] == "synthetic page"

    def test_alert_report_merges_streams(self, monkeypatch, tmp_path):
        path = self._drive(monkeypatch, tmp_path)
        proc = subprocess.run(
            [sys.executable, ALERT_REPORT, str(path)],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        assert "alert timeline" in proc.stdout
        assert "FIRING" in proc.stdout and "resolved" in proc.stdout
        assert "e_page: 1 fired / 1 resolved (all resolved)" in proc.stdout
        assert "still firing" not in proc.stdout
        # Severity floor: an info filter keeps the page rule...
        proc2 = subprocess.run(
            [sys.executable, ALERT_REPORT, "--severity", "page", str(path)],
            capture_output=True, text=True, timeout=60,
        )
        assert "e_page" in proc2.stdout
        # ... and the script stays dependency-free (laptop-runnable).
        src = open(ALERT_REPORT).read()
        assert "import bcg_tpu" not in src and "from bcg_tpu" not in src

    def test_bench_trajectory_alert_out_joins_the_timeline(
            self, monkeypatch, tmp_path):
        runtime_stream = self._drive(monkeypatch, tmp_path)
        good = tmp_path / "BENCH_r01.json"
        bad = tmp_path / "BENCH_r02.json"
        good.write_text(json.dumps(
            {"n": 1, "rc": 0, "parsed": {"value": 10.0, "vs_baseline": 1.0}}
        ))
        bad.write_text(json.dumps(
            {"n": 2, "rc": 0, "parsed": {"value": 1.0, "vs_baseline": 0.1}}
        ))
        bench_stream = tmp_path / "bench-alerts.jsonl"
        proc = subprocess.run(
            [sys.executable, TRAJECTORY, str(good), str(bad),
             "--alert-out", str(bench_stream)],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "BENCH REGRESSION" in proc.stderr
        recs = [json.loads(line) for line in
                bench_stream.read_text().splitlines()]
        assert recs[0]["event"] == "manifest"
        assert recs[0]["run_id"] == "bench-trajectory"
        assert recs[1]["rule"] == "bench_regression"
        assert recs[1]["state"] == "firing"
        # One merged timeline: the runtime stream AND the rc-2 verdict.
        merged = subprocess.run(
            [sys.executable, ALERT_REPORT, str(runtime_stream),
             str(bench_stream)],
            capture_output=True, text=True, timeout=60,
        )
        assert merged.returncode == 0, merged.stderr
        assert "bench_regression" in merged.stdout
        assert "e_page" in merged.stdout
        assert "still firing" in merged.stdout  # bench never resolves


# ----------------------------------------------------- publish + summaries
class TestPublish:
    def test_last_alerts_published_on_evaluate(self, monkeypatch):
        rule = obs_alerts.AlertRule(
            name="p_rule", kind="threshold",
            metric="serve.zz_alerts_pub", op="gt", value=0,
        )
        _engine_with(monkeypatch, [rule])
        monkeypatch.setattr(runtime_metrics, "LAST_ALERTS", None)
        obs_counters.set_gauge("serve.zz_alerts_pub", 1)
        obs_alerts.evaluate_now()
        snap = runtime_metrics.LAST_ALERTS
        assert snap is not None and snap["enabled"]
        assert snap["fired"] == 1 and snap["firing"] == ["p_rule"]
        assert snap["fired_by_rule"] == {"p_rule": 1}
        assert obs_alerts.summary()["firing"] == ["p_rule"]

    def test_off_surface_returns_none(self, no_module_engine, monkeypatch):
        monkeypatch.setattr(runtime_metrics, "LAST_ALERTS", None)
        assert obs_alerts.engine() is None
        assert not obs_alerts.enabled()
        assert obs_alerts.summary() is None
        obs_alerts.evaluate_now()  # no-op, must not publish
        assert runtime_metrics.LAST_ALERTS is None


# ------------------------------------------------------------- zero surface
# Worker for the exact-bytes subprocess pin: boots a scheduler (the
# production alerts-boot seam), serves one request, bumps one
# deterministic non-alert counter (so the unalerted exposition is
# non-empty and the byte comparison can't pass vacuously), asserts the
# thread/registry surface matches the flag, prints the exposition.
_EXPO_WORKER = """
import sys, threading
sys.path.insert(0, sys.argv[1])
expect_on = sys.argv[2] == "on"
from bcg_tpu.engine.fake import FakeEngine
from bcg_tpu.obs import counters as obs_counters, export as obs_export
from bcg_tpu.serve.scheduler import Scheduler
SCHEMA = {
    "type": "object",
    "properties": {
        "internal_strategy": {"type": "string", "minLength": 1,
                              "maxLength": 25},
        "value": {"type": "integer", "minimum": 0, "maximum": 50},
        "public_reasoning": {"type": "string", "minLength": 1,
                             "maxLength": 25},
    },
    "required": ["internal_strategy", "value", "public_reasoning"],
    "additionalProperties": False,
}
sched = Scheduler(FakeEngine(seed=0, policy="consensus"),
                  linger_ms=0, bucket_rows=4)
out = sched.submit_and_wait(
    ("json",),
    [("sys", "Round 2. agent_1 value: 17. Your current value: 17. "
      "Decide.", SCHEMA)],
    [0.0], [64],
)
assert len(out) == 1 and "error" not in out[0], out
sched.close()
obs_counters.inc("engine.probe", 3)
names = [t.name for t in threading.enumerate()]
assert ("bcg-alert-eval" in names) == expect_on, names
registered = [n for n in obs_counters.snapshot() if n.startswith("alert.")]
assert bool(registered) == expect_on, registered
sys.stdout.write(obs_export.render_prometheus())
"""


class TestZeroSurface:
    def test_in_process_off_adds_no_alert_names(self, no_module_engine):
        before = set(obs_counters.snapshot())
        assert obs_alerts.maybe_start() is None
        obs_alerts.evaluate_now()
        obs_alerts.mark_ready("probe_component")  # plain module state
        obs_alerts.clear_readiness("probe_component")
        new = set(obs_counters.snapshot()) - before
        assert not [n for n in new if n.startswith("alert.")], new

    def test_exposition_exact_bytes_vs_unalerted_subprocess(self):
        """The only exposition difference an enabled alert plane may
        make is the alert namespace itself (``bcg_alert_*`` counters,
        gauges, and the labeled firing family): filtering those lines
        out of the alerted run's exposition must reproduce the
        unalerted run's exposition EXACTLY, byte for byte (fresh
        subprocess per arm = a pristine registry, which an in-process
        test cannot get back once other tests constructed engines)."""
        def scrape(flag_on: bool) -> str:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": REPO, "BCG_TPU_ALERT_MS": "3600000"}
            env.pop("BCG_TPU_ALERTS", None)
            env.pop("BCG_TPU_ALERT_EVENTS", None)
            if flag_on:
                env["BCG_TPU_ALERTS"] = "1"
            proc = subprocess.run(
                [sys.executable, "-c", _EXPO_WORKER, REPO,
                 "on" if flag_on else "off"],
                capture_output=True, text=True, timeout=180, env=env,
                cwd=REPO,
            )
            assert proc.returncode == 0, proc.stderr
            return proc.stdout

        def mask_wall_clock(expo: str) -> str:
            # The serve run's *_ms histograms are wall-clock: their sums
            # differ between any two runs, and under load so does the
            # bucket a duration falls in.  Every other line (names,
            # labels, counts, event counters) must stay byte-exact.
            return "\n".join(
                line.split(" ")[0] + " <wall>"
                if ("_ms_sum" in line.split(" ")[0]
                    or "_ms_bucket" in line.split(" ")[0]) else line
                for line in expo.splitlines()
            ) + "\n"

        expo_off = scrape(flag_on=False)
        expo_on = scrape(flag_on=True)
        assert "bcg_engine_probe_total" in expo_off  # non-vacuous
        assert "bcg_alert_" not in expo_off
        # The alerted run really surfaced the namespace...
        assert "bcg_alert_evaluations_total" in expo_on
        assert 'bcg_alert_firing{rule="slo_burn"} 0' in expo_on
        # ... and removing it reproduces the unalerted bytes exactly.
        kept = [line for line in expo_on.splitlines()
                if "bcg_alert_" not in line]
        filtered = "\n".join(kept) + ("\n" if kept else "")
        assert mask_wall_clock(filtered) == mask_wall_clock(expo_off)


# ----------------------------------------------------------- the perf gate
@pytest.fixture(scope="module")
def alerts_gate():
    """One in-process run of the perf_gate alerts scenario — this file
    owns the ``alerts.`` namespace's resurface contract
    (tests/test_perf_gate.py NAMESPACE_OWNERS)."""
    mod = _load_gate()
    return mod, mod.run_alerts_scenario()


class TestPerfGateAlerts:
    def test_scenario_green_and_nothing_stale(self, alerts_gate):
        mod, measured = alerts_gate
        findings = mod.check_metrics(measured, mod.load_baseline())
        findings += mod.check_stale(measured, mod.load_baseline(),
                                    ("alerts",))
        assert findings == [], "\n".join(findings)

    def test_acceptance_values(self, alerts_gate):
        _, measured = alerts_gate
        # One episode per expected recovery rule for 3 injected faults.
        assert measured["alerts.chaos_alerts_fired"] == 3.0
        assert measured["alerts.fault_coverage"] >= 1.0
        # Acceptance: flap count and false positives 0 EXACT; every
        # fired alert resolved by run end.
        assert measured["alerts.flaps"] == 0.0
        assert measured["alerts.false_positives"] == 0.0
        assert measured["alerts.unresolved_at_end"] == 0.0
        assert measured["alerts.unexpected_alerts"] == 0.0
        # Health flipped failing during the page episode and back;
        # readiness flipped unready inside the hang window and back.
        assert measured["alerts.healthz_flip"] == 1.0
        assert measured["alerts.readyz_flip"] == 1.0
        assert measured["alerts.event_stream_ok"] == 1.0

    def test_alerts_off_fails_naming_the_metrics(self, alerts_gate):
        """Acceptance: the evaluator silently off can never read as a
        green alerting gate — the injection must fail naming the
        floored metrics."""
        mod, _ = alerts_gate
        measured = mod.run_alerts_scenario(inject="alerts-off")
        findings = mod.check_metrics(measured, mod.load_baseline())
        for name in ("alerts.rules_evaluated", "alerts.chaos_alerts_fired",
                     "alerts.fault_coverage", "alerts.healthz_flip",
                     "alerts.event_stream_ok"):
            assert any(name in f for f in findings), (name, findings)
        # Readiness is plain module state the scheduler pushes with
        # alerting off too — the gateway's /readyz does not dim.
        assert measured["alerts.readyz_flip"] == 1.0

    def test_removing_each_entry_resurfaces_its_finding(self, alerts_gate):
        mod, measured = alerts_gate
        baseline = mod.load_baseline()
        entries = [n for n in baseline["metrics"]
                   if n.startswith("alerts.")]
        assert sorted(entries) == [
            "alerts.chaos_alerts_fired", "alerts.event_stream_ok",
            "alerts.false_positives", "alerts.fault_coverage",
            "alerts.flaps", "alerts.healthz_flip", "alerts.readyz_flip",
            "alerts.rules_evaluated", "alerts.unexpected_alerts",
            "alerts.unresolved_at_end",
        ]
        for removed in entries:
            pruned = json.loads(json.dumps(baseline))
            del pruned["metrics"][removed]
            findings = mod.check_metrics(measured, pruned)
            assert any(
                removed in f and "no entry" in f for f in findings
            ), (removed, findings)

    @pytest.mark.slow
    def test_cli_injection_exits_nonzero_and_names_metric(self):
        """Subprocess CLI arm (slow: cold jax import + two serve runs).
        The exit-code/naming contract is already pinned in-process
        above; this run keeps the exact `--scenarios alerts
        --inject-regression alerts-off` invocation honest in the full
        suite."""
        proc = subprocess.run(
            [sys.executable, GATE_SCRIPT, "--scenarios", "alerts",
             "--inject-regression", "alerts-off"],
            cwd=REPO, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "alerts.chaos_alerts_fired" in proc.stderr
        assert "PERF REGRESSION" in proc.stderr
