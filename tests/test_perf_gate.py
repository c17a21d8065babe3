"""Hermetic perf gate (scripts/perf_gate.py + perf_baseline.json) in
tier-1.

The gate's contract, asserted here:

* green at HEAD — both CPU scenarios (FakeEngine serving, tiny real
  engine) measure inside every baseline band;
* an injected regression (disabling spec-decode acceptance in the gate
  scenario) FAILS with the metric and tolerance named in the message;
* the baseline is load-bearing: every entry justified, every entry
  matched by a measured metric, removing an entry resurfaces an
  "unbaselined metric" failure (the lint_baseline.json idiom);
* the script exits non-zero on regression (pipefail-composable).

The ``hlo`` scenario is exercised by tests/test_hlo_census.py (same
drift check, no double census cost here).
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "perf_gate.py")

# Metric namespace -> owning test file.  The namespaces this file's
# ``gate`` fixture measures in-process own their resurface contract
# here; every OTHER namespace must name the test file that runs its
# scenario and asserts the same contract there.  PRs 7-11 extended the
# skip-lists below by hand — this mapping is now ASSERTED
# (TestBaselineLoadBearing.test_every_baseline_namespace_has_an_owner),
# so a new perf_baseline.json namespace without a registered owner is a
# tier-1 failure, not a silently unowned gate.
NAMESPACE_OWNERS = {
    "serve": "tests/test_perf_gate.py",
    "engine": "tests/test_perf_gate.py",
    "consensus": "tests/test_perf_gate.py",
    "hlo": "tests/test_hlo_census.py",
    "paged": "tests/test_paged_kv.py",
    "sampler": "tests/test_guided_sampler.py",
    "int4": "tests/test_int4_kv.py",
    "fleet": "tests/test_fleet.py",
    "hostsync": "tests/test_hostsync.py",
    "compile": "tests/test_compile_obs.py",
    "sweep": "tests/test_sweep.py",
    "chaos": "tests/test_resilience.py",
    "scenarios": "tests/test_scenarios.py",
    "alerts": "tests/test_alerts.py",
}
# Namespaces owned elsewhere, as the prefix tuple the measurement-match
# tests skip (derived, not hand-maintained).
FOREIGN_PREFIXES = tuple(
    f"{ns}." for ns, owner in sorted(NAMESPACE_OWNERS.items())
    if owner != "tests/test_perf_gate.py"
)


def _load_script():
    spec = importlib.util.spec_from_file_location("perf_gate", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gate():
    mod = _load_script()
    measured = {}
    measured.update(mod.run_serve_scenario())
    measured.update(mod.run_engine_scenario())
    measured.update(mod.run_consensus_scenario())
    return mod, measured


class TestGreenAtHead:
    def test_gate_passes(self, gate):
        mod, measured = gate
        findings = mod.check_metrics(measured, mod.load_baseline())
        findings += mod.check_stale(
            measured, mod.load_baseline(), ("serve", "engine", "consensus")
        )
        assert findings == [], "\n".join(findings)

    def test_scenarios_measure_the_advertised_metrics(self, gate):
        _, measured = gate
        for name in (
            "engine.decode_steps_per_decision",
            "engine.spec_step_reduction",
            "engine.spec_acceptance_rate",
            "engine.steady_state_retraces",
            "serve.completed_fraction",
            "serve.rows_per_dispatch",
            "serve.spec_acceptance_rate",
            "consensus.convergence_rate",
            "consensus.rounds_to_consensus_mean",
            "consensus.event_schema_completeness",
            "consensus.events_dropped",
            "consensus.histogram_quantile_sanity",
        ):
            assert name in measured, sorted(measured)

    def test_consensus_games_converge_with_complete_schemas(self, gate):
        """Acceptance criterion: the hermetic consensus scenario is
        green — every seeded game converges, every event type lands in
        the JSONL, nothing dropped, quantiles sane."""
        _, measured = gate
        assert measured["consensus.convergence_rate"] == 1.0
        assert measured["consensus.event_schema_completeness"] == 1.0
        assert measured["consensus.events_dropped"] == 0
        assert measured["consensus.histogram_quantile_sanity"] == 1.0

    def test_steady_state_retraces_are_zero(self, gate):
        _, measured = gate
        assert measured["engine.steady_state_retraces"] == 0

    def test_speculation_reduces_decode_iterations(self, gate):
        _, measured = gate
        assert measured["engine.spec_step_reduction"] >= 0.30


class TestInjectedRegression:
    def test_spec_off_fails_with_named_metric_and_tolerance(self, gate):
        """Acceptance criterion: disabling spec-decode acceptance in the
        gate scenario fails the gate, and the failure message carries
        the metric name and its tolerance band."""
        mod, _ = gate
        measured = mod.run_serve_scenario(inject="spec-off")
        findings = mod.check_metrics(measured, mod.load_baseline())
        hits = [f for f in findings if "serve.spec_acceptance_rate" in f]
        assert hits, findings
        assert "tol_rel" in hits[0] and ">=" in hits[0]

    def test_failing_rows_fail_the_gate(self, gate):
        mod, _ = gate
        measured = mod.run_serve_scenario(inject="fail-rows")
        findings = mod.check_metrics(measured, mod.load_baseline())
        assert any("serve.error_row_fraction" in f for f in findings), findings

    def test_events_off_fails_rather_than_passing_vacuously(self, gate):
        """With game-event telemetry silently disabled the consensus
        scenario must FAIL naming its outcome metrics — an empty event
        file can never read as a green convergence gate."""
        mod, _ = gate
        measured = mod.run_consensus_scenario(inject="events-off")
        findings = mod.check_metrics(measured, mod.load_baseline())
        assert any(
            "consensus.event_schema_completeness" in f for f in findings
        ), findings
        assert any(
            "consensus.convergence_rate" in f for f in findings
        ), findings


class TestBaselineLoadBearing:
    def test_every_entry_has_a_reason_and_band(self):
        mod = _load_script()
        baseline = mod.load_baseline()
        assert baseline and baseline["metrics"]
        for name, entry in baseline["metrics"].items():
            assert entry.get("reason", "").strip(), name
            assert entry.get("op") in ("min", "max", "range"), name
            assert "value" in entry, name

    def test_every_baseline_namespace_has_an_owner(self):
        """The NAMESPACE_OWNERS mapping is load-bearing in both
        directions: every namespace present in perf_baseline.json maps
        to an owning test file that EXISTS, and the mapping carries no
        stale namespaces the baseline no longer holds — so adding a
        gate namespace without registering (and writing) its owner
        fails here instead of riding unowned."""
        mod = _load_script()
        baseline = mod.load_baseline()
        namespaces = {n.split(".", 1)[0] for n in baseline["metrics"]}
        assert namespaces == set(NAMESPACE_OWNERS), (
            "perf_baseline.json namespaces and NAMESPACE_OWNERS "
            f"disagree: baseline has {sorted(namespaces)}, owners map "
            f"{sorted(NAMESPACE_OWNERS)} — register the owning test "
            "file for new namespaces (and prune removed ones)"
        )
        for ns, owner in NAMESPACE_OWNERS.items():
            assert os.path.exists(os.path.join(REPO, owner)), (ns, owner)

    def test_every_entry_is_matched_by_a_measurement(self, gate):
        mod, measured = gate
        baseline = mod.load_baseline()
        hlo_entries = [
            n for n in baseline["metrics"] if n.startswith("hlo.")
        ]
        assert hlo_entries == ["hlo.census_drift_findings"]
        for name in baseline["metrics"]:
            if name.startswith(FOREIGN_PREFIXES):
                continue  # owned by NAMESPACE_OWNERS[namespace]
            assert name in measured, name

    def test_removing_an_entry_resurfaces_its_finding(self, gate):
        mod, measured = gate
        baseline = mod.load_baseline()
        for removed in baseline["metrics"]:
            if removed.startswith(FOREIGN_PREFIXES):
                # The same resurface contract is asserted by the
                # namespace's owning test file over its own scenario
                # (NAMESPACE_OWNERS above).
                continue
            pruned = json.loads(json.dumps(baseline))
            del pruned["metrics"][removed]
            findings = mod.check_metrics(measured, pruned)
            assert any(
                removed in f and "no entry" in f for f in findings
            ), (removed, findings)

    def test_stale_entry_is_a_finding(self, gate):
        mod, measured = gate
        baseline = json.loads(json.dumps(mod.load_baseline()))
        baseline["metrics"]["serve.ghost_metric"] = {
            "value": 1, "op": "min", "reason": "synthetic",
        }
        stale = mod.check_stale(measured, baseline, ("serve", "engine"))
        assert any("serve.ghost_metric" in f for f in stale), stale

    def test_skipped_scenarios_entries_are_not_stale(self, gate):
        mod, measured = gate
        serve_only = {
            k: v for k, v in measured.items() if k.startswith("serve.")
        }
        stale = mod.check_stale(serve_only, mod.load_baseline(), ("serve",))
        assert stale == [], stale


class TestScriptExitCodes:
    def test_green_scenario_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, SCRIPT, "--scenarios", "serve"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_injected_regression_exits_nonzero_and_names_metric(self):
        proc = subprocess.run(
            [sys.executable, SCRIPT, "--scenarios", "serve",
             "--inject-regression", "spec-off"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "serve.spec_acceptance_rate" in proc.stderr
        assert "PERF REGRESSION" in proc.stderr
