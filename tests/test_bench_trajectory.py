"""Cross-run bench trajectory report (scripts/bench_trajectory.py).

The acceptance contract, asserted against the checked-in BENCH_r01/r02
records plus three synthetic runs in the shape that once entered the
record as r03-r05 (``value: 0.0`` with rc=0 and an ``error`` field): the
outage runs (and the r02 driver crash) classify as OUTAGES — excluded
from regression analysis — and the script exits 0; a genuine measured
drop below the threshold exits 2 naming the metric.  Kept
bcg_tpu-import-free like the script itself.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_trajectory.py")
CHECKED_IN = [os.path.join(REPO, f"BENCH_r0{i}.json") for i in (1, 2)]


@pytest.fixture(scope="module")
def mod():
    spec = importlib.util.spec_from_file_location("bench_trajectory", SCRIPT)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _write(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)
    return str(path)


def _measured(n, value, vs_baseline=1.0, extra=None):
    return {
        "n": n, "rc": 0,
        "parsed": {
            "metric": "agent_decisions_per_sec", "value": value,
            "unit": "decisions/sec", "vs_baseline": vs_baseline,
            "extra": extra or {},
        },
    }


class TestImportFree:
    def test_no_bcg_tpu_import(self):
        src = open(SCRIPT).read()
        tops = [
            line.split()[1].split(".")[0]
            for line in src.splitlines()
            if line.startswith(("import ", "from "))
        ]
        assert "bcg_tpu" not in tops


@pytest.fixture
def bench_files(tmp_path):
    """r01 (measured) and r02 (driver crash) as checked in, then three
    no-device runs that printed a zero with rc=0."""
    outages = [
        _write(tmp_path / f"BENCH_r0{n}.json", {
            "n": n, "rc": 0,
            "parsed": {
                "metric": "agent_decisions_per_sec", "value": 0.0,
                "unit": "decisions/sec", "vs_baseline": 0.0,
                "error": "accelerator attach failed: TimeoutExpired "
                         "(timeout=900s); backend unavailable",
            },
        })
        for n in (3, 4, 5)
    ]
    return CHECKED_IN + outages


class TestCheckedInTrajectory:
    """The records that motivated the outage-vs-regression distinction."""

    def test_r03_to_r05_classify_as_outages(self, mod, bench_files):
        runs = mod.order_runs([mod.load_run(p) for p in bench_files])
        status = {r.label: r.status for r in runs}
        assert status["BENCH_r01"] == "measured"
        assert status["BENCH_r02"] == "outage"  # driver crash, rc=1
        for label in ("BENCH_r03", "BENCH_r04", "BENCH_r05"):
            assert status[label] == "outage", label
        # The outage notes carry the attach failure, not a number.
        notes = {r.label: r.note for r in runs}
        assert "accelerator attach failed" in notes["BENCH_r03"]

    def test_no_regression_and_rc_zero(self, mod, bench_files):
        runs = mod.order_runs([mod.load_run(p) for p in bench_files])
        assert mod.find_regressions(runs, threshold=0.7) == []
        proc = subprocess.run(
            [sys.executable, SCRIPT] + bench_files,
            capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        assert "4 outage(s)" in proc.stdout
        assert "excluded from regression analysis" in proc.stdout
        assert "REGRESSION" not in proc.stdout

    def test_trend_table_reports_best_known_good(self, mod, bench_files):
        runs = mod.order_runs([mod.load_run(p) for p in bench_files])
        report = mod.render_report(runs, threshold=0.7)
        assert "decisions_per_sec (best-known-good 7.292)" in report
        assert "100.0% of best" in report


class TestClassification:
    def test_null_vs_baseline_is_outage(self, mod, tmp_path):
        run = mod.load_run(_write(tmp_path / "b.json", {
            "n": 9, "rc": 0,
            "parsed": {"metric": "agent_decisions_per_sec", "value": 0.0,
                       "unit": "decisions/sec", "vs_baseline": None},
        }))
        assert run.status == "outage"
        assert "null vs_baseline" in run.note

    def test_error_field_is_outage_even_with_numeric_vs_baseline(
            self, mod, tmp_path):
        # The pre-PR-6 poisoned shape: vs_baseline 0.0 WITH an error.
        run = mod.load_run(_write(tmp_path / "b.json", {
            "n": 9, "rc": 0,
            "parsed": {"value": 0.0, "vs_baseline": 0.0,
                       "error": "backend unavailable"},
        }))
        assert run.status == "outage"
        assert "backend unavailable" in run.note

    def test_empty_parsed_is_outage(self, mod, tmp_path):
        run = mod.load_run(_write(tmp_path / "b.json",
                                  {"n": 2, "rc": 1, "parsed": {}}))
        assert run.status == "outage"
        assert "rc=1" in run.note

    def test_bare_bench_payload_accepted(self, mod, tmp_path):
        run = mod.load_run(_write(tmp_path / "b.json", {
            "metric": "agent_decisions_per_sec", "value": 5.0,
            "unit": "decisions/sec", "vs_baseline": 2.0,
            "extra": {"rounds_per_sec": 0.25},
        }))
        assert run.status == "measured"
        assert run.metrics["decisions_per_sec"] == 5.0
        assert run.metrics["rounds_per_sec"] == 0.25


class TestRegression:
    def test_real_drop_exits_two_naming_metric(self, mod, tmp_path):
        a = _write(tmp_path / "BENCH_r01.json", _measured(1, 10.0))
        b = _write(tmp_path / "BENCH_r02.json", _measured(2, 3.0))
        proc = subprocess.run(
            [sys.executable, SCRIPT, a, b],
            capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert proc.returncode == 2
        assert "BENCH REGRESSION" in proc.stderr
        assert "decisions_per_sec" in proc.stderr
        assert "best-known-good 10" in proc.stderr

    def test_outage_after_good_run_is_not_a_regression(self, mod, tmp_path):
        a = _write(tmp_path / "BENCH_r01.json", _measured(1, 10.0))
        b = _write(tmp_path / "BENCH_r02.json", {
            "n": 2, "rc": 0,
            "parsed": {"value": 0.0, "vs_baseline": None,
                       "error": "attach timeout"},
        })
        runs = mod.order_runs([mod.load_run(p) for p in (a, b)])
        assert mod.find_regressions(runs, 0.7) == []

    def test_within_threshold_is_green(self, mod, tmp_path):
        a = _write(tmp_path / "a.json", _measured(1, 10.0))
        b = _write(tmp_path / "b.json", _measured(2, 8.0))
        runs = mod.order_runs([mod.load_run(p) for p in (a, b)])
        assert mod.find_regressions(runs, 0.7) == []

    def test_recovery_after_outage_compares_to_best_known_good(
            self, mod, tmp_path):
        # measured 10 -> outage -> measured 4: the comparison spans the
        # outage (best-known-good 10), so the drop IS caught.
        files = [
            _write(tmp_path / "BENCH_r01.json", _measured(1, 10.0)),
            _write(tmp_path / "BENCH_r02.json", {
                "n": 2, "rc": 0,
                "parsed": {"value": 0.0, "vs_baseline": None,
                           "error": "attach timeout"},
            }),
            _write(tmp_path / "BENCH_r03.json", _measured(3, 4.0)),
        ]
        runs = mod.order_runs([mod.load_run(p) for p in files])
        findings = mod.find_regressions(runs, 0.7)
        assert len(findings) == 1
        assert "best-known-good 10" in findings[0]

    def test_single_measured_run_cannot_regress(self, mod, tmp_path):
        a = _write(tmp_path / "a.json", _measured(1, 10.0))
        runs = [mod.load_run(a)]
        assert mod.find_regressions(runs, 0.7) == []


class TestCli:
    def test_directory_glob(self, mod, tmp_path):
        _write(tmp_path / "BENCH_r01.json", _measured(1, 10.0))
        _write(tmp_path / "BENCH_r02.json", _measured(2, 11.0))
        proc = subprocess.run(
            [sys.executable, SCRIPT, str(tmp_path)],
            capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        assert "2 measured, 0 outage(s)" in proc.stdout

    def test_no_files_is_usage_error(self, mod, tmp_path):
        proc = subprocess.run(
            [sys.executable, SCRIPT, str(tmp_path)],
            capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert proc.returncode == 1
