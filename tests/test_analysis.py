"""Tier-1 wiring for the static analyzer (bcg_tpu.analysis).

Three layers:

1. fixture tests — every rule ID fires on its seeded-violation fixture
   and stays quiet on the clean-idiom twin (``tests/analysis_fixtures/``);
2. repo meta-test — the full-package run is clean modulo the checked-in
   baseline (``lint_baseline.json``), no BCG-ENV-RAW findings are merely
   baselined (the env migration is enforced complete, not parked), and
   every baseline entry still matches a live finding (removing one makes
   its violation reappear — the baseline is load-bearing, not a mute);
3. envflags registry unit tests.
"""

import os
import subprocess
import sys

import pytest

from bcg_tpu.analysis import (
    RULE_IDS,
    analyze_paths,
    load_baseline,
    repo_root,
)
from bcg_tpu.analysis.core import BaselineEntry, ModuleContext
from bcg_tpu.runtime import envflags

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")

# rule ID -> (bad fixture, good fixture), paths relative to FIXTURES.
RULE_FIXTURES = {
    "BCG-HOST-SYNC": ("bad_host_sync.py", "good_host_sync.py"),
    "BCG-JIT-NP": ("bad_jit_np.py", "good_jit_np.py"),
    "BCG-JIT-BRANCH": ("bad_jit_branch.py", "good_jit_branch.py"),
    "BCG-JIT-OUTSHARD": (
        "models/bad_jit_outshard.py", "models/good_jit_outshard.py",
    ),
    "BCG-JIT-DONATE": (
        "models/bad_jit_donate.py", "models/good_jit_donate.py",
    ),
    "BCG-SHARD-AXIS": ("bad_shard_axis.py", "good_shard_axis.py"),
    "BCG-SHARD-DIVISOR": ("bad_shard_divisor.py", "good_shard_divisor.py"),
    "BCG-ENV-RAW": ("bad_env_raw.py", "good_env_raw.py"),
    "BCG-ENV-UNREG": ("bad_env_unreg.py", "good_env_unreg.py"),
    "BCG-EXCEPT-BROAD": ("bad_except_broad.py", "good_except_broad.py"),
    "BCG-MUT-DEFAULT": ("bad_mut_default.py", "good_mut_default.py"),
    "BCG-LOCK-CALL": ("bad_lock_call.py", "good_lock_call.py"),
    "BCG-TIME-WALL": ("bad_time_wall.py", "good_time_wall.py"),
    "BCG-RETRY-SLEEP": ("bad_retry_sleep.py", "good_retry_sleep.py"),
    "BCG-OBS-NAME": ("bad_obs_name.py", "good_obs_name.py"),
    "BCG-OBS-BUCKET": ("bad_obs_bucket.py", "good_obs_bucket.py"),
    "BCG-LOCK-ORDER": ("bad_lock_order.py", "good_lock_order.py"),
    "BCG-LOCK-BLOCK": ("bad_lock_block.py", "good_lock_block.py"),
    "BCG-SHARED-MUT": ("bad_shared_mut.py", "good_shared_mut.py"),
}


def _run_on(path):
    return analyze_paths(paths=[os.path.join(FIXTURES, path)], baseline=None)


class TestRuleFixtures:
    def test_every_rule_has_a_fixture_pair(self):
        assert sorted(RULE_FIXTURES) == sorted(RULE_IDS)
        for bad, good in RULE_FIXTURES.values():
            assert os.path.exists(os.path.join(FIXTURES, bad)), bad
            assert os.path.exists(os.path.join(FIXTURES, good)), good

    @pytest.mark.parametrize("rule_id", sorted(RULE_FIXTURES))
    def test_rule_fires_on_seeded_violation(self, rule_id):
        bad, _ = RULE_FIXTURES[rule_id]
        hits = [f for f in _run_on(bad).findings if f.rule == rule_id]
        assert hits, f"{rule_id} did not fire on {bad}"

    @pytest.mark.parametrize("rule_id", sorted(RULE_FIXTURES))
    def test_rule_quiet_on_clean_idiom(self, rule_id):
        _, good = RULE_FIXTURES[rule_id]
        hits = [f for f in _run_on(good).findings if f.rule == rule_id]
        assert not hits, (
            f"{rule_id} false-positive on {good}: "
            + "; ".join(f.format() for f in hits)
        )

    def test_expected_finding_counts_on_bad_fixtures(self):
        # The bad fixtures seed a known number of violations each —
        # a drop means a detection regression, not just "still fires".
        expected = {
            "BCG-HOST-SYNC": 4,
            "BCG-ENV-RAW": 5,
            "BCG-SHARD-DIVISOR": 3,
            "BCG-JIT-NP": 2,
            "BCG-JIT-BRANCH": 2,
            "BCG-SHARD-AXIS": 2,
            "BCG-ENV-UNREG": 2,
            "BCG-EXCEPT-BROAD": 2,
            "BCG-MUT-DEFAULT": 2,
            "BCG-JIT-OUTSHARD": 2,
            "BCG-JIT-DONATE": 1,
            "BCG-LOCK-CALL": 3,
            "BCG-TIME-WALL": 3,
            "BCG-RETRY-SLEEP": 3,
            "BCG-OBS-NAME": 6,
            "BCG-OBS-BUCKET": 3,
            # bad_lock_order.py seeds ONE two-lock inversion (the PR 15
            # device-lock-swap shape) between two thread roots.
            "BCG-LOCK-ORDER": 1,
            "BCG-LOCK-BLOCK": 3,
            "BCG-SHARED-MUT": 1,
        }
        for rule_id, want in expected.items():
            bad, _ = RULE_FIXTURES[rule_id]
            got = [f for f in _run_on(bad).findings if f.rule == rule_id]
            assert len(got) == want, (
                f"{rule_id}: expected {want} findings on {bad}, got "
                f"{len(got)}: " + "; ".join(f.format() for f in got)
            )

    def test_inline_suppression(self, tmp_path):
        src = (
            "def f(x, acc=[]):  # lint: ignore[BCG-MUT-DEFAULT]\n"
            "    return acc\n"
            "def g(x, acc=[]):\n"
            "    return acc\n"
        )
        p = tmp_path / "snippet.py"
        p.write_text(src)
        findings = analyze_paths(paths=[str(p)], baseline=None).findings
        muts = [f for f in findings if f.rule == "BCG-MUT-DEFAULT"]
        assert len(muts) == 1 and muts[0].line == 3


@pytest.fixture(scope="module")
def full_tree_raw():
    """ONE baseline-free full-tree analysis shared by the repo
    meta-tests — the tree walk (parse + whole-program index + rules) is
    the expensive part; baseline application is a pure cheap function
    (core.apply_baseline) each test replays as needed."""
    return analyze_paths(baseline=None)


class TestRepoClean:
    def test_repo_is_clean_modulo_baseline(self, full_tree_raw):
        from bcg_tpu.analysis.core import apply_baseline

        assert not full_tree_raw.parse_errors, full_tree_raw.parse_errors
        findings, _, unused = apply_baseline(
            full_tree_raw.findings, load_baseline()
        )
        assert not findings, "\n".join(f.format() for f in findings)

    def test_env_migration_complete_not_baselined(self, full_tree_raw):
        # The env-flag registry migration is a hard guarantee: no raw
        # read of a registered name may even be PARKED in the baseline.
        env_raw = [
            f for f in full_tree_raw.findings if f.rule == "BCG-ENV-RAW"
        ]
        assert not env_raw, "\n".join(f.format() for f in env_raw)

    def test_baseline_entries_are_load_bearing(self, full_tree_raw):
        from bcg_tpu.analysis.core import apply_baseline

        baseline = load_baseline()
        assert baseline, "baseline file missing or empty"
        # Without the baseline every entry's violation must reappear.
        raw = full_tree_raw
        live_keys = {f.key() for f in raw.findings}
        for entry in baseline:
            assert entry.key() in live_keys, (
                f"baseline entry no longer matches any finding (fixed? "
                f"delete it): {entry.rule} {entry.path} {entry.content!r}"
            )
        # And removing any one entry resurfaces exactly its findings.
        # apply_baseline is the same matcher analyze_paths uses, so one
        # analysis run backs every removal replay (the tree walk is the
        # expensive part, the matching is not).
        for removed in baseline:
            remaining = [e for e in baseline if e is not removed]
            resurfaced, _, _ = apply_baseline(raw.findings, remaining)
            assert any(
                f.key() == removed.key() for f in resurfaced
            ), f"removing baseline entry had no effect: {removed.rule}"

    def test_every_baseline_entry_has_a_reason(self):
        for entry in load_baseline():
            assert entry.reason.strip(), (
                f"baseline entry without justification: "
                f"{entry.rule} {entry.path}"
            )

    def test_baseline_count_caps_identical_lines(self, tmp_path):
        # Two textually identical violations share a baseline key; the
        # entry's count bounds how many it parks — a third copy added
        # later must resurface, not ride the existing entry.
        src = (
            "def f():\n    try:\n        risky()\n"
            "    except Exception:\n        pass\n"
            "def g():\n    try:\n        risky()\n"
            "    except Exception:\n        pass\n"
        )
        p = tmp_path / "dup.py"
        p.write_text(src)
        probe = analyze_paths(paths=[str(p)], baseline=None).findings
        assert len(probe) == 2 and len({f.key() for f in probe}) == 1
        entry = BaselineEntry(
            rule=probe[0].rule, path=probe[0].path,
            content=probe[0].content, reason="test", count=1,
        )
        capped = analyze_paths(paths=[str(p)], baseline=[entry])
        assert len(capped.findings) == 1 and len(capped.baselined) == 1
        entry.count = 2
        full = analyze_paths(paths=[str(p)], baseline=[entry])
        assert not full.findings and len(full.baselined) == 2

    def test_unknown_baseline_entry_is_reported_unused(self):
        from bcg_tpu.analysis.core import apply_baseline

        fake = BaselineEntry(
            rule="BCG-MUT-DEFAULT",
            path="bcg_tpu/no/such/file.py",
            content="def f(x=[]):",
            reason="synthetic",
        )
        _, _, unused = apply_baseline([], [fake])
        assert fake in unused

    def test_scan_scope_covers_scripts_and_bench(self):
        # ISSUE-6 satellite: the ENV-RAW migration guarantee extends to
        # scripts/ and bench.py — the default scan scope must include
        # them, or a raw read added to a script escapes the whole suite.
        from bcg_tpu.analysis.core import default_paths, iter_python_files

        paths = default_paths()
        names = {os.path.basename(p.rstrip(os.sep)) for p in paths}
        assert "scripts" in names and "bench.py" in names
        scanned = {
            os.path.relpath(f, repo_root()).replace(os.sep, "/")
            for f in iter_python_files(paths)
        }
        assert "scripts/bench_trajectory.py" in scanned
        assert "scripts/scale_sweep.py" in scanned
        assert "scripts/perf_gate.py" in scanned
        assert "scripts/microbench_prefill.py" in scanned

    def test_env_raw_fires_inside_scripts_scope(self, tmp_path):
        # A seeded raw read placed under a scripts-shaped path is caught
        # by the same analyze_paths call the repo meta-test uses.
        scripts_dir = tmp_path / "scripts"
        scripts_dir.mkdir()
        (scripts_dir / "probe.py").write_text(
            "import os\nMODE = os.environ.get('BCG_TPU_TRACE')\n"
        )
        findings = analyze_paths(paths=[str(scripts_dir)], baseline=None).findings
        assert any(f.rule == "BCG-ENV-RAW" for f in findings)

    def test_cli_exits_zero_on_repo(self):
        proc = subprocess.run(
            [sys.executable, "-m", "bcg_tpu.analysis"],
            cwd=repo_root(), capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_lint_script_diff_mode_runs(self):
        proc = subprocess.run(
            [sys.executable, os.path.join("scripts", "lint.py"), "--diff"],
            cwd=repo_root(), capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestWholeProgram:
    """The interprocedural pass (bcg_tpu/analysis/interproc.py): cross-
    module jit-region lift, thread-root × lock machinery, and the CLI
    surfaces the concurrency rules ride on."""

    def test_cross_module_jit_lift_reaches_helper(self):
        # entry.py jits a caller; the np.asarray violation lives in
        # helper.py, which has no jit of its own — only the whole-
        # program lift can attribute the traced region across the
        # module boundary.  Exactly one finding, in the HELPER module,
        # and the jit-unreachable sibling function stays quiet.
        fix = os.path.join(FIXTURES, "xmod")
        findings = analyze_paths(paths=[fix], baseline=None).findings
        hs = [f for f in findings if f.rule == "BCG-HOST-SYNC"]
        assert len(hs) == 1, "\n".join(f.format() for f in findings)
        assert hs[0].path.endswith("xmod/helper.py")
        assert "np.asarray" in hs[0].content

    def test_helper_alone_is_clean(self):
        # Same helper analyzed WITHOUT its jitting caller in view: no
        # jit region reaches it, so the host-sync rule must stay quiet
        # — the cross-module finding above is the lift's work, not a
        # per-module rule change.
        helper = os.path.join(FIXTURES, "xmod", "helper.py")
        findings = analyze_paths(paths=[helper], baseline=None).findings
        assert not findings, "\n".join(f.format() for f in findings)

    def test_new_rule_baseline_entries_name_their_guard(self):
        # A concurrency suppression that does not say WHICH lock (or
        # which thread-confinement argument) makes the site safe is
        # unreviewable prose; require the rationale to name it.
        import re

        guard = re.compile(
            r"lock|cond|thread|confin|single|serializ|GIL", re.IGNORECASE
        )
        new_rules = {"BCG-LOCK-ORDER", "BCG-LOCK-BLOCK", "BCG-SHARED-MUT"}
        checked = 0
        for entry in load_baseline():
            if entry.rule not in new_rules:
                continue
            checked += 1
            assert guard.search(entry.reason), (
                f"{entry.rule} baseline entry for {entry.path} must name "
                f"the guarding lock or thread-confinement rationale: "
                f"{entry.reason!r}"
            )
        assert checked, "expected concurrency-rule baseline entries"

    def test_json_emits_finding_status(self):
        # Machine-readable output carries each finding's disposition so
        # CI tooling never joins the findings/baselined lists by hand.
        import json as json_mod

        bad = os.path.join(FIXTURES, "bad_lock_block.py")
        proc = subprocess.run(
            [sys.executable, "-m", "bcg_tpu.analysis",
             "--no-baseline", "--json", bad],
            cwd=repo_root(), capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        payload = json_mod.loads(proc.stdout)
        blocks = [
            f for f in payload["findings"] if f["rule"] == "BCG-LOCK-BLOCK"
        ]
        assert len(blocks) == 3
        for f in blocks:
            assert f["status"] == "new"
            assert {"rule", "path", "line", "message"} <= set(f)
        # Baselined findings carry the other disposition.
        proc = subprocess.run(
            [sys.executable, "-m", "bcg_tpu.analysis", "--json",
             os.path.join("bcg_tpu", "engine", "collective.py")],
            cwd=repo_root(), capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json_mod.loads(proc.stdout)
        assert payload["baselined"], "expected baselined collective findings"
        assert all(f["status"] == "baselined" for f in payload["baselined"])

    def test_lint_diff_flags_new_violation(self):
        # Regression gate for the pre-commit path: an untracked file
        # seeding a violation must flip scripts/lint.py --diff to exit
        # code 1 and be named in the JSON payload as NEW debt.
        import json as json_mod

        probe = os.path.join(repo_root(), "scripts", "_lint_diff_probe.py")
        try:
            with open(probe, "w", encoding="utf-8") as f:
                f.write(
                    "import os\n"
                    "MODE = os.environ.get('BCG_TPU_TRACE')\n"
                )
            proc = subprocess.run(
                [sys.executable, os.path.join("scripts", "lint.py"),
                 "--diff", "--json"],
                cwd=repo_root(), capture_output=True, text=True, timeout=120,
            )
            assert proc.returncode == 1, proc.stdout + proc.stderr
            payload = json_mod.loads(proc.stdout)
            hits = [
                f for f in payload["findings"]
                if f["path"].endswith("_lint_diff_probe.py")
            ]
            assert hits and all(f["status"] == "new" for f in hits)
        finally:
            if os.path.exists(probe):
                os.remove(probe)

    def test_locks_report_mode(self):
        proc = subprocess.run(
            [sys.executable, "-m", "bcg_tpu.analysis", "--locks"],
            cwd=repo_root(), capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "thread roots:" in proc.stdout
        assert "lock-order edges" in proc.stdout
        # Known roots and locks from the real tree anchor the report.
        assert "bcg-sweep-*" in proc.stdout
        assert "Scheduler._device_lock" in proc.stdout

    def test_lock_order_quiet_without_second_root(self):
        # The deadlock rule needs two independently spawned roots (or
        # one pooled root) covering different cycle edges — inverted
        # acquisition reached from a single thread cannot deadlock by
        # itself and must not fire.
        src = (
            "import threading\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "        threading.Thread(target=self._one).start()\n"
            "    def _one(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        )
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, "single_root.py")
            with open(p, "w", encoding="utf-8") as f:
                f.write(src)
            findings = analyze_paths(paths=[p], baseline=None).findings
            orders = [f for f in findings if f.rule == "BCG-LOCK-ORDER"]
            assert not orders, "\n".join(f.format() for f in orders)


class TestJitRegionResolution:
    def _ctx(self, tmp_path, src):
        p = tmp_path / "m.py"
        p.write_text(src)
        return ModuleContext(str(p), "m.py", src)

    def test_transitive_callee_is_a_region(self, tmp_path):
        ctx = self._ctx(
            tmp_path,
            "import jax\n"
            "def helper(x):\n"
            "    return x\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return helper(x)\n"
            "def unrelated(x):\n"
            "    return x\n",
        )
        names = {fn.name for fn in ctx.jit_regions}
        assert names == {"helper", "f"}

    def test_lax_while_body_is_a_region(self, tmp_path):
        ctx = self._ctx(
            tmp_path,
            "import jax\n"
            "def run(c):\n"
            "    def body(carry):\n"
            "        return carry\n"
            "    def cond(carry):\n"
            "        return True\n"
            "    return jax.lax.while_loop(cond, body, c)\n",
        )
        names = {fn.name for fn in ctx.jit_regions}
        assert names == {"body", "cond"}

    def test_lambda_lax_operand_is_a_region(self, tmp_path):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def run(c):\n"
            "    return jax.lax.while_loop(\n"
            "        lambda s: s < 3, lambda s: np.asarray(s), c)\n"
        )
        p = tmp_path / "m.py"
        p.write_text(src)
        findings = analyze_paths(paths=[str(p)], baseline=None).findings
        assert any(f.rule == "BCG-HOST-SYNC" for f in findings), findings

    def test_tree_map_function_is_not_a_region(self, tmp_path):
        # jax.tree.map applies its function EAGERLY on host —
        # convert-before-device_put must not be flagged as a jit region.
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def convert(leaf):\n"
            "    return np.asarray(leaf)\n"
            "def load(tree):\n"
            "    return jax.tree.map(convert, tree)\n"
        )
        p = tmp_path / "m.py"
        p.write_text(src)
        findings = analyze_paths(paths=[str(p)], baseline=None).findings
        assert not findings, [f.format() for f in findings]


class TestEnvFlags:
    def test_parse_bool_semantics(self):
        assert envflags.parse_bool(None, True) is True
        assert envflags.parse_bool("", False) is False
        for falsy in ("0", "false", "No", " OFF "):
            assert envflags.parse_bool(falsy, True) is False
        for truthy in ("1", "true", "anything"):
            assert envflags.parse_bool(truthy, False) is True

    def test_read_at_call_time(self, monkeypatch):
        monkeypatch.delenv("BCG_TPU_TRACE", raising=False)
        assert envflags.get_bool("BCG_TPU_TRACE") is False
        monkeypatch.setenv("BCG_TPU_TRACE", "1")
        assert envflags.get_bool("BCG_TPU_TRACE") is True

    def test_get_int_fallback_on_garbage(self, monkeypatch):
        monkeypatch.setenv("BENCH_ROUNDS", "not-a-number")
        assert envflags.get_int("BENCH_ROUNDS") == 3
        monkeypatch.setenv("BENCH_ROUNDS", "7")
        assert envflags.get_int("BENCH_ROUNDS") == 7

    def test_default_override(self, monkeypatch):
        monkeypatch.delenv("BENCH_PREFILL_CHUNK", raising=False)
        assert envflags.get_int("BENCH_PREFILL_CHUNK", 512) == 512
        monkeypatch.setenv("BENCH_PREFILL_CHUNK", "128")
        assert envflags.get_int("BENCH_PREFILL_CHUNK", 512) == 128

    def test_unregistered_name_raises(self):
        with pytest.raises(KeyError):
            envflags.get_bool("BCG_TPU_NO_SUCH_FLAG")
        with pytest.raises(KeyError):
            envflags.is_set("TOTALLY_UNKNOWN")

    def test_kind_mismatch_raises(self):
        with pytest.raises(TypeError):
            envflags.get_int("BCG_TPU_TRACE")
        with pytest.raises(TypeError):
            envflags.get_bool("BENCH_MODEL")

    def test_is_set(self, monkeypatch):
        monkeypatch.delenv("BENCH_QUANTIZATION", raising=False)
        assert envflags.is_set("BENCH_QUANTIZATION") is False
        monkeypatch.setenv("BENCH_QUANTIZATION", "int4")
        assert envflags.is_set("BENCH_QUANTIZATION") is True

    def test_config_env_flag_shim(self, monkeypatch):
        from bcg_tpu.config import env_flag

        monkeypatch.setenv("BCG_TPU_FINE_SUFFIX", "off")
        assert env_flag("BCG_TPU_FINE_SUFFIX") is False
        monkeypatch.setenv("BCG_TPU_FINE_SUFFIX", "1")
        assert env_flag("BCG_TPU_FINE_SUFFIX") is True

    def test_markdown_table_covers_registry(self):
        table = envflags.markdown_table()
        for name in envflags.REGISTRY:
            assert f"`{name}`" in table

    def test_readme_flag_table_matches_registry(self):
        # The README table is pasted from `python -m
        # bcg_tpu.runtime.envflags` — registering a new flag must force
        # a regeneration, or the "derived from the registry" claim rots.
        readme = open(os.path.join(repo_root(), "README.md")).read()
        assert envflags.markdown_table() in readme, (
            "README env-flag table is stale — re-run "
            "`python -m bcg_tpu.runtime.envflags` and paste the output"
        )
