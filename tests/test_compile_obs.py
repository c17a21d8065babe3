"""Compile-cost observability (bcg_tpu/obs/compile.py,
BCG_TPU_COMPILE_OBS) + profiler capture windows (BCG_TPU_PROFILE).

The PR's acceptance contract, asserted here:

* flag off => ZERO surface: nothing registered, no threads, Prometheus
  exposition byte-identical to an untouched process (subprocess
  exact-bytes pin, the hostsync idiom);
* a provoked retrace (new shape signature on a warm engine) yields
  exactly ONE structured cause record naming the changed argument
  (``max_new 64→96``), counted under ``engine.retrace_cause.<kind>``
  and streamed as JSONL when the flag value is a path;
* per-entry compile-time histograms (``engine.compile_ms.<entry>``)
  populate at every trace-cache-miss seam, split first-compile vs
  retrace, with the census's AOT lower+compile charged separately;
* the perf_gate ``compile`` scenario is green vs justified baselines,
  its entries resurface when removed, and ``--inject-regression
  compile-off`` fails NAMING the metrics (this file owns the
  ``compile.`` namespace in tests/test_perf_gate.py's
  NAMESPACE_OWNERS);
* ``BCG_TPU_PROFILE`` + ``BCG_TPU_PROFILE_ROUNDS=a-b`` bound one
  jax.profiler window over the selected rounds/dispatches, stamped
  with a fleet-identity manifest.
"""

import importlib.util
import json
import os
import subprocess
import sys
import threading

import pytest

import bench
from bcg_tpu.obs import compile as obs_compile
from bcg_tpu.obs import counters as obs_counters
from bcg_tpu.obs.compile import _parse_flag, _parse_rounds, diff_signature
from bcg_tpu.runtime import metrics as runtime_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "scripts", "perf_gate.py")

DECISION = {
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


def _load_script(name):
    path = os.path.join(REPO, "scripts", name)
    spec = importlib.util.spec_from_file_location(name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------- signature diff
class TestSignatureDiff:
    def test_single_changed_argument_named(self):
        cause = diff_signature(
            (("sig",), 48, 1.0, "xla", "xla"),
            [(("sig",), 32, 1.0, "xla", "xla")],
            names=("guided_sig", "max_new", "top_p", "attn_impl",
                   "sampler_impl"),
        )
        assert cause["arg"] == "max_new"
        assert cause["old"] == 32 and cause["new"] == 48
        assert cause["cause"] == "static_knob"
        assert cause["changed"] == ["max_new"]

    def test_numeric_non_knob_is_shape(self):
        cause = diff_signature(
            ("full", 4, 128, 256), [("full", 3, 128, 256)],
            names=("path", "batch", "prompt_window", "cache_len"),
        )
        assert cause["cause"] == "shape"
        assert cause["arg"] == "batch"

    def test_path_change_classified_path(self):
        cause = diff_signature(
            ("suffix", 3, 64, 0, 256), [("paged", 3, 64, 0, 256)],
            names=("path", "batch", "suffix_window", "prefix_len",
                   "cache_len"),
        )
        assert cause["cause"] == "path"

    def test_dtype_change_classified_dtype(self):
        cause = diff_signature(("x", "int8"), [("x", "bf16")],
                               names=("guided_sig", "kv"))
        assert cause["cause"] == "dtype"

    def test_impl_marker_is_static_knob(self):
        cause = diff_signature(
            (("s",), 32, 1.0, "pallas", "xla"),
            [(("s",), 32, 1.0, "xla", "xla")],
            names=("guided_sig", "max_new", "top_p", "attn_impl",
                   "sampler_impl"),
        )
        assert cause["cause"] == "static_knob"
        assert cause["arg"] == "attn_impl"

    def test_nearest_prior_wins_fewest_diffs(self):
        # Two priors: one differs in 1 position, one in 3 — the diff
        # must anchor on the 1-position neighbor.
        cause = diff_signature(
            ("full", 4, 128, 256),
            [("full", 2, 64, 512), ("full", 4, 128, 192)],
            names=("path", "batch", "prompt_window", "cache_len"),
        )
        assert cause["arg"] == "cache_len"
        assert cause["old"] == 192 and cause["new"] == 256
        assert cause["changed"] == ["cache_len"]

    def test_recency_breaks_ties(self):
        # Both priors differ in exactly one position; the LATER one
        # (most recently compiled) anchors the diff.
        cause = diff_signature(
            ("full", 4, 128, 256),
            [("full", 4, 128, 512), ("full", 4, 128, 192)],
            names=("path", "batch", "prompt_window", "cache_len"),
        )
        assert cause["old"] == 192

    def test_arity_mismatch(self):
        cause = diff_signature(("full", 4, 128, 256),
                               [("suffix", 4, 16, 0, 256)])
        assert cause["cause"] == "arity"
        assert cause["old"] == 5 and cause["new"] == 4

    def test_nested_tuple_recurses(self):
        cause = diff_signature(
            ((("json", 3), 4, 96), 32),
            [((("json", 3), 4, 64), 32)],
            names=("guided_sig", "max_new"),
        )
        assert cause["arg"] == "guided_sig"
        assert cause["cause"] == "shape"

    def test_multiple_changed_args_listed_primary_first(self):
        cause = diff_signature(
            ("full", 8, 256, 512), [("full", 4, 128, 256)],
            names=("path", "batch", "prompt_window", "cache_len"),
        )
        assert cause["arg"] == "batch"
        assert cause["changed"] == ["batch", "prompt_window", "cache_len"]


class TestFlagParsing:
    @pytest.mark.parametrize("raw,expect", [
        (None, (False, None)),
        ("", (False, None)),
        ("0", (False, None)),
        ("off", (False, None)),
        ("1", (True, None)),
        ("true", (True, None)),
        ("/tmp/causes.jsonl", (True, "/tmp/causes.jsonl")),
    ])
    def test_dual_mode_flag(self, raw, expect):
        assert _parse_flag(raw) == expect

    @pytest.mark.parametrize("raw,expect", [
        ("3-5", (3, 5)),
        ("4", (4, 4)),
        (" 2 - 7 ", (2, 7)),
        ("9-3", (3, 9)),  # normalized, never an empty window
    ])
    def test_rounds_parse(self, raw, expect):
        assert _parse_rounds(raw) == expect

    def test_rounds_unparseable_warns_and_defaults(self, capsys):
        assert _parse_rounds("round-two") == (1, 2)
        assert "BCG_TPU_PROFILE_ROUNDS" in capsys.readouterr().err


# ------------------------------------------------------------ zero surface
@pytest.fixture
def unobserved(monkeypatch):
    """Compile observability OFF with a fresh read-once cache."""
    monkeypatch.delenv("BCG_TPU_COMPILE_OBS", raising=False)
    monkeypatch.delenv("BCG_TPU_PROFILE", raising=False)
    obs_compile.reset()
    yield
    obs_compile.reset()


# Worker for the exact-bytes subprocess pin: plays the hermetic game,
# pokes the compile-observer seam directly (twice — the second note is
# a retrace, so an ENABLED observer registers its whole namespace),
# bumps one deterministic non-compile counter (non-vacuous comparison),
# and prints the exposition + live thread names as JSON.
_EXPO_WORKER = """
import json, sys, threading
sys.path.insert(0, sys.argv[1])
from bcg_tpu.api import run_simulation
from bcg_tpu.obs import compile as obs_compile
from bcg_tpu.obs import counters as obs_counters, export as obs_export
out = run_simulation(n_agents=5, byzantine_count=1, max_rounds=6,
                     backend="fake", seed=7)
assert out["metrics"]["total_rounds"] >= 1
obs_compile.note_signature("probe_entry", ("x", 1), [])
obs_compile.note_signature("probe_entry", ("x", 2), [("x", 1)],
                           names=("path", "n"))
with obs_compile.time_block("probe_entry"):
    pass
obs_counters.inc("engine.probe", 3)
print(json.dumps({
    "expo": obs_export.render_prometheus(),
    "threads": sorted(t.name for t in threading.enumerate()),
}))
"""

_COMPILE_MARKERS = ("compile_obs", "compile_ms", "retrace_cause")


class TestZeroSurface:
    def test_disabled_module_is_inert(self, unobserved):
        before = set(obs_counters.snapshot())
        assert obs_compile.observer() is None
        assert not obs_compile.enabled()
        obs_compile.note_signature("probe", ("a",), [])
        with obs_compile.time_block("probe"):
            pass
        with obs_compile.measure_aot("probe"):
            pass
        obs_compile.publish()
        assert obs_compile.summary() is None
        assert obs_compile.brief() is None
        assert obs_compile.cause_records() == []
        new = set(obs_counters.snapshot()) - before
        assert not [n for n in new
                    if any(m in n for m in _COMPILE_MARKERS)], new

    def test_disabled_profile_span_is_shared_noop(self, unobserved):
        cm = obs_compile.profile_span("round", 1)
        assert cm is obs_compile._NULL_CM
        assert obs_compile.profile_dispatch() is obs_compile._NULL_CM

    def test_exposition_exact_bytes_and_threads_vs_subprocess(self):
        """Flag off => the exposition is byte-identical to an untouched
        process and no thread starts; flag on ('1', no sink path) =>
        the ONLY difference is the compile namespace itself, and STILL
        no thread (the JSONL sink thread exists only when the flag
        value is a path)."""
        def run(flag: str = None) -> dict:
            env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
            env.pop("BCG_TPU_COMPILE_OBS", None)
            if flag is not None:
                env["BCG_TPU_COMPILE_OBS"] = flag
            proc = subprocess.run(
                [sys.executable, "-c", _EXPO_WORKER, REPO],
                capture_output=True, text=True, timeout=180, env=env,
                cwd=REPO,
            )
            assert proc.returncode == 0, proc.stderr
            return json.loads(proc.stdout)

        off = run(None)
        on = run("1")
        assert "bcg_engine_probe_total" in off["expo"]  # non-vacuous
        assert not any(m in off["expo"] for m in _COMPILE_MARKERS)
        # The enabled run really surfaced the namespace...
        assert "bcg_engine_compile_obs_cache_entries" in on["expo"]
        assert "bcg_engine_retrace_cause_shape_total" in on["expo"]
        assert "bcg_engine_compile_ms_probe_entry_bucket" in on["expo"]
        # ... and removing it reproduces the untouched bytes exactly.
        kept = [
            line for line in on["expo"].splitlines()
            if not any(m in line for m in _COMPILE_MARKERS)
        ]
        assert "\n".join(kept) + "\n" == off["expo"]
        # Zero new threads, off AND on-without-sink.
        assert off["threads"] == on["threads"]


# ------------------------------------------------- observed engine workload
@pytest.fixture(scope="module")
def workload(tmp_path_factory):
    """One tiny real-engine run with the observer ON and the JSONL sink
    engaged (flag = path): cold call, identical warm repeat, provoked
    retrace (max_tokens 64 -> 96).  Shared module-wide — engine boots
    are the expensive part of this file."""
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    events = str(tmp_path_factory.mktemp("compile-obs") / "causes.jsonl")
    prior = os.environ.get("BCG_TPU_COMPILE_OBS")  # lint: ignore[BCG-ENV-RAW]
    os.environ["BCG_TPU_COMPILE_OBS"] = events
    obs_compile.reset()
    before = obs_counters.snapshot()
    prompts = [("honest agent system prompt", "Round 3: propose a value",
                DECISION)]
    try:
        eng = JaxEngine(EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048,
        ))
        try:
            cold = eng.batch_generate_json(prompts, temperature=0.0,
                                           max_tokens=64)
            warm_before = obs_counters.snapshot()
            eng.batch_generate_json(prompts, temperature=0.0, max_tokens=64)
            warm_moved = obs_counters.delta(warm_before)
            eng.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        finally:
            eng.shutdown()
        causes = obs_compile.cause_records()
        summary = obs_compile.summary()
        brief = obs_compile.brief()
        published = runtime_metrics.LAST_COMPILE_OBS
        moved = obs_counters.delta(before)
        snapshot = obs_counters.snapshot()
    finally:
        if prior is None:
            os.environ.pop("BCG_TPU_COMPILE_OBS", None)
        else:
            os.environ["BCG_TPU_COMPILE_OBS"] = prior
        obs_compile.reset()  # closes + drains the sink
    return {
        "rows": cold, "causes": causes, "summary": summary,
        "brief": brief, "published": published, "moved": moved,
        "warm_moved": warm_moved, "snapshot": snapshot, "events": events,
    }


class TestCompileAccounting:
    def test_rows_valid(self, workload):
        assert all(isinstance(r, dict) and "error" not in r
                   for r in workload["rows"])

    def test_per_entry_histograms_populate(self, workload):
        moved = workload["moved"]
        # Cold + provoked = 2 timed compiles per entry.
        assert moved.get("engine.compile_ms.prefill.count") == 2
        assert moved.get("engine.compile_ms.decode_loop.count") == 2
        assert workload["snapshot"]["engine.compile_ms.prefill.sum"] > 0

    def test_first_vs_retrace_split(self, workload):
        snap = workload["snapshot"]
        assert snap["engine.compile_obs.first_compile_ms"] > 0
        assert snap["engine.compile_obs.retrace_ms"] > 0

    def test_cache_entry_gauge(self, workload):
        # prefill (cold + provoked) + decode_loop (cold + provoked).
        assert workload["snapshot"]["engine.compile_obs.cache_entries"] == 4
        assert workload["brief"]["cache_entries"] == 4

    def test_warm_repeat_observes_nothing(self, workload):
        warm = {
            k: v for k, v in workload["warm_moved"].items()
            if any(m in k for m in _COMPILE_MARKERS)
        }
        assert warm == {}, warm

    def test_summary_per_entry_table(self, workload):
        table = workload["summary"]["compile_ms_by_entry"]
        assert set(table) == {"prefill", "decode_loop"}
        for row in table.values():
            assert row["count"] == 2 and row["total_ms"] > 0

    def test_published_to_last_compile_obs(self, workload):
        pub = workload["published"]
        assert pub is not None
        assert pub["cache_entries"] == 4
        assert "compile_ms_by_entry" in pub


class TestRetraceCause:
    def test_exactly_one_cause_record_per_retrace(self, workload):
        # Provoked max_tokens 64->96 retraces exactly two entries:
        # decode_loop (max_new) and prefill (cache_len) — one record
        # each, and the cause counters agree.
        assert len(workload["causes"]) == 2
        moved = workload["moved"]
        cause_total = sum(
            v for k, v in moved.items()
            if k.startswith("engine.retrace_cause.")
        )
        retrace_total = sum(
            v for k, v in moved.items()
            if k.startswith("engine.retrace.")
        )
        assert cause_total == retrace_total == 2

    def test_decode_loop_cause_names_max_new(self, workload):
        records = [c for c in workload["causes"]
                   if c["entry"] == "decode_loop"]
        assert len(records) == 1
        rec = records[0]
        assert rec["arg"] == "max_new"
        assert rec["old"] == 64 and rec["new"] == 96
        assert rec["cause"] == "static_knob"
        assert rec["changed"] == ["max_new"]

    def test_prefill_cause_names_cache_len(self, workload):
        records = [c for c in workload["causes"] if c["entry"] == "prefill"]
        assert len(records) == 1
        assert records[0]["arg"] == "cache_len"
        assert records[0]["cause"] == "shape"

    def test_attribution_jit_entry_when_untraced(self, workload):
        # Tracing is off in this workload, so the hostsync attribution
        # ladder lands on the jit-entry rung.
        assert {c["span"] for c in workload["causes"]} == {
            "jit_decode_loop", "jit_prefill"
        }

    def test_jsonl_stream_manifest_and_records(self, workload):
        with open(workload["events"]) as f:
            lines = [json.loads(line) for line in f if line.strip()]
        assert lines[0]["event"] == "manifest"
        assert lines[0]["kind"] == "compile"
        assert lines[0]["schema_version"] is not None
        assert "run_id" in lines[0] and "host" in lines[0]
        records = [r for r in lines if r["event"] == "retrace_cause"]
        assert len(records) == 2
        by_entry = {r["entry"]: r for r in records}
        assert by_entry["decode_loop"]["arg"] == "max_new"
        assert by_entry["decode_loop"]["old"] == 64
        assert by_entry["decode_loop"]["new"] == 96


class TestTimingHandoff:
    """The note/dispatch ordering protocol, on a controlled clock —
    regression cover for the stale-stash bug: a retrace that follows
    warm (steady-state) dispatches must time the actual compile, not
    consume the previous warm call's execute time."""

    @pytest.fixture
    def clocked(self, monkeypatch):
        monkeypatch.setenv("BCG_TPU_COMPILE_OBS", "1")
        obs_compile.reset()
        clock = {"t": 0.0}
        monkeypatch.setattr(obs_compile.time, "perf_counter",
                            lambda: clock["t"])
        yield obs_compile.observer(), clock
        obs_compile.reset()

    def test_retrace_after_warm_dispatch_times_the_compile(self, clocked):
        o, clock = clocked
        first_before = obs_counters.value(
            "engine.compile_obs.first_compile_ms")
        retrace_before = obs_counters.value("engine.compile_obs.retrace_ms")
        hist_before = obs_counters.value(
            "engine.compile_ms.handoff_loop.count")
        # Cold: note (pending), then the dispatch pays a 300 ms compile.
        o.note_signature("handoff_loop", ("g", 32), [],
                         names=("guided_sig", "max_new"))
        with o.time_block("handoff_loop"):
            clock["t"] += 0.300
        # Warm steady-state dispatch: 10 ms execute, no note.
        with o.time_block("handoff_loop"):
            clock["t"] += 0.010
        # Retrace: note (pending — must DISCARD the warm stash), then
        # the dispatch pays a 250 ms compile.
        o.note_signature("handoff_loop", ("g", 48), [("g", 32)],
                         names=("guided_sig", "max_new"))
        with o.time_block("handoff_loop"):
            clock["t"] += 0.250
        first = (obs_counters.value("engine.compile_obs.first_compile_ms")
                 - first_before)
        retrace = (obs_counters.value("engine.compile_obs.retrace_ms")
                   - retrace_before)
        timed = (obs_counters.value("engine.compile_ms.handoff_loop.count")
                 - hist_before)
        assert first == pytest.approx(300.0)
        assert retrace == pytest.approx(250.0)  # NOT the warm 10 ms
        assert timed == 2  # the warm dispatch is never observed

    def test_stash_mode_consumes_the_preceding_block(self, clocked):
        o, clock = clocked
        first_before = obs_counters.value(
            "engine.compile_obs.first_compile_ms")
        # Prefill ordering: timed dispatch first, note after ("stash").
        with o.time_block("handoff_prefill"):
            clock["t"] += 0.120
        o.note_signature("handoff_prefill", ("full", 3, 64, 256), [],
                         names=("path", "batch", "prompt_window",
                                "cache_len"),
                         timing="stash")
        first = (obs_counters.value("engine.compile_obs.first_compile_ms")
                 - first_before)
        assert first == pytest.approx(120.0)

    def test_failed_dispatch_clears_pending_without_recording(self, clocked):
        o, clock = clocked
        hist_before = obs_counters.value(
            "engine.compile_ms.handoff_fail.count")
        o.note_signature("handoff_fail", ("a",), [])
        with pytest.raises(RuntimeError):
            with o.time_block("handoff_fail"):
                clock["t"] += 0.5
                raise RuntimeError("dispatch died")
        # A later successful warm dispatch must not inherit the marker.
        with o.time_block("handoff_fail"):
            clock["t"] += 0.010
        timed = (obs_counters.value("engine.compile_ms.handoff_fail.count")
                 - hist_before)
        assert timed == 0


class TestAotSeam:
    def test_census_aot_compile_charged(self, unobserved, monkeypatch,
                                        tmp_path):
        import numpy as np
        import jax

        from bcg_tpu.obs import hlo as obs_hlo

        monkeypatch.setenv("BCG_TPU_COMPILE_OBS", "1")
        obs_compile.reset()
        obs_hlo.enable(True)
        before = obs_counters.snapshot()
        try:
            jitted = jax.jit(lambda x: x + 1)
            obs_hlo.maybe_record("compile_obs_probe", jitted,
                                 (np.ones(4, np.float32),))
        finally:
            obs_hlo.reset()
            obs_compile.reset()
        moved = obs_counters.delta(before)
        # Own histogram name (aot_<entry>), never the serving entry's:
        # the AOT runs inside the entry's first dispatch, so sharing the
        # name would double-count the enclosing time_block's window.
        assert moved.get("engine.compile_ms.aot_compile_obs_probe.count") == 1
        assert moved.get("engine.compile_ms.compile_obs_probe.count") is None
        assert obs_counters.value("engine.compile_obs.aot_ms") > 0


class TestServeSnapshotBlock:
    def test_block_none_when_off(self, unobserved):
        from bcg_tpu.engine.fake import FakeEngine
        from bcg_tpu.serve.scheduler import Scheduler

        sched = Scheduler(FakeEngine(seed=0, policy="consensus"),
                          linger_ms=0, bucket_rows=4)
        try:
            assert sched.snapshot()["compile"] is None
        finally:
            sched.close()

    def test_block_present_when_on(self, monkeypatch):
        from bcg_tpu.engine.fake import FakeEngine
        from bcg_tpu.serve.scheduler import Scheduler

        monkeypatch.setenv("BCG_TPU_COMPILE_OBS", "1")
        obs_compile.reset()
        try:
            obs_compile.note_signature("probe_serve", ("a",), [])
            sched = Scheduler(FakeEngine(seed=0, policy="consensus"),
                              linger_ms=0, bucket_rows=4)
            try:
                block = sched.snapshot()["compile"]
            finally:
                sched.close()
            assert block["cache_entries"] >= 1
            assert "retraces" in block and "causes" in block
        finally:
            obs_compile.reset()


class TestBenchHelper:
    def test_compile_stats_none_when_unpublished(self, unobserved,
                                                 monkeypatch):
        monkeypatch.setattr(runtime_metrics, "LAST_COMPILE_OBS", None)
        assert bench._compile_stats_or_none() is None

    def test_compile_stats_reads_published(self, monkeypatch):
        probe = {"cache_entries": 7}
        monkeypatch.setattr(runtime_metrics, "LAST_COMPILE_OBS", probe)
        assert bench._compile_stats_or_none() is probe

    def test_result_attaches_compile_block(self, monkeypatch, capsys):
        probe = {"cache_entries": 7}
        monkeypatch.setattr(runtime_metrics, "LAST_COMPILE_OBS", probe)
        for name, val in (("BENCH_BACKEND", "fake"), ("BENCH_ROUNDS", "1"),
                          ("BENCH_WARMUP", "1")):
            monkeypatch.setenv(name, val)
        bench.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["extra"]["compile"] == probe

    def test_flags_are_config_overrides(self):
        for flag in ("BCG_TPU_COMPILE_OBS", "BCG_TPU_PROFILE",
                     "BCG_TPU_PROFILE_ROUNDS"):
            assert flag in bench._CONFIG_OVERRIDE_ENVS


class TestProfileWindow:
    """Window selection/ownership logic runs in tier-1 against a
    STUBBED profiler (jax.profiler's cold start/stop costs ~10s of CPU
    — the real capture is the slow-marked end-to-end test below, and
    the verify recipe drives it through the CLI)."""

    @pytest.fixture
    def stubbed_profiler(self, monkeypatch, tmp_path):
        monkeypatch.setenv("BCG_TPU_PROFILE", str(tmp_path / "p"))
        calls = {"started": 0, "stopped": 0}

        def fake_start(state, kind):
            calls["started"] += 1
            calls["owner"] = kind
            return True

        def fake_stop(state):
            calls["stopped"] += 1
            state["active"] = False
            state["done"] = True

        monkeypatch.setattr(obs_compile, "_start_profiler", fake_start)
        monkeypatch.setattr(obs_compile, "_stop_profiler", fake_stop)
        obs_compile.reset()
        yield calls
        obs_compile.reset()

    @pytest.mark.slow
    def test_game_rounds_window_writes_manifest_and_trace(
            self, monkeypatch, tmp_path):
        from bcg_tpu.api import run_simulation

        prof_dir = tmp_path / "profile"
        monkeypatch.setenv("BCG_TPU_PROFILE", str(prof_dir))
        monkeypatch.setenv("BCG_TPU_PROFILE_ROUNDS", "1-2")
        obs_compile.reset()
        try:
            out = run_simulation(n_agents=5, byzantine_count=1,
                                 max_rounds=6, backend="fake", seed=7)
            assert out["metrics"]["total_rounds"] >= 2
            state = obs_compile._profile_cfg()
            assert state["done"] and not state["active"]
            manifest = json.loads(
                (prof_dir / "manifest.json").read_text()
            )
            assert manifest["kind"] == "profile"
            assert manifest["window_kind"] == "round"
            assert manifest["first_index"] == 1
            assert manifest["last_index"] == 2
            assert "run_id" in manifest and "host" in manifest
            # jax.profiler wrote its capture tree next to the manifest.
            captured = [
                os.path.join(root, f)
                for root, _, files in os.walk(prof_dir) for f in files
                if f != "manifest.json"
            ]
            assert captured, "profiler window captured no files"
        finally:
            obs_compile.reset()

    def test_dispatch_window_start_stop(self, monkeypatch,
                                        stubbed_profiler):
        monkeypatch.setenv("BCG_TPU_PROFILE_ROUNDS", "2-3")
        with obs_compile.profile_dispatch():  # index 1: before window
            pass
        assert not obs_compile._profile_cfg()["active"]
        assert stubbed_profiler["started"] == 0
        with obs_compile.profile_dispatch():  # index 2: starts
            assert obs_compile._profile_cfg()["active"]
        with obs_compile.profile_dispatch():  # index 3: stops after
            pass
        state = obs_compile._profile_cfg()
        assert state["done"] and not state["active"]
        assert stubbed_profiler == {"started": 1, "stopped": 1,
                                    "owner": "dispatch"}
        # A closed window never restarts.
        assert obs_compile.profile_dispatch() is obs_compile._NULL_CM

    def test_round_stream_owns_window_and_closes_it(self, monkeypatch,
                                                    stubbed_profiler):
        monkeypatch.setenv("BCG_TPU_PROFILE_ROUNDS", "1-2")
        with obs_compile.profile_span("round", 1):
            pass
        assert obs_compile._profile_cfg()["active"]
        # A competing dispatch stream cannot steal or close the window.
        with obs_compile.profile_dispatch():
            pass
        assert obs_compile._profile_cfg()["active"]
        with obs_compile.profile_span("round", 2):
            pass
        assert stubbed_profiler == {"started": 1, "stopped": 1,
                                    "owner": "round"}

    def test_short_run_window_closed_by_reset(self, monkeypatch,
                                              stubbed_profiler):
        # A run shorter than the window leaves the profiler recording;
        # reset() (standing in for the registered atexit hook) must
        # close it rather than leak a torn trace.
        monkeypatch.setenv("BCG_TPU_PROFILE_ROUNDS", "1-99")
        with obs_compile.profile_span("round", 1):
            pass
        assert obs_compile._profile_cfg()["active"]
        obs_compile.reset()  # must stop the trace without raising
        assert stubbed_profiler["stopped"] == 1
        # The re-read state (same env via monkeypatch) starts idle —
        # the previous window really closed.
        state = obs_compile._profile_cfg()
        assert state is not None and not state["active"]


# ------------------------------------------------------------- perf gate
@pytest.fixture(scope="module")
def gate():
    spec = importlib.util.spec_from_file_location("perf_gate", GATE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.run_compile_scenario()


class TestGate:
    def test_green_at_head(self, gate):
        mod, measured = gate
        findings = mod.check_metrics(measured, mod.load_baseline())
        findings += mod.check_stale(measured, mod.load_baseline(),
                                    ("compile",))
        assert findings == []

    def test_advertised_metrics_measured(self, gate):
        _, measured = gate
        assert set(measured) == {
            "compile.steady_state_retraces",
            "compile.retrace_cause_coverage",
            "compile.compile_cache_entries",
            "compile.error_rows",
        }
        assert measured["compile.steady_state_retraces"] == 0.0
        assert measured["compile.retrace_cause_coverage"] >= 0.95

    def test_every_compile_entry_matched(self, gate):
        mod, measured = gate
        baseline = mod.load_baseline()
        for name in baseline["metrics"]:
            if name.startswith("compile."):
                assert name in measured, f"stale baseline entry {name}"

    def test_removing_entry_resurfaces(self, gate):
        mod, measured = gate
        baseline = json.loads(json.dumps(mod.load_baseline()))
        del baseline["metrics"]["compile.retrace_cause_coverage"]
        findings = mod.check_metrics(measured, baseline)
        assert any("compile.retrace_cause_coverage" in f
                   and "no entry" in f for f in findings)

    def test_compile_off_injection_fails_naming_metrics(self, gate):
        mod, _ = gate
        measured = mod.run_compile_scenario("compile-off")
        findings = mod.check_metrics(measured, mod.load_baseline())
        named = "\n".join(findings)
        assert "compile.retrace_cause_coverage" in named
        assert "compile.compile_cache_entries" in named


# ------------------------------------------------------- compile_report.py
class TestCompileReportScript:
    def test_import_free(self):
        src = open(os.path.join(REPO, "scripts", "compile_report.py")).read()
        assert "bcg_tpu" not in [
            line.split()[1].split(".")[0]
            for line in src.splitlines()
            if line.startswith(("import ", "from "))
        ]

    def test_renders_workload_counters(self, workload, tmp_path):
        mod = _load_script("compile_report.py")
        # The bench-JSON shape: counters under extra.
        payload = {"extra": {"counters": workload["snapshot"]}}
        report = mod.render_report(mod.extract_counters(payload))
        assert "compile time by entry" in report
        assert "decode_loop" in report and "prefill" in report
        assert "retraces by cause" in report
        assert "static_knob" in report
        assert "trace-cache entries" in report

    def test_events_table_names_argument(self, workload):
        mod = _load_script("compile_report.py")
        events = mod.load_events(workload["events"])
        report = mod.render_report(workload["snapshot"], events)
        assert "max_new" in report
        assert "64→96" in report

    def test_cli_on_trace_shape(self, workload, tmp_path):
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps(
            {"traceEvents": [],
             "otherData": {"counters": workload["snapshot"]}}
        ))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "compile_report.py"),
             str(trace), "--events", workload["events"]],
            capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        assert "compile time by entry" in proc.stdout
        assert "max_new" in proc.stdout

    def test_empty_export_says_so(self):
        mod = _load_script("compile_report.py")
        report = mod.render_report({})
        assert "no compile observability" in report
