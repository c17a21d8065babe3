"""Unified observability (bcg_tpu/obs): span tracer + counter registry.

Covers the ISSUE-4 acceptance surface: balanced-span invariant (every B
has an E, nesting valid), cross-thread parent handoff, Chrome-trace
JSON schema, counter ``delta()`` accounting over a scripted FakeEngine
serving run, compile/retrace counters incrementing exactly once per new
shape signature (steady-state decode: zero), and the disabled-tracer
overhead bound against the straggler micro-benchmark scenario.
"""

import json
import threading
import time

import pytest

from bcg_tpu.api import run_simulation
from bcg_tpu.engine.fake import FakeEngine
from bcg_tpu.engine.interface import InferenceEngine
from bcg_tpu.obs import counters as obs_counters, tracer as obs_tracer
from bcg_tpu.obs.tracer import SpanAggregator, Tracer
from bcg_tpu.serve.engine import ServingEngine, run_serving_simulations

DECIDE = {
    "type": "object",
    "properties": {"value": {"type": "integer", "minimum": 0, "maximum": 50}},
}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("BCG_TPU_TRACE", "1")
    monkeypatch.delenv("BCG_TPU_TRACE_OUT", raising=False)
    monkeypatch.delenv("BCG_TPU_TRACE_RING", raising=False)
    obs_tracer.reset()
    yield obs_tracer.get_tracer()
    obs_tracer.reset()


@pytest.fixture
def untraced(monkeypatch):
    monkeypatch.delenv("BCG_TPU_TRACE", raising=False)
    monkeypatch.delenv("BCG_TPU_TRACE_OUT", raising=False)
    obs_tracer.reset()
    yield
    obs_tracer.reset()


def validate_balance(events):
    """Assert the balanced-span invariant — every B closed by an E at
    its thread's stack top — and return {span_id: B-or-X event}."""
    stacks = {}
    spans = {}
    for ev in events:
        ph = ev["ph"]
        if ph == "M":
            continue
        args = ev.get("args", {})
        if ph == "B":
            stacks.setdefault(ev["tid"], []).append(args["span_id"])
            spans[args["span_id"]] = ev
        elif ph == "E":
            stack = stacks.get(ev["tid"])
            assert stack, f"E without an open B on its thread: {ev}"
            assert stack.pop() == args["span_id"], f"unbalanced E: {ev}"
        elif ph == "X":
            assert "dur" in ev, f"X event without dur: {ev}"
            spans[args["span_id"]] = ev
    leftovers = {tid: s for tid, s in stacks.items() if s}
    assert not leftovers, f"B events never closed: {leftovers}"
    return spans


class TestTracer:
    def test_balanced_nested_spans_and_parents(self, traced):
        with obs_tracer.span("outer") as outer:
            with obs_tracer.span("inner"):
                pass
            with pytest.raises(RuntimeError):
                with obs_tracer.span("failing"):
                    raise RuntimeError("boom")
        data = traced.export()
        spans = validate_balance(data["traceEvents"])
        by_name = {ev["name"]: ev for ev in spans.values()}
        assert by_name["inner"]["args"]["parent_id"] == outer.span_id
        assert by_name["failing"]["args"]["parent_id"] == outer.span_id
        assert "parent_id" not in by_name["outer"]["args"]
        # The failing span still closed (its E carries the failure mark).
        failed_ends = [
            ev for ev in data["traceEvents"]
            if ev["ph"] == "E" and ev.get("args", {}).get("failed")
        ]
        assert len(failed_ends) == 1

    def test_cross_thread_parent_handoff(self, traced):
        with obs_tracer.span("request") as handle:
            def worker():
                with obs_tracer.span("device", parent=handle):
                    obs_tracer.complete("queue_wait", 0.002, parent=handle)

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        spans = validate_balance(traced.export()["traceEvents"])
        by_name = {ev["name"]: ev for ev in spans.values()}
        req, dev, qw = (by_name[n] for n in ("request", "device", "queue_wait"))
        assert dev["args"]["parent_id"] == req["args"]["span_id"]
        assert qw["args"]["parent_id"] == req["args"]["span_id"]
        assert dev["tid"] != req["tid"]  # the handoff crossed threads

    def test_ring_buffer_evicts_but_summary_survives(self):
        tracer = Tracer(ring_capacity=32)
        for _ in range(100):
            with tracer.span("tick"):
                pass
        assert len(tracer.events()) <= 32
        assert tracer.summarize()["tick"]["count"] == 100
        # ... and the tracer says how much it dropped: a reader that
        # needs every event of an interval checks this count.
        assert tracer.evicted() == 200 - 32
        assert Tracer().evicted() == 0

    def test_summarize_percentiles(self):
        tracer = Tracer()
        for ms in range(1, 101):
            tracer.complete("op", ms / 1e3)
        row = tracer.summarize()["op"]
        assert row["count"] == 100
        assert abs(row["p50_ms"] - 50) <= 2
        assert abs(row["p95_ms"] - 95) <= 2
        assert row["total_ms"] == pytest.approx(5050, rel=0.01)

    def test_chrome_trace_schema(self, traced, tmp_path):
        with obs_tracer.span("alpha", args={"k": 1}):
            obs_tracer.complete("beta", 0.001)
        path = tmp_path / "trace.json"
        traced.export(str(path))
        data = json.loads(path.read_text())
        assert isinstance(data["traceEvents"], list) and data["traceEvents"]
        for ev in data["traceEvents"]:
            assert ev["ph"] in ("B", "E", "X", "M")
            assert isinstance(ev["name"], str)
            assert "pid" in ev and "tid" in ev
            if ev["ph"] != "M":
                assert isinstance(ev["ts"], (int, float))
        # Thread-name metadata present (Perfetto labels the lanes).
        assert any(ev["ph"] == "M" for ev in data["traceEvents"])
        # Counters ride along so one file is the full observability state.
        assert "counters" in data["otherData"]
        # The epoch's host-clock value places every ts (and the X events
        # no profiler sees) on time.perf_counter(), without _epoch.
        epoch = data["otherData"]["epoch_perf_counter"]
        assert epoch == traced.epoch_perf_counter()
        alpha = next(e for e in data["traceEvents"] if e["name"] == "alpha")
        assert epoch + alpha["ts"] * 1e-6 <= time.perf_counter()
        assert data["otherData"]["evicted_events"] == traced.evicted() == 0

    def test_annotate_rides_the_exit_event(self, traced):
        """What a body learns at its end (a decode loop's steps) lands
        on the innermost open span's E event, beside ``failed``."""
        with obs_tracer.span("outer"):
            with obs_tracer.span("inner", args={"rows": 2}):
                obs_tracer.annotate(steps=7)
                obs_tracer.annotate(tokens=9)
        with pytest.raises(ValueError):
            with obs_tracer.span("boom"):
                obs_tracer.annotate(steps=1)
                raise ValueError("x")
        ends = {e[1]: e[6] for e in traced.events() if e[0] == "E"}
        assert ends == {"inner": {"steps": 7, "tokens": 9}, "outer": None,
                        "boom": {"steps": 1, "failed": True}}
        obs_tracer.annotate(steps=3)  # outside any span: no-op

    def test_complete_lands_under_the_open_span(self, traced):
        with obs_tracer.span("outer") as outer:
            obs_tracer.complete("measured", 0.002)
        obs_tracer.complete("loose", 0.001)
        parents = {e[1]: e[5] for e in traced.events() if e[0] == "X"}
        assert parents == {"measured": outer.span_id, "loose": None}

    def test_span_once_opens_a_name_once_per_thread(self, traced):
        """A phase opened by a recorder around a function, and by the
        function for callers without a recorder, is one span."""
        @obs_tracer.spanned_once("boot.stack")
        def stack():
            return obs_tracer.current().name

        with obs_tracer.span_once("boot.stack"):
            with obs_tracer.span("other"):
                assert stack() == "other"
        assert stack() == "boot.stack"
        names = [e[1] for e in traced.events() if e[0] == "B"]
        assert names == ["boot.stack", "other", "boot.stack"]

    def test_disabled_span_is_shared_noop(self, untraced):
        assert obs_tracer.get_tracer() is None
        cm1 = obs_tracer.span("a")
        cm2 = obs_tracer.span("b")
        assert cm1 is cm2  # the shared no-op singleton — zero allocation
        with cm1 as handle:
            assert handle is None
        assert obs_tracer.current() is None
        obs_tracer.complete("c", 0.1)  # must not raise
        obs_tracer.annotate(steps=1)   # nor this
        assert obs_tracer.span_once("a") is cm1

    def test_trace_out_implies_enabled_and_flush_writes(
        self, monkeypatch, tmp_path
    ):
        out = tmp_path / "exported.json"
        monkeypatch.delenv("BCG_TPU_TRACE", raising=False)
        monkeypatch.setenv("BCG_TPU_TRACE_OUT", str(out))
        obs_tracer.reset()
        try:
            assert obs_tracer.enabled()
            with obs_tracer.span("only"):
                pass
            assert obs_tracer.flush() == str(out)
            data = json.loads(out.read_text())
            assert any(ev["name"] == "only" for ev in data["traceEvents"])
        finally:
            obs_tracer.reset()


class TestCounters:
    def test_counter_gauge_snapshot_delta(self):
        base = obs_counters.snapshot()
        obs_counters.inc("test_obs.widgets")
        obs_counters.inc("test_obs.widgets", 2)
        obs_counters.set_gauge("test_obs.depth", 7)
        snap = obs_counters.snapshot()
        assert snap["test_obs.widgets"] - base.get("test_obs.widgets", 0) == 3
        assert snap["test_obs.depth"] == 7
        d = obs_counters.delta(base)
        assert d["test_obs.widgets"] == 3
        assert "test_obs.depth" not in d  # gauges excluded from delta

    def test_counters_are_monotonic(self):
        with pytest.raises(ValueError):
            obs_counters.inc("test_obs.widgets", -1)

    def test_counter_gauge_name_clash_rejected(self):
        obs_counters.inc("test_obs.clash")
        with pytest.raises(TypeError):
            obs_counters.gauge("test_obs.clash")

    def test_value_read_does_not_create(self):
        assert obs_counters.value("test_obs.never_touched") == 0
        assert "test_obs.never_touched" not in obs_counters.snapshot()


class TestHistogram:
    def test_observe_buckets_and_flat_snapshot(self):
        h = obs_counters.histogram("test_obs.lat_ms", (1, 5, 25))
        for v in (0.5, 3, 3, 30, 1000):
            h.observe(v)
        flat = h.flat()
        # Cumulative buckets; the overflow (+Inf) bucket is .count.
        assert flat["test_obs.lat_ms.bucket.le_1"] == 1
        assert flat["test_obs.lat_ms.bucket.le_5"] == 3
        assert flat["test_obs.lat_ms.bucket.le_25"] == 3
        assert flat["test_obs.lat_ms.count"] == 5
        assert flat["test_obs.lat_ms.sum"] == 1036.5
        assert h.flat().items() <= obs_counters.snapshot().items()

    def test_bucket_derived_quantiles_are_ordered_and_bounded(self):
        h = obs_counters.histogram("test_obs.q_ms", (10, 100, 1000))
        for v in (5, 20, 50, 200, 5000):
            h.observe(v)
        q = h.quantiles()
        assert set(q) == {"p50", "p95", "p99"}
        assert 0 <= q["p50"] <= q["p95"] <= q["p99"] <= 1000
        # Overflow-bucket ranks clamp to the highest FINITE bound.
        assert q["p99"] == 1000

    def test_quantile_interpolates_within_bucket(self):
        h = obs_counters.histogram("test_obs.interp_ms", (0, 10))
        for _ in range(4):
            h.observe(5)
        # All mass in (0, 10]: the median interpolates to mid-bucket.
        assert h.quantile(0.5) == 5.0

    def test_bounds_validation(self):
        with pytest.raises(ValueError):
            obs_counters.histogram("test_obs.bad_desc", (5, 1))
        with pytest.raises(ValueError):
            obs_counters.histogram("test_obs.bad_inf", (1, float("inf")))
        with pytest.raises(ValueError):
            obs_counters.histogram("test_obs.bad_neg", (-1, 5))
        with pytest.raises(ValueError):
            obs_counters.histogram("test_obs.bad_empty", ())

    def test_conflicting_bounds_rejected_same_bounds_ok(self):
        obs_counters.histogram("test_obs.stable_ms", (1, 2))
        assert obs_counters.histogram("test_obs.stable_ms").bounds == (1.0, 2.0)
        assert obs_counters.histogram("test_obs.stable_ms", (1, 2)).bounds \
            == (1.0, 2.0)
        with pytest.raises(ValueError):
            obs_counters.histogram("test_obs.stable_ms", (1, 3))

    def test_undeclared_observe_rejected(self):
        with pytest.raises(KeyError):
            obs_counters.observe("test_obs.never_declared", 1)

    def test_type_clash_rejected(self):
        obs_counters.inc("test_obs.hist_clash")
        with pytest.raises(TypeError):
            obs_counters.histogram("test_obs.hist_clash", (1,))
        obs_counters.histogram("test_obs.hist_first", (1,))
        with pytest.raises(TypeError):
            obs_counters.counter("test_obs.hist_first")
        with pytest.raises(TypeError):
            obs_counters.gauge("test_obs.hist_first")

    def test_delta_carries_counts_not_sum(self):
        before = obs_counters.snapshot()
        h = obs_counters.histogram("test_obs.delta_ms", (1, 10))
        h.observe(0.5)
        h.observe(100)
        moved = obs_counters.delta(before)
        assert moved["test_obs.delta_ms.count"] == 2
        assert moved["test_obs.delta_ms.bucket.le_1"] == 1
        assert "test_obs.delta_ms.sum" not in moved

    def test_raw_baseline_idiom(self):
        """Per-instance share via construction-time raw() baselines —
        the SchedulerStats idiom."""
        h = obs_counters.histogram("test_obs.shared_ms", (1, 10))
        h.observe(0.5)
        base_counts, base_sum, base_n = h.raw()
        h.observe(5)
        counts, total, n = h.raw()
        own = [c - b for c, b in zip(counts, base_counts)]
        assert n - base_n == 1
        assert own == [0, 1, 0]
        assert total - base_sum == 5


class TestServeCounters:
    def test_delta_accounts_scripted_fake_run(self, untraced):
        """Scripted FakeEngine run: exact request/row movement in the
        process-wide registry (the satellite's delta() criterion)."""
        before = obs_counters.snapshot()
        serve = ServingEngine(FakeEngine(seed=0), linger_ms=0)
        for i in range(3):
            out = serve.batch_generate_json(
                [("sys", f"Your current value: {i}", DECIDE)], 0.5, 64
            )
            assert len(out) == 1
        serve.shutdown()
        moved = obs_counters.delta(before)
        assert moved["serve.requests"] == 3
        assert moved["serve.dispatched_rows"] == 3
        assert 1 <= moved["serve.dispatches"] <= 3
        # One queue-wait observation per dispatched request, now in the
        # first-class serve.queue_wait_ms histogram (delta carries its
        # monotonic .count / .bucket.* entries).
        assert moved["serve.queue_wait_ms.count"] == 3
        assert moved["serve.e2e_ms.count"] == 3

    def test_snapshot_latency_breakdown_and_hist_isolation(self, untraced):
        first = ServingEngine(FakeEngine(seed=0), linger_ms=0)
        first.batch_generate_json([("s", "u1", DECIDE)])
        first.batch_generate_json([("s", "u2", DECIDE)])
        snap1 = first.scheduler.snapshot()
        first.shutdown()
        assert sum(snap1["linger_hist_ms"].values()) == 2
        lat = snap1["latency_ms"]
        for stage in ("queue_wait", "admission", "batch_form", "device",
                      "scatter"):
            assert lat[stage]["count"] >= 1, stage
            assert set(lat[stage]) == {
                "count", "total_ms", "mean_ms", "p50_ms", "p95_ms"
            }
        assert snap1["mean_linger_ms"] == lat["queue_wait"]["mean_ms"]
        # A second scheduler's histogram is ITS OWN share of the
        # process-wide counters (construction-time baselines), not the
        # accumulated process total.
        second = ServingEngine(FakeEngine(seed=0), linger_ms=0)
        second.batch_generate_json([("s", "u3", DECIDE)])
        snap2 = second.scheduler.snapshot()
        second.shutdown()
        assert sum(snap2["linger_hist_ms"].values()) == 1


class TestDeviceMemoryMax:
    """Satellite: runtime.metrics._device_memory takes the MAX across
    all devices (device-0-only under-reported multi-chip peaks)."""

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    def test_max_across_devices(self, monkeypatch):
        import jax

        from bcg_tpu.runtime import metrics

        devs = [
            self._Dev({"bytes_in_use": 100, "peak_bytes_in_use": 300}),
            self._Dev({"bytes_in_use": 700, "peak_bytes_in_use": 900}),
            self._Dev({"bytes_in_use": 50, "peak_bytes_in_use": 60}),
        ]
        monkeypatch.setattr(jax, "devices", lambda: devs)
        assert metrics._device_memory() == (700, 900)

    def test_statless_backend_falls_back_to_none(self, monkeypatch):
        import jax

        from bcg_tpu.runtime import metrics

        monkeypatch.setattr(jax, "devices", lambda: [self._Dev(None)])
        assert metrics._device_memory() == (None, None)


class TestAcceptanceTrace:
    """ISSUE-4 acceptance: a traced FakeEngine serving run exports a
    Chrome trace with balanced, correctly-parented spans for at least
    round, decide, queue_wait, batch_form, device, prefill/decode."""

    REQUIRED = {
        "round", "decide", "vote", "serve.request", "serve.queue_wait",
        "serve.batch_form", "serve.device", "serve.scatter",
        "engine.prefill", "engine.decode", "round.summaries", "round.advance",
        "game.setup",
    }

    def _run_games(self):
        def make(i):
            def go(engine):
                return run_simulation(
                    n_agents=3, byzantine_count=0, max_rounds=2,
                    backend="fake", seed=i, engine=engine,
                )
            return go

        outs = run_serving_simulations(
            FakeEngine(seed=0, policy="stubborn"),
            [make(i) for i in range(2)], linger_ms=1,
        )
        assert all(isinstance(o, dict) for o in outs), outs

    def test_traced_serving_game_trace(self, traced, tmp_path):
        self._run_games()
        path = tmp_path / "game.json"
        data = traced.export(str(path))
        events = data["traceEvents"]
        spans = validate_balance(events)
        names = {ev["name"] for ev in spans.values()}
        missing = self.REQUIRED - names
        assert not missing, f"span names missing from trace: {missing}"

        by_id = spans
        def parent_name(ev):
            pid = ev["args"].get("parent_id")
            return by_id[pid]["name"] if pid in by_id else None

        for ev in spans.values():
            if ev["name"] in ("decide", "round.summaries", "round.advance"):
                assert parent_name(ev) == "round"
            if ev["name"] == "serve.queue_wait":
                # Cross-thread handoff: the X event on the scheduler
                # thread points back at the submitter's request span.
                assert parent_name(ev) == "serve.request"
            if ev["name"] == "serve.device":
                assert parent_name(ev) == "serve.request"
            if ev["name"] == "engine.prefill":
                # FakeEngine runs inside the scheduler's device span —
                # thread-local nesting parents it there.
                assert parent_name(ev) == "serve.device"
        # The request spans live on game threads, the device spans on
        # the dispatch thread — the parent links crossed threads.
        req_tids = {ev["tid"] for ev in spans.values()
                    if ev["name"] == "serve.request"}
        dev_tids = {ev["tid"] for ev in spans.values()
                    if ev["name"] == "serve.device"}
        assert req_tids and dev_tids and not (req_tids & dev_tids)
        # summarize(): per-name latency table over the run.
        table = traced.summarize()
        assert table["round"]["count"] == 4  # 2 games x 2 rounds
        assert table["game.setup"]["count"] == 2  # once a game, before its rounds
        assert {"count", "total_ms", "mean_ms", "p50_ms", "p95_ms"} == set(
            table["round"]
        )


class TestProfilerDelegation:
    def test_phases_become_spans_when_traced(self, traced):
        from bcg_tpu.runtime.profiler import SimulationProfiler

        prof = SimulationProfiler()
        with prof.phase("decide"):
            pass
        names = [e[1] for e in traced.events()]
        assert "decide" in names
        assert prof.phase_counts["decide"] == 1

    def test_phases_accumulate_untraced(self, untraced):
        from bcg_tpu.runtime.profiler import SimulationProfiler

        prof = SimulationProfiler()
        with prof.phase("vote"):
            time.sleep(0.005)
        assert prof.phase_counts["vote"] == 1
        assert prof.phase_seconds["vote"] >= 0.005
        assert prof.summary()["phase_counts"]["vote"] == 1


_PROGRESS = ("engine.prefill.positions_", "engine.decode.tokens",
             "engine.decode.row_steps")


class TestRetraceCounters:
    """Compile/retrace accounting: exactly +1 per NEW shape signature,
    zero in steady state (the single most expensive silent regression
    this engine has)."""

    VOTE = {
        "type": "object",
        "properties": {
            "decision": {"type": "string", "enum": ["stop", "continue"]}
        },
        "required": ["decision"],
        "additionalProperties": False,
    }

    @pytest.mark.parametrize("cell", [None, "qwen3-8b-int8"],
                             ids=["defaults", "qwen3-8b-int8"])
    def test_steady_state_zero_then_new_shape_exactly_one(
            self, cell, cell_engine_options):
        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine

        engine = JaxEngine(EngineConfig(**{
            "backend": "jax", "model_name": "bcg-tpu/tiny-test",
            "max_model_len": 512,
            **(cell_engine_options(cell) if cell else {}),
        }))
        # Without a prefix cache (the cell's option) every call
        # allocates its cache anew: engine.cache.* counts those bytes.
        progress = _PROGRESS + (("engine.cache.",) if cell else ())
        prompts = [("sys", "vote please", self.VOTE)]
        engine.batch_generate_json(prompts, temperature=0.0, max_tokens=16)
        after_first = obs_counters.snapshot()
        # Steady state: identical shapes -> ZERO engine.* movement.
        engine.batch_generate_json(prompts, temperature=0.0, max_tokens=16)
        steady = {
            k: v for k, v in obs_counters.delta(after_first).items()
            if k.startswith("engine.")
            # engine.prefill.positions_* are per-call PROGRESS counters
            # (real/padded prefill work) — they legitimately move every
            # call; this test pins the compile/retrace/spec families,
            # where any steady-state movement is a regression.  So are
            # engine.decode.tokens / .row_steps (the loop's yield).
            and not k.startswith(progress)
        }
        assert steady == {}, f"steady-state decode retraced: {steady}"
        # A new token budget is a new decode-loop signature: exactly +1
        # compile AND +1 retrace on the matching counter.
        before_new = obs_counters.snapshot()
        engine.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
        moved = obs_counters.delta(before_new)
        assert moved.get("engine.retrace.decode_loop") == 1, moved
        assert moved.get("engine.compile.decode_loop") == 1, moved
        # ... and once counted, the signature never counts again.
        before_repeat = obs_counters.snapshot()
        engine.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
        repeat = {
            k: v for k, v in obs_counters.delta(before_repeat).items()
            if k.startswith("engine.")
            and not k.startswith(progress)  # per-call progress
        }
        assert repeat == {}, repeat
        engine.shutdown()

    def test_speculative_loop_steady_state_zero_retraces(self):
        """BCG_TPU_SPEC=1 steady state: per-row acceptance counts vary
        call to call (different prompts draft and accept differently)
        but live in the while-loop CARRY, not in any shape — so after
        the first compile, further calls must show ZERO compile/retrace
        movement on every jit entry point."""
        import dataclasses

        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine

        engine = JaxEngine(dataclasses.replace(
            EngineConfig(
                backend="jax", model_name="bcg-tpu/tiny-test",
                max_model_len=512,
            ),
            spec_decode=True,
        ))
        # Prompts chosen to vary acceptance: no echo, heavy echo of the
        # JSON skeleton, and a longer mixed one.
        variants = [
            [("sys", "vote now", self.VOTE)],
            [("sys", 'history: {"decision": "stop"} {"decision": "stop"} '
                     "vote again", self.VOTE)],
            [("sys", "round 5 results were mixed; vote once more please",
              self.VOTE)],
        ]
        engine.batch_generate_json(variants[0], temperature=0.0, max_tokens=32)
        after_first = obs_counters.snapshot()
        accepts = []
        for prompts in variants * 2:
            engine.batch_generate_json(prompts, temperature=0.0, max_tokens=32)
            accepts.append(
                obs_counters.value("engine.spec.accepted")
            )
        moved = {
            k: v for k, v in obs_counters.delta(after_first).items()
            if k.startswith("engine.compile") or k.startswith("engine.retrace")
        }
        assert moved == {}, f"speculative steady-state retraced: {moved}"
        # Non-vacuous: the calls really did accept varying amounts.
        deltas = {b - a for a, b in zip(accepts, accepts[1:])}
        assert len(deltas) > 1, deltas
        engine.shutdown()


def _xplane_spans(trace_dir):
    """``[(name, start_ns, end_ns)]`` of the ``bcg.*`` events on the
    host planes of the capture under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    rows = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("bcg.")
    ]
    # By start, the longer first: a parent before its first child.
    return sorted(rows, key=lambda row: (row[1], -row[2]))


class TestProfilerMirror:
    """One clock: with the tracer on, every span is also a
    ``bcg.<name>`` TraceAnnotation, so a jax.profiler capture carries
    the program's spans beside the device rows."""

    @staticmethod
    def _game():
        return run_simulation(n_agents=3, byzantine_count=0, max_rounds=2,
                              backend="fake", seed=0)

    def test_spans_land_in_the_profilers_trace(self, untraced, monkeypatch,
                                               tmp_path):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            # Tracer off: the shared no-op, and nothing for the profiler.
            assert obs_tracer.span("round") is obs_tracer._NULL_SPAN
            self._game()
            monkeypatch.setenv("BCG_TPU_TRACE", "1")
            obs_tracer.reset()
            self._game()
            events = obs_tracer.get_tracer().events()
        finally:
            jax.profiler.stop_trace()
            obs_tracer.reset()
        mirrored = _xplane_spans(tmp_path)
        # Every B/E pair of the traced game, and none of the untraced
        # game's, under the same name ...
        pairs = [e[1] for e in events if e[0] == "B"]
        assert pairs.count("round") == 2
        assert sorted(n for n, _, _ in mirrored) == sorted(
            "bcg." + n for n in pairs)
        # ... nested the same way: walking the profiler's rows by start
        # gives the tracer's B order, and each row lies inside the row
        # of its tracer parent.
        assert [n for n, _, _ in mirrored] == ["bcg." + n for n in pairs]
        by_id = {e[4]: i for i, e in enumerate(
            e for e in events if e[0] == "B")}
        for i, begin in enumerate(e for e in events if e[0] == "B"):
            if begin[5] is not None:
                _, s, t = mirrored[i]
                _, ps, pt = mirrored[by_id[begin[5]]]
                assert ps <= s and t <= pt, begin[1]


_VOTE = {
    "type": "object",
    "properties": {
        "decision": {"type": "string", "enum": ["stop", "continue"]}
    },
    "required": ["decision"],
    "additionalProperties": False,
}
_ROWS = [("sys", "vote please", _VOTE),
         ("sys", "round 5 was mixed; vote once more please", _VOTE),
         ("sys", "vote", _VOTE)]


def _tiny_engine(**overrides):
    import dataclasses

    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    return JaxEngine(dataclasses.replace(
        EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                     max_model_len=512),
        **overrides,
    ))


def _case_options(options, cell_engine_options):
    """A case's engine options: its own dict, or a benchmark cell's by
    the cell's name."""
    return (cell_engine_options(options) if isinstance(options, str)
            else options)


class TestEngineSpans:
    """The engine call's span table (DESIGN.md "Observability") and the
    two always-on counters of the decode loop's yield."""

    @pytest.mark.parametrize("overrides", [
        {"prefix_caching": False},
        {"prefix_caching": True},
        {"paged_kv": True},
        "qwen3-8b-int8",
    ], ids=["full_prompt", "prefixed", "paged", "cell_options"])
    def test_engine_call_span_table(self, traced, overrides,
                                    cell_engine_options):
        cell = isinstance(overrides, str)
        engine = _tiny_engine(**_case_options(overrides, cell_engine_options))
        try:
            # First call: token DFAs, compiles, prefix entries.
            engine.batch_generate_json(_ROWS, temperature=0.0, max_tokens=48)
            first = len(traced.events())
            engine.batch_generate_json(_ROWS, temperature=0.0, max_tokens=48)
            steps = engine.last_decode_steps
        finally:
            engine.shutdown()
        events = traced.events()[first:]
        begins = [e for e in events if e[0] == "B"]
        assert [e[1] for e in begins] == [
            "engine.call", "engine.format", "engine.provision",
            "engine.guides", "engine.prefill", "engine.tokenize",
            "engine.decode", "engine.account", "engine.detokenize",
            "engine.release", "engine.parse",
        ]
        ids = {e[1]: e[4] for e in begins}
        parents = {e[1]: e[5] for e in begins}
        assert parents["engine.tokenize"] == ids["engine.prefill"]
        for child in ("engine.format", "engine.provision", "engine.guides",
                      "engine.prefill", "engine.decode", "engine.account",
                      "engine.detokenize", "engine.release", "engine.parse"):
            assert parents[child] == ids["engine.call"], child
        assert begins[0][6] == {"rows": 3, "max_tokens": 48}
        ends = {e[1]: e[6] for e in events if e[0] == "E"}
        # Nothing built on a second call; the loop's iterations ride
        # engine.decode's exit, the shapes engine.prefill's.
        assert ends["engine.guides"] == {"schemas": 1, "built": 0}
        assert steps > 0 and ends["engine.decode"] == {"steps": steps}
        if cell:
            # The cell's chunk program, 64 wide here, over the window.
            assert (ends["engine.prefill"]["chunks"]
                    + ends["engine.prefill"]["chunks_skipped"]
                    == -(-ends["engine.prefill"]["prompt_window"] // 64) > 1)
        else:
            assert ends["engine.prefill"]["chunks"] == 1
        assert ends["engine.prefill"]["prompt_window"] > 0
        assert ends["engine.prefill"]["cache_len"] > 0

    def test_untraced_call_opens_only_the_shared_noop(self, untraced,
                                                      monkeypatch):
        """Tracing off, every span of an engine call, the ones that own
        the call's host gaps included, is the shared no-op: no event,
        no annotation, no per-site branch."""
        opened = []
        span = obs_tracer.span

        def counting(name, *args, **kwargs):
            got = span(name, *args, **kwargs)
            opened.append((name, got))
            return got

        engine = _tiny_engine(prefix_caching=False)
        try:
            monkeypatch.setattr(obs_tracer, "span", counting)
            engine.batch_generate_json(_ROWS, temperature=0.0, max_tokens=8)
        finally:
            engine.shutdown()
        assert {"engine.call", "engine.format", "engine.provision",
                "engine.account", "engine.release", "engine.parse"} <= {
                    n for n, _ in opened}
        assert all(got is obs_tracer._NULL_SPAN for _, got in opened)
        assert obs_tracer.get_tracer() is None

    @pytest.mark.parametrize("options", [
        {"decode_fast_forward": False}, {"decode_fast_forward": True},
        # The benchmark's decode_tokens_per_row_step, on its options.
        "qwen3-8b-int8",
    ], ids=["plain_loop", "fast_forward", "cell_options"])
    def test_decode_yield_counters(self, untraced, monkeypatch, options,
                                   cell_engine_options):
        """``engine.decode.tokens`` over ``engine.decode.row_steps``: at
        most 1 on the plain loop (rows sit finished while the longest
        decodes), above 1 under fast-forward on a schema with a forced
        chain — from what the call reads back anyway: the syncs of a
        call stay three."""
        from bcg_tpu.obs import hostsync as obs_hostsync

        monkeypatch.setenv("BCG_TPU_HOSTSYNC", "1")
        obs_hostsync.reset()
        options = _case_options(options, cell_engine_options)
        fast_forward = options["decode_fast_forward"]
        engine = _tiny_engine(**{
            "prefix_caching": False, "guided_compact_json": True, **options})
        try:
            before = obs_counters.snapshot()
            out = engine.batch_generate_json(
                _ROWS, temperature=0.0, max_tokens=48)
            moved = obs_counters.delta(before)
            steps = engine.last_decode_steps
        finally:
            engine.shutdown()
            obs_hostsync.reset()
        assert all(row.get("decision") in ("stop", "continue") for row in out)
        assert moved["engine.hostsync.total"] == 3
        assert moved["engine.decode.row_steps"] == steps * len(_ROWS)
        # Byte tokens, compact JSON: {"decision":"stop"} is 19 of them,
        # {"decision":"continue"} 23.
        assert 19 * len(_ROWS) <= moved["engine.decode.tokens"] \
            <= 23 * len(_ROWS)
        ratio = moved["engine.decode.tokens"] / moved["engine.decode.row_steps"]
        assert (ratio > 1.0) if fast_forward else (0.0 < ratio <= 1.0)

    def test_compile_listener_records_under_the_open_span(self, traced):
        """The program's one jax.monitoring listener: a forced retrace
        is one jax.trace, one jax.lower and one jax.compile interval
        under the span that was open, and adds to engine.jax.*_ms."""
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x: jax.lax.mul(x, x))
        small, large = jnp.ones(3), jnp.ones(4)
        fn(small).block_until_ready()
        before = obs_counters.snapshot()
        first = len(traced.events())
        with obs_tracer.span("retrace") as handle:
            fn(large).block_until_ready()
        fn(large).block_until_ready()   # cached: nothing more
        intervals = [e for e in traced.events()[first:] if e[0] == "X"]
        assert sorted(e[1] for e in intervals) == [
            "jax.compile", "jax.lower", "jax.trace"]
        for e in intervals:
            assert e[5] == handle.span_id and e[7] > 0
        moved = obs_counters.delta(before)
        assert {"engine.jax.trace_ms", "engine.jax.lower_ms",
                "engine.jax.compile_ms"} <= set(moved)


class TestRetrySpans:
    @pytest.mark.parametrize("failing, level, rows", [
        (4, "batch", 4),        # the whole first batch: the batch again
        (1, "sequential", 1),   # one row of four: that agent on its own
    ], ids=["batch", "sequential"])
    def test_retry_ladder_calls_are_spans_and_counted(self, traced, failing,
                                                      level, rows):
        before = obs_counters.snapshot()
        run_simulation(n_agents=4, byzantine_count=0, max_rounds=1,
                       backend="fake", seed=0,
                       engine=FakeEngine(seed=0, fail_first_n_calls=failing))
        moved = obs_counters.delta(before)
        retries = [e for e in traced.events()
                   if e[0] == "B" and e[1] == "round.retry"]
        assert [e[6] for e in retries] == [{"level": level, "rows": rows}]
        decide = next(e for e in traced.events()
                      if e[0] == "B" and e[1] == "decide")
        assert retries[0][5] == decide[4]
        assert moved["game.retry.calls"] == 1
        assert moved["game.retry.rows"] == rows

    def test_a_clean_round_retries_nothing(self, traced):
        before = obs_counters.snapshot()
        run_simulation(n_agents=3, byzantine_count=0, max_rounds=1,
                       backend="fake", seed=0)
        assert not any(e[1] == "round.retry" for e in traced.events())
        assert "game.retry.calls" not in obs_counters.delta(before)


class TestBootSpans:
    def test_boot_phases_are_spans(self, traced):
        """One seam: phase() opens ``boot.<phase>`` (once, where the
        phase's own function opens it too), note() completes it."""
        from bcg_tpu.runtime.metrics import BootPhaseRecorder

        @obs_tracer.spanned_once("boot.stack")
        def stack():
            pass

        boot = BootPhaseRecorder()
        with boot.phase("stack"):
            stack()
        stack()
        with pytest.raises(MemoryError):
            with boot.phase("quantize"):
                raise MemoryError("RESOURCE_EXHAUSTED")
        boot.note("first_compile", 0.25)
        events = traced.events()
        assert [(e[0], e[1]) for e in events] == [
            ("B", "boot.stack"), ("E", "boot.stack"),
            ("B", "boot.stack"), ("E", "boot.stack"),
            ("B", "boot.quantize"), ("E", "boot.quantize"),
            ("X", "boot.first_compile"),
        ]
        assert events[5][6] == {"failed": True}
        assert events[6][7] == pytest.approx(0.25e6)
        assert set(boot.phases) == {"stack", "quantize", "first_compile"}
        assert boot.phases["quantize"]["failed"] is True


class _DelayedCalls(InferenceEngine):
    """Per-call host-side delay in front of a shared proxy (the
    straggler micro-benchmark's workload shape, tests/test_serve.py)."""

    def __init__(self, engine, delay):
        self._engine = engine
        self._delay = delay

    def batch_generate_json(self, prompts, temperature=0.8, max_tokens=512):
        time.sleep(self._delay)
        return self._engine.batch_generate_json(prompts, temperature, max_tokens)

    def generate_json(self, prompt, schema, temperature=0.0, max_tokens=512,
                      system_prompt=None):
        time.sleep(self._delay)
        return self._engine.generate_json(
            prompt, schema, temperature, max_tokens, system_prompt=system_prompt
        )

    def generate(self, prompt, temperature=0.0, max_tokens=256, top_p=1.0,
                 system_prompt=None):
        return self._engine.generate(
            prompt, temperature, max_tokens, top_p, system_prompt=system_prompt
        )

    def batch_generate(self, prompts, temperature=0.0, max_tokens=256,
                       top_p=1.0):
        return self._engine.batch_generate(prompts, temperature, max_tokens,
                                           top_p)

    def shutdown(self):
        pass


class TestDisabledOverhead:
    """ISSUE-4 acceptance: BCG_TPU_TRACE=0 adds <5% wall-clock to the
    straggler micro-benchmark scenario.

    Measured as (spans the scenario emits) x (per-call cost of a
    disabled span), against the scenario's disabled wall-clock — the
    instrumentation is compiled in either way, so the disabled cost IS
    the number of no-op span entries times their unit cost."""

    FAST = 0.005
    GAMES, ROUNDS = 8, 2

    def _run_scenario(self):
        def make(i):
            delay = self.FAST * 10 if i == 0 else self.FAST

            def go(engine):
                return run_simulation(
                    n_agents=4, byzantine_count=0, max_rounds=self.ROUNDS,
                    backend="fake", seed=i,
                    engine=_DelayedCalls(engine, delay),
                )
            return go

        t0 = time.perf_counter()
        outs = run_serving_simulations(
            FakeEngine(seed=0, policy="stubborn"),
            [make(i) for i in range(self.GAMES)],
            max_concurrent=4, linger_ms=1,
        )
        assert all(isinstance(o, dict) for o in outs)
        return time.perf_counter() - t0

    def test_disabled_overhead_bound(self, untraced, monkeypatch):
        # Unit cost of the disabled fast path.
        # Since the spans mirror into the profiler's trace, the path is
        # also: no TraceAnnotation made, no jax import asked for, and
        # an exit annotation that finds no span.
        assert obs_tracer.span("probe") is obs_tracer._NULL_SPAN
        probes = 20_000
        t0 = time.perf_counter()
        for _ in range(probes):
            with obs_tracer.span("probe"):
                obs_tracer.annotate(steps=1)
        per_span = (time.perf_counter() - t0) / probes

        # Scenario wall-clock with the tracer disabled (the shipped
        # default path).
        wall = self._run_scenario()

        # Span volume of the SAME scenario, counted by running it traced.
        monkeypatch.setenv("BCG_TPU_TRACE", "1")
        obs_tracer.reset()
        try:
            self._run_scenario()
            events = obs_tracer.get_tracer().events()
            span_calls = sum(1 for e in events if e[0] in ("B", "X"))
        finally:
            obs_tracer.reset()

        overhead = span_calls * per_span
        assert overhead < 0.05 * wall, (
            f"disabled tracer overhead {overhead * 1e3:.2f}ms is not <5% of "
            f"the {wall * 1e3:.0f}ms straggler scenario "
            f"({span_calls} spans x {per_span * 1e9:.0f}ns)"
        )
