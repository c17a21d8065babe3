"""The readers of the program's own spans and counters
(``lib/program_spans.py``), on rows made by hand and on the record of one
traced 8B run on the chip (``data/program_record.json``, thinned by
``tools/program_record.py``: of the device's operations only the merged
busy intervals, of the tracer's intervals those of a millisecond and
more)."""

import json
import os
import types

import pytest

from lib import program_spans, trace
from readers import (counter_ratio, idle_unattributed, setup_spans,
                     span_self_mean, span_sum_per)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DEV = "/device:TPU:0"


def _by_hand(**changes):
    """One round of two engine calls, microseconds for nanoseconds."""
    rec = dict(
        host=[
            ["bench.round", 90.0, 920.0],
            ["bcg.round", 100.0, 900.0],
            ["bcg.decide", 100.0, 500.0],
            ["bcg.engine.call", 110.0, 480.0],
            ["bcg.engine.guides", 112.0, 8.0],
            ["bcg.engine.prefill", 120.0, 200.0],
            ["bcg.engine.tokenize", 120.0, 20.0],
            ["bcg.engine.decode", 330.0, 250.0],
            ["bcg.engine.detokenize", 582.0, 6.0],
            ["bcg.vote", 600.0, 400.0],
            ["bcg.engine.call", 620.0, 370.0],
            ["bcg.engine.prefill", 630.0, 160.0],
            ["bcg.engine.tokenize", 630.0, 10.0],
            ["bcg.engine.decode", 800.0, 180.0],
            ["bcg.round", 2000.0, 50.0],        # after the window: not read
        ],
        device=[
            [DEV, trace.MODULES_LINE, "jit_prefill_chunk(1)", 141.0, 178.0],
            [DEV, trace.MODULES_LINE, "jit_loop(2)", 332.0, 240.0],
            [DEV, trace.MODULES_LINE, "jit_prefill_chunk(1)", 641.0, 148.0],
            [DEV, trace.MODULES_LINE, "jit_loop(2)", 801.0, 180.0],   # 1 past its span
            [DEV, trace.OPS_LINE, "busy", 141.0, 178.0],
            [DEV, trace.OPS_LINE, "busy", 332.0, 240.0],
            [DEV, trace.OPS_LINE, "busy", 641.0, 148.0],
            [DEV, trace.OPS_LINE, "busy", 801.0, 180.0],
        ],
        events=[
            ["boot.init_params", 1.0, 3.0, {}],
            ["boot.stack", 3.0, 3.5, {}],
            ["jax.trace", 1.2, 1.4, {"fun": "_init"}],
            ["jax.trace", 5.0, 9.0, {"fun": "loop"}],
            ["jax.trace", 6.0, 7.0, {"fun": "_gumbel"}],    # inside the last
            ["jax.lower", 9.0, 10.0, {"fun": "jit(loop)"}],
            ["round", 4.0, 30.0, {}],
            ["round", 30.5, 55.0, {}],
            ["round", 60.0, 85.0, {}],                      # the window's
            ["jax.trace", 61.0, 62.0, {"fun": "late"}],
        ],
        evicted=0,
        counters={"engine.decode.tokens": 3190, "engine.decode.row_steps": 3200},
        setup_end=30.0,
    )
    rec.update(changes)
    return program_spans.Record(**rec)


@pytest.fixture
def by_hand(monkeypatch):
    def use(**changes):
        monkeypatch.setattr(program_spans, "SOURCE", lambda ctx: _by_hand(**changes))
    use()
    return use


def test_window_and_spans(by_hand):
    rec = _by_hand()
    assert rec.window_ns() == (90.0, 1010.0)
    assert rec.spans("round") == [(100.0, 1000.0)]
    assert rec.spans("engine.call") == [(110.0, 590.0), (620.0, 990.0)]


def test_self_time_and_host_work_per_call(by_hand):
    # the round's 900 less its calls' 480 + 370
    assert span_self_mean.read({}, "round", "engine.call") == pytest.approx(50e-9)
    # guides 8 + tokenize 20 + 10 + detokenize 6, over two calls
    assert span_sum_per.read(
        {}, ["engine.guides", "engine.tokenize", "engine.detokenize"],
        "engine.call") == pytest.approx(22e-9)
    assert counter_ratio.read({}, "engine.decode.tokens",
                              "engine.decode.row_steps") == pytest.approx(3190 / 3200)


def test_idle_goes_to_the_span_that_holds_it(by_hand):
    # the five gaps of the window [90, 1010], cut at the spans' edges;
    # not named: what no span holds ([90,100], [1000,1010]) and what
    # engine.call holds alone ([110,112]; [320,330] and [790,800] between
    # prefill and decode; [580,582], [588,590], [620,630], [981,990])
    idle = 51 + 13 + 69 + 12 + 29
    alone = 2 + 10 + 10 + 2 + 2 + 10 + 9
    assert idle_unattributed.read({}, ["round", "engine.call"]) == \
        pytest.approx(100.0 * (20 + alone) / idle)
    # with nothing held to be a container, only what no span holds
    assert idle_unattributed.read({}, []) == pytest.approx(100.0 * 20 / idle)


def test_one_clock():
    rec = _by_hand()
    assert program_spans.program_time_inside(
        rec, "prefill", "engine.prefill") == pytest.approx(1.0)
    assert program_spans.program_time_inside(
        rec, r"^jit_loop\(", "engine.decode") == pytest.approx(419 / 420)
    assert program_spans.program_time_inside(rec, "no_such_program", "round") is None


def test_set_up_is_a_union_up_to_the_first_rounds_end(by_hand):
    assert setup_spans.read({}, ["boot.init_params", "boot.quantize", "boot.stack"]) \
        == pytest.approx(2.5)
    # [1.2,1.4] + [5,9] holding [6,7] + [9,10]; the window's own is left out
    assert setup_spans.read({}, ["jax.trace", "jax.lower"]) == pytest.approx(5.2)
    # the first round ends with set-up; the second, proving, is not set-up's
    assert setup_spans.read({}, ["round"]) == pytest.approx(26.0)
    assert setup_spans.read({}, ["no.such.span"]) is None


def test_a_ring_that_dropped_events_is_an_error(by_hand):
    by_hand(evicted=3)
    with pytest.raises(RuntimeError, match="dropped 3 event"):
        setup_spans.read({}, ["round"])
    # the window's own spans come from the profiler's trace: still read
    assert span_self_mean.read({}, "round", "engine.call") == pytest.approx(50e-9)


def test_a_program_without_the_mirror_reads_nothing(by_hand):
    """A parent commit: ``bench.*`` spans and device rows, no ``bcg.*``
    span, a tracer without a public epoch, none of the counters."""
    by_hand(host=[["bench.round", 90.0, 920.0]], device=[], events=None,
            counters={"engine.hostsync.total": 12}, setup_end=30.0)
    assert span_self_mean.read({}, "round", "engine.call") is None
    assert span_sum_per.read({}, ["engine.guides"], "engine.call") is None
    assert counter_ratio.read({}, "engine.decode.tokens", "engine.decode.row_steps") is None
    assert idle_unattributed.read({}, ["round", "engine.call"]) is None
    assert setup_spans.read({}, ["round"]) is None


def test_tracer_events_become_host_clock_intervals():
    events = [
        ("B", "engine.decode", 1_000_000.0, 1, 7, None, {"rows": 10}, None),
        ("X", "jax.trace", 1_200_000.0, 1, 8, 7, {"fun": "loop"}, 300_000.0),
        ("E", "engine.decode", 3_000_000.0, 1, 7, None, {"steps": 299}, None),
        ("E", "evicted.begin", 3_500_000.0, 1, 3, None, None, None),
    ]
    assert program_spans.intervals(events, 100.0) == [
        ["jax.trace", pytest.approx(101.2), pytest.approx(101.5), {"fun": "loop"}],
        ["engine.decode", pytest.approx(101.0), pytest.approx(103.0),
         {"rows": 10, "steps": 299}],
    ]


def test_from_run_reads_the_live_tracer(monkeypatch, tmp_path):
    """A run's record: no trace directory here, so no rows; the tracer's
    events through its public surface, placed by its published epoch."""
    from bcg_tpu.obs import tracer as obs_tracer

    monkeypatch.setenv("BCG_TPU_TRACE", "1")
    monkeypatch.delenv("BCG_TPU_TRACE_OUT", raising=False)
    monkeypatch.setattr(program_spans, "TRACE_DIR", str(tmp_path))
    obs_tracer.reset()
    try:
        import time

        t0 = time.perf_counter()
        with obs_tracer.span("round"):
            obs_tracer.complete("jax.trace", 0.001)
        rec = program_spans.from_run({"boot": {"setup_end": time.perf_counter()},
                                      "counters": {"a": 1}})
    finally:
        obs_tracer.reset()
    assert rec.host == [] and rec.device == [] and rec.evicted == 0
    assert rec.counters == {"a": 1}
    assert [e[0] for e in rec.events] == ["jax.trace", "round"]
    assert t0 <= rec.events[1][1] <= rec.events[1][2] <= rec.setup_end
    assert rec.in_setup(["round"]) == [(rec.events[1][1], rec.events[1][2])]


# ------------------------------------------------- the record from the chip

RECORD = os.path.join(HERE, "data", "program_record.json")


@pytest.fixture(scope="module")
def recorded():
    return program_spans.from_file(RECORD)


def test_recorded_clocks_are_one(recorded):
    """At least 99% of the prefill program's device time lies inside
    ``bcg.engine.prefill`` spans and of the decode loop's inside
    ``bcg.engine.decode``: the program's spans are on the device trace's
    clock."""
    names = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "qwen3-8b-int8.json")))["trace_names"]
    assert program_spans.program_time_inside(
        recorded, names["prefill_program"], "engine.prefill") >= 0.99
    assert program_spans.program_time_inside(
        recorded, names["decode_program"], "engine.decode") >= 0.99


def test_recorded_run_reads_every_new_metric(recorded, monkeypatch):
    """Through the metric files, as a run reads them, from the record."""
    import run

    monkeypatch.setattr(program_spans, "SOURCE", lambda ctx: recorded)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    new = ["round_host_s", "engine_host_s_per_call", "decode_tokens_per_row_step",
           "idle_unattributed_pct", "setup_weights_s", "setup_trace_lower_s",
           "setup_rounds_s", "prefill_positions_real_share"]
    only = dict(bench, per_layer=[m for m in bench["per_layer"] if m["name"] in new])
    out = run.read_per_layer(only, "qwen3-8b-int8.lockstep", {})
    assert list(out) == new
    assert 0.0 < out["round_host_s"]["value"] < 0.05
    assert 0.0 < out["engine_host_s_per_call"]["value"] < 0.5
    assert 0.9 < out["decode_tokens_per_row_step"]["value"] < 1.01
    assert 0.0 <= out["idle_unattributed_pct"]["value"] <= 100.0
    assert out["setup_trace_lower_s"]["value"] < out["setup_rounds_s"]["value"]
    assert 0.5 < out["prefill_positions_real_share"]["value"] <= 1.0
    # set-up's rounds are the one round that ends it
    first = min(e[2] for e in recorded.events if e[0] == "round")
    assert recorded.setup_end == first
    assert out["setup_rounds_s"]["value"] == pytest.approx(
        sum(e[2] - e[1] for e in recorded.events if e[0] == "round" and e[2] <= first))
    assert len(recorded.spans("round")) == 2 and len(recorded.spans("engine.call")) == 4
    for m in bench["per_layer"]:
        if m["name"] in new:
            spec = run.metric_file(bench, m["name"])
            if m["source"] == "program_span":
                assert spec["env"]["BCG_TPU_TRACE"] == "1"
            assert m["workloads"] == ["qwen3-8b-int8.lockstep"]
            assert (m["unit"], m["source"], m["layer"], m["moves"]) == \
                (spec["unit"], spec["source"], spec["layer"], spec["moves"])
