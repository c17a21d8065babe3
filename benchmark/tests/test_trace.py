"""The trace reduction, on rows made by hand and on a small trace
recorded on the chip (``data/trace_events.json``: the first events of
each device line and the harness's spans of one traced 8B window)."""

import json
import os

import pytest

from lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_union_gaps_and_clip():
    busy = trace.union([(5, 9), (0, 2), (1, 3), (8, 12)])
    assert busy == [(0, 3), (5, 12)]
    assert trace.total(busy) == 10
    assert trace.gaps(busy, 0, 15) == [(3, 5), (12, 15)]
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def _rows():
    return [
        [HOST, "python3", "bench.round", 100.0, 1000.0],
        [HOST, "python3", "bench.engine_call", 150.0, 600.0],
        [DEV, trace.MODULES_LINE, "jit_prefill(1)", 200.0, 300.0],
        [DEV, trace.OPS_LINE, "fusion.1", 200.0, 100.0],
        [DEV, trace.OPS_LINE, "while.2 while s32[]", 300.0, 200.0],   # shell of the next two
        [DEV, trace.OPS_LINE, "fusion.1", 300.0, 50.0],
        [DEV, trace.OPS_LINE, "custom-call.3", 400.0, 100.0],
        [DEV, trace.OPS_LINE, "fusion.9", 900.0, 400.0],     # runs past the round
        [DEV, "Steps", "0", 0.0, 5000.0],
    ]


def test_reduce_by_hand():
    r = trace.reduce_events(_rows())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    # busy: [200,300] u [300,500] u [900,1100 clipped] = 300 + 200
    assert r["busy_s"] == pytest.approx(500 * ns)
    assert r["ops_s"]["fusion.1"] == pytest.approx(150 * ns)
    assert "while.2 while s32[]" not in r["ops_s"]
    assert r["ops_s"]["fusion.9"] == pytest.approx(200 * ns)
    assert r["modules_s"]["jit_prefill(1)"] == pytest.approx(300 * ns)
    # gaps: [100,200] in the round but before... the call opens at 150:
    # midpoint 150 -> engine_call; [500,900] midpoint 700 -> engine_call
    assert r["idle_s"] == pytest.approx({"engine_call": 500 * ns})
    assert r["breakdown"]["device_ops"][0][0] == "fusion.9"
    assert trace.seconds_matching(r["ops_s"], r"custom-call") == pytest.approx(100 * ns)
    assert trace.seconds_matching(r["ops_s"], r"no-such-kernel") is None


def test_op_name_from_hlo_text():
    text = ("%closed_call.15 = bf16[10,32,512,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(bf16[10,32,512,128]{3,2,1,0:T(8,128)(2,1)} %fusion.1), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(text) == "closed_call.15 custom-call bf16[10,32,512,128]"
    shell = ("%while.48 = (s32[]{:T(128)}, bf16[10,4,4096]{2,0,1:T(8,128)(2,1)S(1)}) "
             "while((s32[]{:T(128)}, bf16[10,4,4096]{2,0,1}) %tuple.3), condition=%c")
    assert trace.op_name(shell) == "while.48 while s32[]"
    assert trace._SHELLS.match(trace.op_name(shell))
    assert trace.op_name("bench.round") == "bench.round"


def test_no_round_span_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce_events([[DEV, trace.OPS_LINE, "fusion.1", 0.0, 1.0]])


def _sweep_union_length(intervals, lo, hi):
    """Another way to the same number: count open intervals at each
    boundary, add up the stretches where the count is above nought."""
    points = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    open_, length, last = 0, 0.0, None
    for t, step in points:
        if open_ > 0:
            length += t - last
        open_, last = open_ + step, t
    return length


def test_recorded_trace():
    """The first 3,000 device operations, the programs and the harness's
    spans of one traced 8B window on the v5e (my chip run, PR 28;
    ``tools/program_record.py --rows``)."""
    rows = json.load(open(os.path.join(HERE, "data", "trace_events.json")))
    r = trace.reduce_events(rows)
    ops = [(s, s + d) for p, l, n, s, d in rows if l == trace.OPS_LINE]
    rounds = [(s, s + d) for p, l, n, s, d in rows if n == trace.ROUND_SPAN]
    lo, hi = min(s for s, _ in rounds), max(e for _, e in rounds)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(_sweep_union_length(ops, lo, hi) * 1e-9, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    # the flash prefill kernel, by name: the plain sum of its events
    flash = [d for p, l, n, s, d in rows
             if l == trace.OPS_LINE and "custom-call bf16[10,32,512,128]" in n]
    assert len(flash) > 10
    got = trace.seconds_matching(r["ops_s"], r"custom-call bf16\[\d+,32,512,128\]")
    assert got == pytest.approx(sum(flash) * 1e-9, rel=1e-9)
    # shells are in the union, not in the by-name sums
    assert any(trace._SHELLS.match(n) for p, l, n, s, d in rows if l == trace.OPS_LINE)
    assert not any(trace._SHELLS.match(n) for n in r["ops_s"])
    # the whole gap before the first operation falls inside the engine call
    assert set(r["idle_s"]) <= {"engine_call", "round", "outside_spans"}
    assert sum(r["idle_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    assert any("unknown" in m or "prefill" in m for m in r["modules_s"])
