"""Every per-layer metric of ``BENCHMARK.json`` is read through its own
file and reader, from the recorded trace and a hand-made window: the
names a reader looks for come from the configuration's file, and a
configuration that keeps no such name leaves the metric silent."""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "qwen3-8b-int8.lockstep"


def _ctx():
    from lib import spans, trace

    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", "qwen3-8b-int8.json")))
    rows = json.load(open(os.path.join(HERE, "data", "trace_events.json")))
    call = types.SimpleNamespace(
        kind="decide", rows=10, prompt_lens=[2190] * 10, budgets=[300] * 10,
        texts=["x" * 299] * 10, steps=299, prefill_s=5.5, decode_s=12.7)
    return {
        "config": config, "cell": {"name": CELL, "chips": 1},
        "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 14 * 2 ** 30},
        "window": {"seconds": 25.0, "game_rounds": 1, "rows": 20, "decisions": 20},
        "calls": [call], "spans": spans, "trace": trace.reduce_events(rows),
        "boot": {"boot_s": 10.0, "compile_s": 1.0, "rounds_passed_over": 0},
        "counters": {"engine.hostsync.total": 6},
    }


def test_every_metric_file_reads():
    import run

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spans = _ctx()["spans"]
    spans.RECORDED[:] = [("bench.round", 0.0, 25.0), ("bench.engine_call", 0.001, 18.0),
                         ("bench.engine_call", 18.0005, 24.9995)]
    try:
        out = run.read_per_layer(bench, CELL, _ctx())
    finally:
        spans.RECORDED.clear()
    # the recorded trace ends inside the first prefill: no decode kernel in it,
    # so that roofline has nothing to read and is left out, not reported as 0
    assert set(out) == {m["name"] for m in bench["per_layer"]} - {"decode_attn_roofline"}
    for name in ("flash_prefill_roofline", "prefill_mfu_pct",
                 "decode_hbm_pct", "round_mfu_pct"):
        assert 0.0 < out[name]["value"], name
    assert out["rounds_passed_over"]["value"] == 0
    assert out["host_between_calls_s"]["value"] == pytest.approx(0.002)
    assert out["syncs_per_round"]["value"] == 6


def test_a_configuration_without_the_name_is_silent():
    from readers import kernel_roofline, program_share

    ctx = _ctx()
    ctx["config"] = dict(ctx["config"], trace_names={})
    assert kernel_roofline.read(ctx, "flash_prefill", "flash_prefill_kernel") is None
    assert program_share.read(ctx, "decode_program", "decode_bytes", "hbm_bytes_per_s") is None
