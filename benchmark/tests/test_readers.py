"""Every per-layer metric of ``BENCHMARK.json`` is read through its own
file and reader, from the recorded trace, a hand-made window and, for
the program's own spans and counters, the record of one traced 8B run
(``data/program_record.json``): the names a reader looks for, and the
cost model, come from the configuration's file, and a configuration
that keeps no such name leaves the metric silent."""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "qwen3-8b-int8.lockstep"


CONFIGS = [os.path.join(ROOT, "benchmark", "configs", "qwen3-8b-int8.json"),
           os.path.join(HERE, "configs", "tiny-named.json")]


def _ctx(config_path=CONFIGS[0]):
    from lib import spans, trace

    config = json.load(open(config_path))
    rows = json.load(open(os.path.join(HERE, "data", "trace_events.json")))
    call = types.SimpleNamespace(
        kind="decide", rows=10, prompt_lens=[2190] * 10, budgets=[300] * 10,
        texts=["x" * 299] * 10, steps=299, prefill_s=5.5, decode_s=12.7)
    return {
        "config": config, "cell": {"name": CELL, "chips": 1},
        "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 14 * 2 ** 30},
        "window": {"seconds": 25.0, "game_rounds": 1, "rows": 20, "decisions": 20},
        "calls": [call], "spans": spans, "trace": trace.reduce_events(rows),
        "boot": {"boot_s": 10.0, "compile_s": 1.0, "rounds_passed_over": 0,
                 "rounds_off_band": 1, "setup_end": 40.0},
        "counters": {"engine.hostsync.total": 6},
    }


@pytest.mark.parametrize("config_path", CONFIGS, ids=["cell", "named"])
def test_every_metric_file_reads(monkeypatch, config_path):
    """Once with the cell's configuration, once with one that names a
    cost model of its own (``tests/stubs/costs/recording.py``): every
    share of a peak is then worked out by that module."""
    import run
    from costs import recording
    from lib import program_spans

    del recording.CALLS[:]

    record = program_spans.from_file(os.path.join(HERE, "data", "program_record.json"))
    monkeypatch.setattr(program_spans, "SOURCE", lambda ctx: record)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spans = _ctx(config_path)["spans"]
    spans.RECORDED[:] = [("bench.round", 0.0, 25.0), ("bench.engine_call", 0.001, 18.0),
                         ("bench.engine_call", 18.0005, 24.9995)]
    try:
        out = run.read_per_layer(bench, CELL, _ctx(config_path))
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
    asked = {name for name, _config in recording.CALLS}
    assert asked == ({"prefill_flops", "decode_flops", "decode_bytes", "flash_prefill_kernel"}
                     if config_path == CONFIGS[1] else set())
    assert out["prefill_positions_real_share"]["value"] == pytest.approx(
        record.counters["engine.prefill.positions_real"]
        / record.counters["engine.prefill.positions_run"])


def test_a_configuration_without_the_name_is_silent():
    from readers import kernel_roofline, program_share

    ctx = _ctx()
    ctx["config"] = dict(ctx["config"], trace_names={})
    assert kernel_roofline.read(ctx, "flash_prefill", "flash_prefill_kernel") is None
    assert program_share.read(ctx, "decode_program", "decode_bytes", "hbm_bytes_per_s") is None
