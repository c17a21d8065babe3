"""A configuration comes in as files alone: ``tests/configs/tiny-named.json``
names a reference and a cost model of its own (``tests/stubs``, found as
``references.recording`` and ``costs.recording``), states a key of its
own for the spec check, and runs through the harness with no harness
file knowing either module."""

import json
import os
import re
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _named():
    return json.load(open(os.path.join(HERE, "configs", "tiny-named.json")))


def test_no_harness_file_names_a_model():
    """The dense decoder's reference and arithmetic are reached through
    the configuration's ``reference`` and ``costs`` alone."""
    named = re.compile(r"dense_gqa|lib\.reference|import reference")
    for folder in ("", "lib", "readers", "drivers", "tools"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith((".py", ".sh")):
                text = open(os.path.join(BENCH, folder, name)).read()
                assert not named.search(text), os.path.join(folder, name)


def test_the_named_configuration_runs_the_rehearsal():
    import run
    from references import recording

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    traffic = json.load(open(os.path.join(HERE, "traffic", "tiny-lockstep.json")))
    cell = {"name": "tiny-named.tiny-lockstep", "config": "tiny-named",
            "traffic": "tiny-lockstep", "chips": 1}
    del recording.CALLS[:]
    out = run.run_cell(bench, cell, _named(), traffic, 2147404729, 6.0, False, DEVICE)
    assert out["correct"] is True, out["compared"]
    assert recording.CALLS and {c[:2] for c in recording.CALLS} == {("tiny-named", "bf16")}


def test_spec_check_takes_what_the_file_states():
    from lib.system import check_spec

    spec = types.SimpleNamespace(
        name="hybrid", vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=4, head_dim=16, intermediate_size=128, rms_eps=1e-6,
        tie_embeddings=False, layer_types=("linear_attention", "full_attention") * 2,
        linear_key_head_dim=24)
    config = {"name": "hybrid", "vocab_size": 512, "hidden_size": 64,
              "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 4,
              "head_dim": 16, "intermediate_size": 128, "rms_norm_eps": 1e-6,
              "tie_word_embeddings": False,
              "rope_theta": None,          # stated as null: states nothing
              "layer_types": ["linear_attention", "full_attention"] * 2,
              "linear_key_head_dim": 24,
              "spec_keys": {"layer_types": "layer_types",
                            "linear_key_head_dim": "linear_key_head_dim"}}
    check_spec(config, spec)               # the spec has no rope_theta at all
    with pytest.raises(RuntimeError, match="layer_types"):
        check_spec(dict(config, layer_types=["full_attention"] * 4), spec)
    with pytest.raises(RuntimeError, match="linear_key_head_dim=32"):
        check_spec(dict(config, linear_key_head_dim=32), spec)
    with pytest.raises(RuntimeError, match="has no rope_theta"):
        check_spec(dict(config, rope_theta=10000.0), spec)
    with pytest.raises(RuntimeError, match="has no conv_kernel"):
        check_spec(dict(config, linear_conv_kernel_dim=4,
                        spec_keys={"linear_conv_kernel_dim": "conv_kernel"}), spec)
