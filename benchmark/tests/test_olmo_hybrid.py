"""The hybrid configuration's files: its reference against a per-token
NumPy loop, its cost model against hand-worked counts, its file against
the program's spec, and the whole harness over it at tiny size on the
CPU, with no harness file knowing either module."""

import json
import os
import re
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _tiny():
    return json.load(open(os.path.join(HERE, "configs", "tiny-hybrid.json")))


def _published():
    return json.load(open(os.path.join(BENCH, "configs", "olmo-hybrid-7b-int8.json")))


# ------------------------------------------------------------- the reference

def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rms(x, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)


def _numpy_logits(cfg, seed, toks, cols):
    """One row through the architecture in float64, position by position
    where the architecture is recurrent; the weights are the reference's
    own (its recipe is not what this case checks)."""
    import jax
    import jax.numpy as jnp

    from references import olmo_hybrid as ref

    D, F, V, eps = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"], cfg["rms_norm_eps"]
    H, Hl = cfg["num_attention_heads"], cfg["linear_num_value_heads"]
    dk, dv, taps_n = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + sum(ref._KEYS[k] for k in cfg["layer_types"]))
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        mat = lambda key, shape, damp=1.0: np.asarray(  # noqa: E731
            ref._matrix(key, shape, "bf16", damp), np.float64)
        x = mat(keys[0], (V, D))[toks]
        n, at = len(toks), 1
        for kind in cfg["layer_types"]:
            k_ = keys[at: at + ref._KEYS[kind]]
            at += ref._KEYS[kind]
            if kind == "linear_attention":
                u = np.concatenate([x @ mat(k_[0], (D, Hl * dk)), x @ mat(k_[1], (D, Hl * dk)),
                                    x @ mat(k_[2], (D, Hl * dv))], axis=1)
                a = x @ mat(k_[3], (D, Hl), ref._GATE_DAMP)
                b = x @ mat(k_[4], (D, Hl), ref._GATE_DAMP)
                taps = np.asarray(ref._round(
                    jax.random.normal(k_[5], (taps_n, u.shape[1]), jnp.float32) / 2.0, "bf16"), np.float64)
                a_log = np.asarray(ref._round(jnp.log(
                    jax.random.uniform(k_[6], (Hl,), jnp.float32, 1.0, 16.0)), "bf16"), np.float64)
                dt = jax.random.uniform(k_[7], (Hl,), jnp.float32, 0.001, 0.1)
                dt_bias = np.asarray(ref._round(dt + jnp.log(-jnp.expm1(-dt)), "bf16"), np.float64)
                S = np.zeros((Hl, dv, dk))
                o = np.zeros((n, Hl, dv))
                for t in range(n):
                    conv = sum(taps[i] * u[t - (taps_n - 1) + i]
                               for i in range(taps_n) if t - (taps_n - 1) + i >= 0)
                    y = _silu(conv)
                    q = y[: Hl * dk].reshape(Hl, dk)
                    k = y[Hl * dk: 2 * Hl * dk].reshape(Hl, dk)
                    v = y[2 * Hl * dk:].reshape(Hl, dv)
                    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
                    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
                    beta = 2.0 / (1.0 + np.exp(-b[t]))
                    g = -np.exp(a_log) * np.log1p(np.exp(a[t] + dt_bias))
                    for h in range(Hl):
                        S[h] = np.exp(g[h]) * S[h]
                        S[h] += np.outer(beta[h] * (v[h] - S[h] @ k[h]), k[h])
                        o[t, h] = S[h] @ q[h]
                gate = _silu(x @ mat(k_[8], (D, Hl * dv))).reshape(n, Hl, dv)
                y = (_rms(o, eps) * gate).reshape(n, Hl * dv) @ mat(k_[9], (Hl * dv, D))
            else:
                Dh = D // H
                q = _rms(x @ mat(k_[0], (D, D)), eps).reshape(n, H, Dh)
                k = _rms(x @ mat(k_[1], (D, D)), eps).reshape(n, H, Dh)
                v = (x @ mat(k_[2], (D, D))).reshape(n, H, Dh)
                out = np.zeros((n, H, Dh))
                for t in range(n):
                    s = np.einsum("hd,khd->hk", q[t], k[: t + 1]) / np.sqrt(Dh)
                    p = np.exp(s - s.max(-1, keepdims=True))
                    out[t] = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True), v[: t + 1])
                y = out.reshape(n, D) @ mat(k_[3], (D, D))
            x = x + ref._MIXER_NORM[kind] * _rms(y, eps)
            m = k_[-3:]
            mlp = (_silu(x @ mat(m[0], (D, F))) * (x @ mat(m[1], (D, F)))) @ mat(m[2], (F, D))
            x = x + _rms(mlp, eps)
        return ref._FINAL_NORM * _rms(x, eps) @ mat(keys[at], (D, V))[:, :cols]
    finally:
        jax.config.update("jax_threefry_partitionable", prev)


def test_reference_is_the_per_token_loop():
    from references import olmo_hybrid as ref

    cfg = _tiny()
    rng = np.random.default_rng(4)
    toks = rng.integers(0, 256, 40)
    tokens = np.zeros((1, 512), np.int32)
    tokens[0, :40] = toks
    got = ref.logits(cfg, 5, tokens, np.array([40]), 257)
    want = _numpy_logits(cfg, 5, toks, 257)
    # float32 at highest against float64: rounding alone, which this
    # architecture amplifies (decays are exp(-16 softplus(.)) at most, the
    # norms sit on every sublayer's output): 6e-4 read, logits of order 3
    np.testing.assert_allclose(got[0, :40], want, atol=2e-3)


def test_reference_control_and_its_refusals():
    from references import olmo_hybrid as ref

    cfg = _tiny()
    tokens = np.zeros((1, 512), np.int32)
    tokens[0, :30] = np.arange(30) + 60
    sound = ref.logits(cfg, 5, tokens, np.array([30]), 257)
    low = ref.logits(cfg, 5, tokens, np.array([30]), 257, "int4")
    assert np.abs(sound[0, :30] - low[0, :30]).max() > 0.1
    with pytest.raises(ValueError, match="multiple of 512"):
        ref.logits(cfg, 5, tokens[:, :100], np.array([30]), 257)
    with pytest.raises(ValueError, match="unknown weight rounding"):
        ref.logits(cfg, 5, tokens, np.array([30]), 257, "int2")
    with pytest.raises(ValueError, match="equal query and key/value"):
        ref.logits(dict(cfg, num_key_value_heads=2), 5, tokens, np.array([30]), 257)


def test_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "references", "olmo_hybrid.py")).read()
    assert not re.search(r"^\s*(from|import) (bcg_tpu|lib|readers|costs)", text, re.M)


# ---------------------------------------------------------------- the costs

CALL = types.SimpleNamespace(prompt_lens=[10, 20], passes=[3, 5], steps=5)


def test_costs_against_hand_worked_counts():
    """tiny-hybrid: D 64, F 128, 4 heads of 16, 4 delta heads of dk 8 /
    dv 16, 4 taps, three linear layers and one full, vocabulary 512."""
    from costs import olmo_hybrid as costs

    cfg = _tiny()
    # linear: 64 (64 + 128) + 64 64 + 3 64 128 = 40,960 and 2 64 4 = 512 of gates;
    # full: 4 64 64 + 3 64 128 = 40,960
    assert costs.block_matmul_params(cfg) == 3 * (40_960 + 512) + 40_960 == 165_376
    assert costs.head_params(cfg) == 32_768
    # a token: 4 heads 6 8 16 + 2 4 taps 128 channels = 4,096 a layer
    assert costs.delta_rule_flops(cfg, 1) == 3 * 4_096
    # pairs 55 + 210; 4 Dh H = 256 a pair
    assert costs.prefill_attention_flops(cfg, CALL.prompt_lens) == 256 * 265
    assert costs.prefill_flops(cfg, CALL) == \
        2 * 165_376 * 30 + 2 * 32_768 * 2 + 67_840 + 12_288 * 30 == 10_490_112
    # context tokens 3 10 + 6 + 5 20 + 15 = 151
    assert costs.decode_flops(cfg, CALL) == \
        2 * (165_376 + 32_768) * 8 + 256 * 151 + 12_288 * 8 == 3_307_264
    # int8: 196,608 quantised weights, 4 x 576 + 512 channels of f32 scale,
    # 1,536 gate weights in bf16
    assert costs.weight_bytes(cfg, "int8") == 196_608 + 4 * 2_816 + 2 * 1_536 == 210_944
    assert costs.weight_bytes(cfg, "bfloat16") == 2 * (196_608 + 1_536)
    assert costs.kv_bytes_per_token(cfg, "int8") == 2 * (64 + 4 * 4) == 160
    assert costs.kv_bytes_per_token(cfg, "bfloat16") == 256
    # a row: 3 layers x (4 16 8 float32 + 3 x 128 bf16)
    assert costs.state_bytes_per_row(cfg) == 3 * (2_048 + 768) == 8_448
    assert costs.decode_bytes(cfg, CALL) == 5 * 210_944 + 160 * 151 + 2 * 8_448 * 8 == 1_214_048
    assert costs.flash_prefill_kernel(cfg, CALL) == {"flops": 67_840, "bytes": 15_360}
    assert costs.decode_attention_kernel(cfg, CALL) == {
        "flops": 38_656, "bytes": 160 * 151 + 2 * 2 * 64 * 8}
    # a token and head: q k v 2 (8 + 8 + 16), g beta 8, o 32 = 104 bytes;
    # the state 512 bytes in and out, once a program (one: 20 <= 512), row and layer
    assert costs.prefill_programs(cfg, CALL) == 1
    assert costs.gated_delta_prefill_kernel(cfg, CALL) == {
        "flops": 6 * 8 * 16 * 4 * 3 * 30, "bytes": 4 * 3 * (104 * 30 + 2 * 512 * 2)}
    with pytest.raises(ValueError):
        costs.weight_bytes(cfg, "int4")


def test_costs_at_published_widths_match_the_issue():
    from costs import olmo_hybrid as costs

    cfg = _published()
    assert costs.block_matmul_params(cfg) == 24 * 215_516_160 + 8 * 185_794_560
    assert costs.state_bytes_per_row(cfg) == 24 * (30 * 192 * 96 * 4 + 3 * 11_520 * 2)
    assert costs.kv_bytes_per_token(cfg, "int8") == 8 * 2 * 30 * 132
    long = types.SimpleNamespace(prompt_lens=[2_200] * 10, passes=[298] * 10, steps=299)
    assert costs.prefill_programs(cfg, long) == 5
    need = costs.gated_delta_prefill_kernel(cfg, long)
    # memory-bound by the cost model on a v5e (197 TFLOP/s, 819 GB/s)
    assert need["bytes"] / 819e9 > need["flops"] / 197e12


# ------------------------------------------------------- the file, the spec

@pytest.mark.parametrize("config, model", [
    (_published, "bcg-tpu/bench-olmo-hybrid-7b"), (_published, "allenai/Olmo-Hybrid-7B"),
    (_tiny, "bcg-tpu/tiny-hybrid"),
], ids=["served", "published_name", "tiny"])
def test_file_states_what_the_program_runs(config, model):
    from bcg_tpu.models.configs import spec_for_model
    from lib.system import check_spec

    cfg = config()
    spec = spec_for_model(model)
    check_spec(cfg, spec)
    assert set(cfg["spec_keys"]) >= {"layer_types", "linear_key_head_dim", "linear_value_head_dim"}
    with pytest.raises(RuntimeError, match="linear_value_head_dim"):
        check_spec(dict(cfg, linear_value_head_dim=cfg["linear_value_head_dim"] * 2), spec)
    with pytest.raises(RuntimeError, match="layer_types"):
        check_spec(dict(cfg, layer_types=cfg["layer_types"][::-1]), spec)


def test_published_file_is_the_catalog_row_uncut():
    cfg = _published()
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 32
    assert cfg["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert {"rope", "norms", "linear_layer", "weights", "tokenizer", "max_model_len"} <= \
        set(cfg["assumed"])
    # one cell runs it, on the traffic file as it stands, and every
    # per-layer metric lists the cell; the two of this configuration list it alone
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    cell = cfg["name"] + ".lockstep"
    assert [(w["name"], w["traffic"], w["chips"]) for w in bench["workloads"]
            if w["config"] == cfg["name"]] == [(cell, "lockstep", 1)]
    assert all(cell in m["workloads"] for m in bench["per_layer"])
    assert [m["name"] for m in bench["per_layer"] if m["workloads"] == [cell]] == \
        ["gated_delta_prefill_roofline", "linear_state_to_kv_bytes"]


def test_no_harness_file_names_the_hybrid():
    named = re.compile(r"olmo|hybrid|gated_delta|linear_state")
    for folder in ("", "lib", "readers", "drivers", "tools"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith((".py", ".sh")):
                text = open(os.path.join(BENCH, folder, name)).read()
                assert not named.search(text), os.path.join(folder, name)


def test_new_metric_files_read_and_fall_silent():
    """Each new metric through its reader: a value where the trace and
    the counters hold something, nothing (not an error) where they do
    not, as on the parent's program."""
    import importlib

    cfg = _tiny()
    calls = [types.SimpleNamespace(prompt_lens=[600, 700], texts=["ab", "abc"],
                                   budgets=[10, 10], steps=3)]
    ctx = {"config": cfg, "cell": {"chips": 1}, "device": {"kind": "TPU v5 lite"},
           "calls": calls, "counters": {"engine.cache.linear_state_bytes": 17, "engine.cache.kv_bytes": 100},
           "trace": {"ops_s": {"gated_delta_prefill.23 custom-call bf16[10,30,512,192]": 0.5,
                               "closed_call.1 custom-call bf16[10,30,512,128]": 0.25}},
           "boot": {}, "window": {"seconds": 1.0}}
    values = {}
    for name in ("gated_delta_prefill_roofline", "linear_state_to_kv_bytes"):
        spec = json.load(open(os.path.join(BENCH, "metrics", name + ".json")))
        reader = importlib.import_module("readers." + spec["reader"])
        values[name] = reader.read(ctx, **spec["args"])
        bare = dict(ctx, counters={}, trace={"ops_s": {"closed_call.1 custom-call bf16[10,30,512,128]": 0.25}})
        assert reader.read(bare, **spec["args"]) is None
    assert values["linear_state_to_kv_bytes"] == pytest.approx(0.17)
    assert 0 < values["gated_delta_prefill_roofline"] < 100
    # the flash pattern of this file does not take the delta kernel's time
    from lib import trace

    assert trace.seconds_matching(ctx["trace"]["ops_s"], cfg["trace_names"]["flash_prefill"]) == 0.25
    assert trace.seconds_matching(ctx["trace"]["ops_s"], cfg["trace_names"]["decode_attention"]) is None


# ------------------------------------------------------------ the rehearsal

def test_the_hybrid_runs_the_rehearsal():
    import run

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    traffic = json.load(open(os.path.join(HERE, "traffic", "tiny-lockstep.json")))
    cell = {"name": "tiny-hybrid.tiny-lockstep", "config": "tiny-hybrid",
            "traffic": "tiny-lockstep", "chips": 1}
    out = run.run_cell(bench, cell, _tiny(), traffic, 2147404729, 6.0, False, DEVICE)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] % 8 == 0 and out["attempted"] >= 8
