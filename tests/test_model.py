"""Transformer model tests (CPU, tiny spec)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.models import init_params, prefill, decode_step, spec_for_model
from bcg_tpu.models.transformer import init_kv_cache, param_count

SPEC = spec_for_model("bcg-tpu/tiny-test")


@pytest.fixture(scope="module")
def params():
    return init_params(SPEC, jax.random.PRNGKey(0))


def test_param_shapes(params):
    assert params["embed"].shape == (SPEC.vocab_size, SPEC.hidden_size)
    assert len(params["layers"]) == SPEC.num_layers
    l0 = params["layers"][0]
    assert l0["wq"].shape == (SPEC.hidden_size, SPEC.q_size)
    assert l0["wk"].shape == (SPEC.hidden_size, SPEC.kv_size)
    assert l0["w_gate"].shape == (SPEC.hidden_size, SPEC.intermediate_size)
    assert "q_norm" in l0  # qk_norm model
    assert param_count(params) > 0


def test_prefill_shapes_and_finiteness(params):
    B, L, S = 2, 8, 16
    tokens = jnp.arange(B * L).reshape(B, L) % SPEC.vocab_size
    valid = jnp.ones((B, L), bool)
    cache = init_kv_cache(SPEC, B, S)
    logits, cache = prefill(params, SPEC, tokens, valid, cache)
    assert logits.shape == (B, SPEC.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert cache[0]["k"].shape == (B, S, SPEC.num_kv_heads, SPEC.head_dim)


def test_decode_step_matches_prefill(params):
    """Teacher-forcing equivalence: running the prompt token-by-token
    through decode_step must give the same final logits as one prefill."""
    B, L, S = 1, 6, 12
    tokens = jnp.asarray([[3, 7, 11, 13, 17, 19]], dtype=jnp.int32)
    valid = jnp.ones((B, L), bool)

    cache = init_kv_cache(SPEC, B, S)
    ref_logits, _ = prefill(params, SPEC, tokens, valid, cache)

    cache = init_kv_cache(SPEC, B, S)
    valid_mask = np.zeros((B, S), bool)
    logits = None
    for t in range(L):
        valid_mask[:, t] = True
        logits, cache = decode_step(
            params, SPEC,
            tokens[:, t], jnp.int32(t), jnp.asarray([t]),
            cache, jnp.array(valid_mask),   # a copy: the loop writes to it
        )
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(logits), rtol=2e-2, atol=2e-2
    )


def test_left_padding_equivalence(params):
    """A left-padded prompt must produce the same last-token logits as the
    unpadded prompt (pads masked out + positions shifted)."""
    toks = [5, 9, 2, 31]
    B = 1
    unpadded = jnp.asarray([toks], dtype=jnp.int32)
    cache = init_kv_cache(SPEC, B, 8)
    ref, _ = prefill(params, SPEC, unpadded, jnp.ones((1, 4), bool), cache)

    pad = 3
    padded = jnp.asarray([[0] * pad + toks], dtype=jnp.int32)
    valid = jnp.asarray([[False] * pad + [True] * 4])
    cache = init_kv_cache(SPEC, B, 8 + pad)
    out, _ = prefill(params, SPEC, padded, valid, cache)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-2, atol=2e-2)


def test_real_model_specs_registered():
    for name in ("Qwen/Qwen3-8B", "Qwen/Qwen3-14B", "Qwen/Qwen3-32B",
                 "mistralai/Mistral-Small-Instruct-2409"):
        spec = spec_for_model(name)
        assert spec is not None
        assert spec.num_heads % spec.num_kv_heads == 0


def test_param_count_size_classes():
    """param_count drives the bench's size-class gates (kv dtype, scan):
    it must land in the right ballpark for every preset family."""
    billions = {
        "bcg-tpu/bench-1b": (1, 2),
        "bcg-tpu/bench-8b": (7, 10),
        "bcg-tpu/bench-14b": (13, 16),
        "bcg-tpu/bench-32b": (30, 36),
        "Qwen/Qwen3-8B": (7, 10),
        "meta-llama/Meta-Llama-3.1-8B-Instruct": (7, 10),
        "mistralai/Mistral-Small-Instruct-2409": (20, 25),
    }
    for name, (lo, hi) in billions.items():
        spec = spec_for_model(name)
        count = spec.param_count
        assert lo * 1e9 <= count <= hi * 1e9, (name, count)
        # The per-layer matmul unit must agree with the total.
        assert spec.num_layers * spec.matmul_params_per_layer <= count


def test_attn_bias_models():
    """Qwen2-style projection biases: present in the pytree and actually
    applied (nonzero bias must change the logits)."""
    import dataclasses

    spec = dataclasses.replace(SPEC, attn_bias=True)
    params = init_params(spec, jax.random.PRNGKey(0))
    layer0 = params["layers"][0]
    assert layer0["bq"].shape == (spec.q_size,)
    assert layer0["bk"].shape == (spec.kv_size,)

    tokens = jnp.asarray([[3, 7, 11]], dtype=jnp.int32)
    valid = jnp.ones((1, 3), bool)
    base, _ = prefill(params, spec, tokens, valid, init_kv_cache(spec, 1, 4))
    for lay in params["layers"]:
        lay["bq"] = jnp.ones_like(lay["bq"]) * 0.5
    biased, _ = prefill(params, spec, tokens, valid, init_kv_cache(spec, 1, 4))
    assert not np.allclose(np.asarray(base), np.asarray(biased), atol=1e-3)


def test_llama3_rope_scaling():
    """NTK-by-parts: high-frequency dims untouched, low-frequency dims
    stretched by ~factor; tables stay bounded."""
    from bcg_tpu.models.configs import RopeScaling
    from bcg_tpu.models.transformer import rope_table

    positions = jnp.arange(0, 16000, 500)[None, :]
    sc = RopeScaling(factor=8.0, original_max_position=8192)
    cos_p, sin_p = rope_table(positions, 128, 500_000.0)
    cos_s, sin_s = rope_table(positions, 128, 500_000.0, sc)
    # Highest-frequency dim (index 0): wavelength tiny -> identical.
    np.testing.assert_allclose(np.asarray(cos_p[..., 0]), np.asarray(cos_s[..., 0]))
    # Lowest-frequency dim: scaled (angle divided by factor).
    assert not np.allclose(np.asarray(cos_p[..., -1]), np.asarray(cos_s[..., -1]))
    assert np.isfinite(np.asarray(cos_s)).all() and np.isfinite(np.asarray(sin_s)).all()
    # The registered Llama-3.1 spec carries the scaling config.
    spec = spec_for_model("meta-llama/Meta-Llama-3.1-8B-Instruct")
    assert spec.rope_scaling is not None and spec.rope_scaling.factor == 8.0
    assert spec_for_model("Qwen/Qwen2.5-7B-Instruct").attn_bias


class TestCapacityMath:
    """Single-chip fit story as tested arithmetic (16 GB v5e, ~15.75
    usable): which presets board one chip at which quantization —
    weights must leave room for KV cache + activations (~3 GB at game
    shapes), so the serving-fit bar is ~12 GB of weights."""

    USABLE = 15.75 * (1 << 30)
    SERVING_FIT = 12.0 * (1 << 30)

    def _wb(self, name, mode):
        return spec_for_model(name).weight_bytes(mode)

    def test_fit_matrix(self):
        # 1B serves even in bf16.
        assert self._wb("bcg-tpu/bench-1b", None) < self.SERVING_FIT
        # 8B needs quantized weights; int8 fits with room for cache.
        assert self._wb("bcg-tpu/bench-8b", None) > self.SERVING_FIT
        assert self._wb("bcg-tpu/bench-8b", "int8") < self.SERVING_FIT
        # 14B: int8 weights alone nearly fill the chip; int4 serves.
        assert self._wb("bcg-tpu/bench-14b", "int8") > self.SERVING_FIT
        assert self._wb("bcg-tpu/bench-14b", "int4") < self.SERVING_FIT
        # 32B cannot board one chip even at int4 -> tp>=2 territory.
        assert self._wb("bcg-tpu/bench-32b", "int4") > self.USABLE
        # Mistral-Small-22B (the reference's 4th preset): int8 exceeds
        # the chip, int4 boards it — same class as 14B.
        assert self._wb("mistralai/Mistral-Small-Instruct-2409", "int8") \
            > self.SERVING_FIT
        assert self._wb("mistralai/Mistral-Small-Instruct-2409", "int4") \
            < self.SERVING_FIT

    def test_estimates_track_modes(self):
        for name in ("bcg-tpu/bench-1b", "bcg-tpu/bench-8b"):
            bf16 = self._wb(name, None)
            i8 = self._wb(name, "int8")
            i4 = self._wb(name, "int4")
            assert bf16 > i8 > i4
            # int8 halves the matmul bytes (embedding stays bf16).
            assert 0.4 * bf16 < i8 < 0.62 * bf16

    def test_tied_embeddings_not_double_counted_bf16(self):
        import dataclasses

        spec = spec_for_model("bcg-tpu/bench-1b")
        tied = dataclasses.replace(spec, tie_embeddings=True)
        embed_bytes = spec.vocab_size * spec.hidden_size * 2
        # bf16: tied serving shares one table -> exactly one head less.
        assert spec.weight_bytes(None) - tied.weight_bytes(None) == embed_bytes
        # Quantized: tied models materialize an explicit quantized head
        # (models/quantize.py ensure_quantized_head) -> same estimate.
        assert spec.weight_bytes("int8") == tied.weight_bytes("int8")
