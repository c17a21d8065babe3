"""int8 W8A8 weight quantization (models/quantize.py).

Properties tested:
* per-channel dequantization error is bounded;
* a quantized tiny model's logits track the bf16 model closely enough to
  agree on greedy tokens most of the time;
* the quantized engine still produces schema-valid JSON (the automaton
  guarantees structure regardless of weight numerics);
* quantized param pytrees shard over a tp mesh without error.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.engine.jax_engine import JaxEngine
from bcg_tpu.models import init_params, prefill, spec_for_model
from bcg_tpu.models.quantize import dense, is_quantized, quantize_params, quantize_weight
from bcg_tpu.models.transformer import init_kv_cache


class TestQuantizeWeight:
    def test_roundtrip_error_bounded(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
        qw = quantize_weight(w)
        assert qw["q"].dtype == jnp.int8
        assert qw["scale"].shape == (32,)
        deq = qw["q"].astype(jnp.float32) * qw["scale"]
        # Max error per element <= scale/2 (half a quantization step).
        assert float(jnp.max(jnp.abs(deq - w) / qw["scale"])) <= 0.5 + 1e-3

    def test_dense_matches_bf16_matmul(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(k1, (4, 64), jnp.bfloat16)
        w = jax.random.normal(k2, (64, 32), jnp.bfloat16)
        exact = (x @ w).astype(jnp.float32)
        quant = dense(x, quantize_weight(w)).astype(jnp.float32)
        # W8A8 with per-token/per-channel scales: ~1% relative error on
        # well-conditioned gaussian data.
        rel = jnp.linalg.norm(quant - exact) / jnp.linalg.norm(exact)
        assert float(rel) < 0.03

    def test_passthrough_for_bf16(self):
        x = jnp.ones((2, 8), jnp.bfloat16)
        w = jnp.ones((8, 4), jnp.bfloat16)
        assert not is_quantized(w)
        np.testing.assert_array_equal(np.asarray(dense(x, w)), np.asarray(x @ w))


class TestQuantizedModel:
    def test_logits_track_bf16(self):
        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        qparams = quantize_params(params, spec)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, spec.vocab_size)
        valid = jnp.ones((2, 16), bool)
        cache = init_kv_cache(spec, 2, 17)
        qcache = init_kv_cache(spec, 2, 17)
        logits, _ = prefill(params, spec, tokens, valid, cache)
        qlogits, _ = prefill(qparams, spec, tokens, valid, qcache)
        lf = np.asarray(logits, np.float64)
        qf = np.asarray(qlogits, np.float64)
        cos = (lf * qf).sum() / (np.linalg.norm(lf) * np.linalg.norm(qf) + 1e-9)
        assert cos > 0.98

    def test_tied_embeddings_get_quantized_head(self):
        spec = dataclasses.replace(spec_for_model("bcg-tpu/tiny-test"), tie_embeddings=True)
        params = init_params(spec, jax.random.PRNGKey(0))
        assert "lm_head" not in params
        qparams = quantize_params(params, spec)
        assert is_quantized(qparams["lm_head"])
        # bf16 embedding table must survive for token gathers.
        assert qparams["embed"].dtype == jnp.bfloat16


class TestQuantizedEngine:
    @pytest.mark.parametrize("cell", [None, "qwen3-8b-int8"],
                             ids=["int8_weights", "qwen3-8b-int8"])
    def test_guided_json_still_valid(self, cell, cell_engine_options):
        engine = JaxEngine(EngineConfig(**{
            "backend": "jax", "model_name": "bcg-tpu/tiny-test",
            "max_model_len": 1024, "quantization": "int8",
            **(cell_engine_options(cell) if cell else {}),
        }))
        schema = {
            "type": "object",
            "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
            "required": ["decision"],
            "additionalProperties": False,
        }
        out = engine.generate_json("vote now", schema, temperature=0.7, max_tokens=24)
        assert out.get("decision") in ("stop", "continue")
        engine.shutdown()

    def test_rejects_unknown_quantization(self):
        with pytest.raises(ValueError, match="quantization"):
            JaxEngine(EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                                   quantization="fp4"))


class TestQuantizedSharding:
    def test_shards_over_tp_mesh(self):
        from bcg_tpu.parallel.mesh import build_mesh
        from bcg_tpu.parallel.sharding import shard_params

        spec = spec_for_model("bcg-tpu/tiny-test")
        qparams = quantize_params(init_params(spec, jax.random.PRNGKey(0)), spec)
        mesh = build_mesh(tp=2, dp=1, sp=1)
        sharded = shard_params(qparams, spec, mesh)
        layer = sharded["layers"][0]
        # Column-parallel weight: output dim split over tp; its scale too.
        wq = layer["wq"]
        assert wq["q"].sharding.spec == jax.sharding.PartitionSpec(None, "tp")
        assert wq["scale"].sharding.spec == jax.sharding.PartitionSpec("tp")
        # Row-parallel weight: input dim split; scale replicated.
        wo = layer["wo"]
        assert wo["q"].sharding.spec == jax.sharding.PartitionSpec("tp", None)
        assert wo["scale"].sharding.spec in (
            jax.sharding.PartitionSpec(None), jax.sharding.PartitionSpec(),
        )
        # And the sharded quantized model still runs.
        tokens = jnp.zeros((2, 8), jnp.int32)
        valid = jnp.ones((2, 8), bool)
        cache = init_kv_cache(spec, 2, 9)
        logits, _ = prefill(sharded, spec, tokens, valid, cache)
        assert logits.shape == (2, spec.vocab_size)


class TestW8A16Prefill:
    """Experimental BCG_TPU_W8A16_PREFILL row-threshold dispatch:
    at/above the threshold dense() skips activation quantization and
    multiplies the dequantized bf16 weight directly (W8A16)."""

    def test_matches_explicit_dequant(self, monkeypatch):
        import numpy as np

        from bcg_tpu.models.quantize import dense, quantize_weight

        monkeypatch.setenv("BCG_TPU_W8A16_PREFILL", "4")
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((32, 16)) * 0.1, jnp.bfloat16)
        qw = quantize_weight(w)
        x = jnp.asarray(rng.standard_normal((8, 32)) * 0.5, jnp.bfloat16)
        got = dense(x, qw)
        w_bf = (qw["q"].astype(jnp.float32) * qw["scale"]).astype(jnp.bfloat16)
        want = (x.astype(jnp.bfloat16) @ w_bf).astype(x.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_below_threshold_keeps_w8a8(self, monkeypatch):
        import numpy as np

        from bcg_tpu.models.quantize import dense, quantize_weight

        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((32, 16)) * 0.1, jnp.bfloat16)
        qw = quantize_weight(w)
        x = jnp.asarray(rng.standard_normal((2, 32)) * 0.5, jnp.bfloat16)
        monkeypatch.delenv("BCG_TPU_W8A16_PREFILL", raising=False)
        base = np.asarray(dense(x, qw))
        monkeypatch.setenv("BCG_TPU_W8A16_PREFILL", "1000")
        below = np.asarray(dense(x, qw))
        np.testing.assert_array_equal(base, below)
