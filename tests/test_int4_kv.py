"""Packed-int4 KV cache (models/quantize.py int4-KV contract +
transformer/paged layouts + engine kv_dtype plumbing).

Layers:

* **Packing contract**: quantize/unpack/dequant roundtrip within the
  half-step bound, low-nibble-first halves, bf16 scales (the layout
  marker ``kv_is_int4`` keys every dispatch on).
* **Cache paths**: dense slab and block pool allocate packed shapes,
  writes quantize through the shared dispatch, reads dequantize
  identically on the slab, the paged gather, and the paged Pallas
  kernel's in-VMEM nibble unpack (interpret mode).
* **Engine**: int4 decisions stay within the established quantization
  tolerance vs bf16 (the int8 suite's idiom), paged int4 (fused
  kernel) is token-identical to dense int4, steady-state retraces stay
  zero for the int4 jit entry keys, and BCG_TPU_KV_DTYPE resolves
  bf16/int8(alias)/int4 over the config field.
* **Capacity, gated**: slot bytes are exactly half int8's, cap_for
  admission and pool auto-sizing come out >= 1.8x at the same
  synthetic HBM budget, and the perf-gate ``int4`` scenario conforms
  to perf_baseline.json with the resurface contract owned here for
  the int4.* namespace.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.engine.jax_engine import JaxEngine
from bcg_tpu.models import init_params, prefill, spec_for_model
from bcg_tpu.models.quantize import (
    dequantize_kv_int4,
    quantize_kv_int4,
    unpack_kv_int4,
)
from bcg_tpu.models.transformer import (
    _cache_attention,
    _dequant_slice,
    _write_cache,
    _xla_attention,
    decode_step,
    init_kv_cache,
    kv_is_int4,
)
from bcg_tpu.obs import counters as obs_counters
from bcg_tpu.ops.paged_attention import (
    PALLAS_INTERPRET,
    init_block_pool,
    paged_decode_attention,
    paged_write,
)

SCHEMA = {
    "type": "object",
    "properties": {
        "decision": {"type": "string", "enum": ["stop", "continue"]},
        "value": {"type": "integer", "minimum": 0, "maximum": 50},
    },
    "required": ["decision", "value"],
    "additionalProperties": False,
}

PROMPTS = [
    ("You are honest agent_1 in a consensus game.",
     "Round 2. agent_2 value: 17. Decide.", SCHEMA),
    ("You are byzantine agent_2 in a consensus game.",
     "Round 2. agent_1 value: 16. Decide.", SCHEMA),
]


def _cfg(**kw):
    return EngineConfig(
        backend="jax", model_name="bcg-tpu/tiny-test", max_model_len=2048,
        **kw,
    )


class TestPackingContract:
    def test_roundtrip_half_step_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 2, 16),
                              jnp.float32) * 5
        packed, scale = quantize_kv_int4(x)
        assert packed.shape == (3, 7, 2, 8) and packed.dtype == jnp.int8
        assert scale.shape == (3, 7, 2) and scale.dtype == jnp.bfloat16
        back = dequantize_kv_int4(packed, scale)
        # Half-step bound against the bf16-ROUNDED scale (what dequant
        # reads): |err| <= scale / 2 per element.
        bound = np.asarray(scale.astype(jnp.float32))[..., None] / 2 + 1e-6
        assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()

    def test_nibble_halves_low_first(self):
        """dims [0, Dh/2) in the low nibble, [Dh/2, Dh) in the high —
        the shared contract the paged kernel's in-VMEM unpack mirrors."""
        x = jnp.asarray(np.arange(-8, 8, dtype=np.float32))[None, :] / 1.0
        packed, scale = quantize_kv_int4(x)
        un = np.asarray(unpack_kv_int4(packed))
        q = np.clip(np.round(np.asarray(x) / np.asarray(
            scale.astype(jnp.float32))[..., None]), -8, 7)
        np.testing.assert_array_equal(un, q.astype(np.int8))

    def test_odd_head_dim_rejected(self):
        with pytest.raises(ValueError, match="even head dim"):
            quantize_kv_int4(jnp.zeros((2, 15)))
        spec = dataclasses.replace(
            spec_for_model("bcg-tpu/tiny-test"), head_dim=15
        )
        with pytest.raises(ValueError, match="even head dim"):
            init_kv_cache(spec, 1, 8, quantized="int4")
        with pytest.raises(ValueError, match="even head dim"):
            init_block_pool(spec, 4, 2, quantized="int4")


class TestCachePaths:
    def test_dense_slab_layout_and_marker(self):
        spec = spec_for_model("bcg-tpu/tiny-test")
        entry = init_kv_cache(spec, 2, 8, quantized="int4")[0]
        assert kv_is_int4(entry)
        assert entry["k"].shape == (2, spec.num_kv_heads, 8,
                                    spec.head_dim // 2)
        assert entry["k_scale"].dtype == jnp.bfloat16
        int8_entry = init_kv_cache(spec, 2, 8, quantized=True)[0]
        assert not kv_is_int4(int8_entry)

    def test_write_then_read_matches_manual_dequant(self):
        spec = dataclasses.replace(
            spec_for_model("bcg-tpu/tiny-test"),
            num_heads=4, num_kv_heads=2, head_dim=16, num_layers=1,
        )
        B, S = 2, 8
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, 16))
        entry = _write_cache(
            init_kv_cache(spec, B, S + 2, quantized="int4")[0],
            k, v, jnp.int32(0),
        )
        got = _dequant_slice(entry, "k", S, jnp.float32)
        want = dequantize_kv_int4(*quantize_kv_int4(k))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)

    def test_dense_attention_matches_dequant_oracle(self):
        spec = dataclasses.replace(
            spec_for_model("bcg-tpu/tiny-test"),
            num_heads=4, num_kv_heads=2, head_dim=16, num_layers=1,
        )
        B, S = 2, 8
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, 16))
        entry = _write_cache(
            init_kv_cache(spec, B, S, quantized="int4")[0],
            k, v, jnp.int32(0),
        )
        q = jax.random.normal(jax.random.PRNGKey(3), (B, 1, 4, 16))
        mask = jnp.ones((B, S), bool)
        out = _cache_attention(q, entry, mask, 0.25, "xla")
        kd = dequantize_kv_int4(*quantize_kv_int4(k)).astype(q.dtype)
        vd = dequantize_kv_int4(*quantize_kv_int4(v)).astype(q.dtype)
        want = _xla_attention(q, kd, vd, mask[:, None, :], 0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_paged_kernel_matches_gather_oracle_int4(self):
        """The fused paged kernel's in-VMEM nibble unpack (interpret
        mode) against the XLA gather+dequant reference — int4's arm of
        the TestPallasKernelParity suite, incl. a non-pow2 GQA group."""
        for H, Hkv, Dh in ((4, 2, 16), (28, 4, 128)):
            spec = dataclasses.replace(
                spec_for_model("bcg-tpu/tiny-test"),
                num_heads=H, num_kv_heads=Hkv, head_dim=Dh, num_layers=1,
            )
            B, bs, nblk = 2, 8, 2
            S = bs * nblk
            pool = init_block_pool(spec, 12, bs, quantized="int4")[0]
            tbl = jnp.asarray(np.stack(
                [np.arange(1, 1 + nblk), np.arange(5, 5 + nblk)]
            ).astype(np.int32))
            ks = jax.random.split(jax.random.PRNGKey(H + Dh), 4)
            entry = paged_write(
                {**pool, "tbl": tbl},
                jax.random.normal(ks[0], (B, S, Hkv, Dh), jnp.float32),
                jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32),
                jnp.int32(0),
            )
            q = jax.random.normal(ks[2], (B, 1, H, Dh), jnp.float32)
            lens = jax.random.randint(ks[3], (B,), 1, S + 1)
            mask = jnp.arange(S)[None, :] < lens[:, None]
            scale = 1.0 / np.sqrt(Dh)
            ref = paged_decode_attention(q, entry, mask, scale, impl="xla")
            out = paged_decode_attention(q, entry, mask, scale,
                                         impl=PALLAS_INTERPRET)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)


class TestEngineInt4:
    def test_decode_logits_close_to_bf16(self):
        """The int8 suite's tolerance idiom at int4's coarser grid:
        logits drift bounded, and the int4 path's best token no further
        below the bf16 best, in bf16 logits, than twice that bound (the
        benchmark's ``greedy_gap``; two argmaxes over a random model's
        flat logits need not agree)."""
        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        B, L = 2, 32
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (B, L), 0, spec.vocab_size
        )
        valid = jnp.ones((B, L), bool)
        outs = []
        for quant in (False, "int4"):
            cache = init_kv_cache(spec, B, L + 4, quantized=quant)
            logits, cache = prefill(params, spec, tokens, valid, cache)
            vm = jnp.zeros((B, L + 4), bool).at[:, : L + 1].set(True)
            tok = jnp.argmax(logits, -1)
            step_logits, _ = decode_step(
                params, spec, tok, jnp.int32(L), jnp.full((B,), L), cache, vm
            )
            outs.append(np.asarray(step_logits))
        # int4's grid is 16x coarser than int8's (15 levels vs 255), so
        # the drift bound scales accordingly.
        drift = 1.2
        assert np.abs(outs[0] - outs[1]).max() < drift
        served = np.take_along_axis(
            outs[0], outs[1].argmax(-1)[:, None], axis=-1
        )[:, 0]
        assert (outs[0].max(-1) - served).max() <= 2 * drift

    @pytest.mark.parametrize("extra", [
        pytest.param({}, id="dense"),
        pytest.param({"paged_kv": True}, id="paged"),
        pytest.param({"paged_kv": True, "paged_kv_impl": "pallas"},
                     id="paged-pallas"),
    ])
    def test_guided_json_valid_and_tolerant(self, extra):
        eng = JaxEngine(_cfg(kv_cache_dtype="int4", **extra))
        try:
            out = eng.batch_generate_json(PROMPTS, temperature=0.0,
                                          max_tokens=48)
        finally:
            eng.shutdown()
        for r in out:
            assert r.get("decision") in ("stop", "continue"), r
            assert 0 <= r.get("value", -1) <= 50, r

    def test_paged_pallas_token_identical_to_dense_int4(self):
        dense = JaxEngine(_cfg(kv_cache_dtype="int4"))
        paged = JaxEngine(_cfg(kv_cache_dtype="int4", paged_kv=True,
                               paged_kv_impl="pallas"))
        try:
            r_d = dense.batch_generate_json(PROMPTS, temperature=0.0,
                                            max_tokens=48)
            r_p = paged.batch_generate_json(PROMPTS, temperature=0.0,
                                            max_tokens=48)
            pool = paged.kv_pool_stats()
        finally:
            dense.shutdown()
            paged.shutdown()
        assert r_d == r_p
        assert pool["kv_dtype"] == "int4"

    def test_spec_decode_composes_with_int4(self):
        """Speculative decoding over an int4 cache: per-row compacted
        scatter writes through the packed layout, greedy outputs match
        the plain int4 loop."""
        plain = JaxEngine(_cfg(kv_cache_dtype="int4"))
        spec_eng = JaxEngine(_cfg(kv_cache_dtype="int4", spec_decode=True))
        try:
            r_plain = plain.batch_generate_json(PROMPTS, temperature=0.0,
                                                max_tokens=48)
            r_spec = spec_eng.batch_generate_json(PROMPTS, temperature=0.0,
                                                  max_tokens=48)
        finally:
            plain.shutdown()
            spec_eng.shutdown()
        assert r_plain == r_spec

    def test_zero_steady_state_retraces_for_int4_entry_keys(self):
        eng = JaxEngine(_cfg(kv_cache_dtype="int4", paged_kv=True))
        try:
            eng.batch_generate_json(PROMPTS, temperature=0.0, max_tokens=48)
            before = obs_counters.snapshot()
            eng.batch_generate_json(PROMPTS, temperature=0.0, max_tokens=48)
            moved = obs_counters.delta(before)
        finally:
            eng.shutdown()
        jit_movement = {
            k: v for k, v in moved.items()
            if k.startswith(("engine.compile.", "engine.retrace."))
        }
        assert jit_movement == {}, jit_movement


class TestKvDtypeSwitch:
    def test_env_flag_overrides_and_aliases(self, monkeypatch):
        for raw, want in (("bf16", "bfloat16"), ("bfloat16", "bfloat16"),
                          ("int8", "int8"), ("int4", "int4")):
            monkeypatch.setenv("BCG_TPU_KV_DTYPE", raw)
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = JaxEngine(_cfg(kv_cache_dtype="bfloat16"))
            try:
                assert eng.kv_dtype == want, raw
                assert eng.sampler_stats()["kv_dtype"] == want
            finally:
                eng.shutdown()
        monkeypatch.delenv("BCG_TPU_KV_DTYPE")

    def test_bad_dtype_rejected(self, monkeypatch):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            JaxEngine(_cfg(kv_cache_dtype="fp8"))
        monkeypatch.setenv("BCG_TPU_KV_DTYPE", "int3")
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            JaxEngine(_cfg())

    def test_slot_bytes_exactly_half_of_int8(self):
        import warnings

        bytes_by = {}
        for dtype in ("int8", "int4"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = JaxEngine(_cfg(kv_cache_dtype=dtype))
            bytes_by[dtype] = eng._kv_slot_bytes
            eng.shutdown()
        assert bytes_by["int8"] == 2 * bytes_by["int4"]

    def test_paged_block_bytes_honest(self):
        """kv_pool/admission snapshots report the PACKED bytes: an int4
        pool's per-block device bytes are half an int8 pool's at the
        same block count, read off the actual leaves."""
        import warnings

        bb = {}
        for dtype in ("int8", "int4"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = JaxEngine(_cfg(kv_cache_dtype=dtype, paged_kv=True,
                                     kv_pool_blocks=64))
            stats = eng.kv_pool_stats()
            bb[dtype] = (eng._paged.block_bytes_dev,
                         stats["free_block_headroom_bytes"])
            eng.shutdown()
        assert bb["int8"][0] == 2 * bb["int4"][0]
        assert bb["int8"][1] == 2 * bb["int4"][1]


# --------------------------------------------------------- gate-backed
SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "perf_gate.py")


@pytest.fixture(scope="module")
def int4_gate_metrics():
    spec = importlib.util.spec_from_file_location("perf_gate", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.run_int4_scenario()


class TestGateBacked:
    def test_row_cap_gain_at_least_1_8x(self, int4_gate_metrics):
        """ISSUE-10 acceptance: cap_for-derived row cap >= 1.8x the
        int8 cap at the same HBM budget, and the paged pool affords the
        same gain in blocks."""
        _, m = int4_gate_metrics
        assert m["int4.row_cap_gain"] >= 1.8
        assert m["int4.pool_blocks_gain"] >= 1.8

    def test_parity_and_validity(self, int4_gate_metrics):
        _, m = int4_gate_metrics
        assert m["int4.paged_parity_mismatches"] == 0.0
        assert m["int4.error_rows"] == 0.0

    def test_metrics_conform_to_perf_baseline(self, int4_gate_metrics):
        mod, m = int4_gate_metrics
        findings = mod.check_metrics(m, mod.load_baseline())
        findings += mod.check_stale(m, mod.load_baseline(), ("int4",))
        assert findings == [], findings

    def test_removing_an_int4_entry_resurfaces_its_finding(
        self, int4_gate_metrics
    ):
        mod, m = int4_gate_metrics
        baseline = mod.load_baseline()
        for removed in m:
            pruned = json.loads(json.dumps(baseline))
            del pruned["metrics"][removed]
            findings = mod.check_metrics(m, pruned)
            assert any(
                removed in f and "no entry" in f for f in findings
            ), (removed, findings)
