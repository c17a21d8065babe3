"""The hybrid family (``ModelSpec.layer_types``): gated delta-rule
layers beside full attention, recurrent state beside the KV cache.

Everything is held against the benchmark's plain reference of the
architecture (``benchmark/references/olmo_hybrid.py``: float32 at
``highest``, the linear layers as the token-by-token recurrence, no
cache, no import of the program): one reference, the one the chip's
``correct`` uses.  Sizes are ``bcg-tpu/tiny-hybrid``'s (one period of
three linear layers and one full layer, key dim 8 != value dim 16).
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.models import transformer as T
from bcg_tpu.models.configs import (
    FULL_ATTENTION, LARGE_MODEL_PARAMS, LINEAR_ATTENTION, MODEL_SPECS,
    XL_MODEL_PARAMS,
)
from bcg_tpu.models.loader import boot_peak_report, init_random_params_sharded
from bcg_tpu.models.quantize import is_quantized, quantize_leaf_transform
from bcg_tpu.ops import gated_delta

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from references import olmo_hybrid as reference  # noqa: E402

SPEC = MODEL_SPECS["bcg-tpu/tiny-hybrid"]
SEED = 11
# float32 program against the float32 reference, both at ``highest`` on
# the CPU: what is left is summation order (the chunkwise form against
# the recurrence, fused against unfused norms).  Logits are of order 3.
F32_TOL = 2e-4


# what the reference reads of a configuration's file: the benchmark's own
# tiny file of this spec (benchmark/tests holds it to the spec)
REFERENCE_CONFIG = json.load(open(os.path.join(
    BENCH, "tests", "configs", "tiny-hybrid.json")))


@functools.lru_cache(maxsize=None)
def made_params(quantized: bool = False):
    """The recipe's weights from ``SEED``, made once for the file."""
    transform = quantize_leaf_transform(SPEC, "int8") if quantized else None
    return init_random_params_sharded(
        SPEC, jax.random.PRNGKey(SEED), leaf_transform=transform)


@functools.lru_cache(maxsize=None)
def f32_params(stacked: bool = False):
    """The recipe's weights (every leaf rounded to bfloat16) held in
    float32, so that the program's arithmetic is the reference's."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), made_params())
    return T.stack_layer_params(params, spec=SPEC) if stacked else params


def rows(lengths, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def left_padded(toks, L):
    tokens = np.zeros((len(toks), L), np.int32)
    valid = np.zeros((len(toks), L), bool)
    for i, t in enumerate(toks):
        tokens[i, L - len(t):] = t
        valid[i, L - len(t):] = True
    return tokens, valid


def reference_logits(toks, seed: int = SEED, weights: str = "bf16"):
    """[row][position] -> the reference's logits over the vocabulary."""
    n = max(map(len, toks))
    width = -(-n // 512) * 512
    tokens = np.zeros((len(toks), width), np.int32)
    for i, t in enumerate(toks):
        tokens[i, :len(t)] = t
    return reference.logits(REFERENCE_CONFIG, seed, tokens,
                            np.array([len(t) for t in toks]), SPEC.vocab_size, weights)


def chunked_prefill(params, tokens, valid, cache, C, skip_dead=False):
    """The engine's loop over ``prefill_chunk_at`` (fixed history mask,
    traced write slot); with ``skip_dead`` it starts at the first chunk
    that holds a token, as the engine does."""
    B, L = tokens.shape
    live = np.flatnonzero(valid.any(axis=0))
    first = (int(live[0]) // C * C) if skip_dead else 0
    for start in range(first, L, C):
        hist = np.zeros((B, L - C), bool)
        hist[:, :start] = valid[:, :start]
        logits, cache = T.prefill_chunk_at(
            params, SPEC, jnp.asarray(tokens[:, start:start + C]),
            jnp.asarray(valid[:, start:start + C]), cache, jnp.asarray(hist),
            jnp.asarray(valid[:, :start].sum(axis=1), jnp.int32), jnp.int32(start))
    return logits, cache


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


class TestSpec:
    def test_published_counts_by_layer_type(self):
        spec = MODEL_SPECS["allenai/Olmo-Hybrid-7B"]
        per = {kind: sum(i * o for i, o in spec.matmul_shapes(kind).values())
               for kind in (LINEAR_ATTENTION, FULL_ATTENTION)}
        # 3840 x (2880 + 2880 + 5760 + 5760) + 5760 x 3840 + 2 x 3840 x 30
        # + 3 x 3840 x 11008; 4 x 3840^2 + 3 x 3840 x 11008
        assert per == {LINEAR_ATTENTION: 215_516_160, FULL_ATTENTION: 185_794_560}
        assert spec.block_matmul_params == 24 * 215_516_160 + 8 * 185_794_560
        assert spec.param_count == spec.block_matmul_params + 2 * 100_352 * 3840
        assert spec.layer_period == (LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,)
        ragged = dataclasses.replace(
            SPEC, num_layers=5, layer_types=(FULL_ATTENTION,) + SPEC.layer_types)
        assert ragged.layer_period == ragged.layer_types     # one period of five
        assert spec.layers_of(LINEAR_ATTENTION) == 24
        # a 7.4B model: int8 weights and int8 KV, not yet int4 weights
        assert LARGE_MODEL_PARAMS <= spec.param_count < XL_MODEL_PARAMS
        assert 7.7e9 < spec.weight_bytes("int8") < 7.9e9
        assert MODEL_SPECS["bcg-tpu/bench-olmo-hybrid-7b"].layer_types == spec.layer_types

    def test_dense_family_counts_unchanged(self):
        spec = MODEL_SPECS["Qwen/Qwen3-8B"]
        assert spec.matmul_params_per_layer == 192_937_984
        assert spec.param_count == 8_190_427_136
        assert spec.weight_bytes("int8") == 8_818_968_064
        assert not spec.hybrid and spec.layers_of(FULL_ATTENTION) == 36

    @pytest.mark.parametrize("change, match", [
        ({"layer_types": (LINEAR_ATTENTION,) * 2 + (FULL_ATTENTION,)}, "must name 4"),
        ({"layer_types": ("windowed",) * 4}, "must name 4"),
        ({"linear_num_key_heads": 2}, "as many key heads"),
    ], ids=["short", "unknown_kind", "grouped_linear_heads"])
    def test_malformed_layer_types_raise(self, change, match):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(SPEC, **change)

    def test_plan_key_contracts(self):
        plan = T.param_plan(SPEC)
        random = [p for p in plan if p[1] in T.RANDOM_KINDS]
        # embed, 3 x 13 linear leaves, 7 full leaves, head
        assert len(random) == 2 + 3 * 13 + 7
        assert T.plan_keys(SPEC, jax.random.PRNGKey(0), plan).shape[0] == len(random)
        dense = MODEL_SPECS["bcg-tpu/tiny-test"]
        assert T.plan_keys(dense, jax.random.PRNGKey(0)).shape[0] == 4 + 7 * dense.num_layers
        names = [p[0].split(".")[-1] for p in plan if p[0].startswith("layers.0.")]
        assert names == ["lin_wq", "lin_wk", "lin_wv", "lin_wa", "lin_wb", "lin_conv",
                         "lin_a_log", "lin_dt_bias", "lin_wg", "lin_out_norm", "lin_wo",
                         "attn_norm", "mlp_norm", "w_gate", "w_up", "w_down"]

    def test_eager_and_born_sharded_init_agree_on_the_tree(self):
        eager = T.init_params(SPEC, jax.random.PRNGKey(1))
        born = made_params()
        assert jax.tree.structure(eager) == jax.tree.structure(born)
        assert [a.shape for a in jax.tree.leaves(eager)] == \
            [a.shape for a in jax.tree.leaves(born)]
        # the recipe's ranges: beta can pass 1, decays cover long and short memory
        layer = born["layers"][0]
        a = np.exp(np.asarray(layer["lin_a_log"], np.float32))
        assert a.min() >= 0.99 and a.max() <= 16.1
        dt = np.log1p(np.exp(np.asarray(layer["lin_dt_bias"], np.float32)))
        assert dt.min() > 5e-4 and dt.max() < 0.11

    def test_quantize_transform_takes_the_five_projections(self):
        params = made_params(quantized=True)
        lin, full = params["layers"][0], params["layers"][3]
        quantized = {k for k, v in lin.items() if is_quantized(v)}
        assert quantized == {"lin_wq", "lin_wk", "lin_wv", "lin_wg", "lin_wo",
                             "w_gate", "w_up", "w_down"}
        assert {k for k, v in full.items() if is_quantized(v)} == \
            {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
        assert is_quantized(params["lm_head"])

    def test_boot_peak_report_counts_by_layer_type(self):
        report = boot_peak_report(SPEC, quantization="int8")
        params = made_params(quantized=True)
        held = sum(a.nbytes for a in jax.tree.leaves(params))
        assert report["final_bytes_per_device"] == held

    def test_no_checkpoint_loader(self):
        from bcg_tpu.models.loader import load_checkpoint_params

        with pytest.raises(ValueError, match="no checkpoint loader"):
            load_checkpoint_params(SPEC, "bcg-tpu/tiny-hybrid")


class TestKernel:
    """The chunkwise form against the plain recurrence (the reference's
    own ``recurrence``), on data that holds what breaks a chunkwise
    form: write strengths above 1, decays near 0 and near 1."""

    @pytest.fixture(scope="class")
    def data(self):
        B, Tn, H, dk, dv = 2, 150, 3, 8, 16     # 150: the last chunk is padded
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
        q = unit(jax.random.normal(ks[0], (B, Tn, H, dk))) * dk ** -0.5
        k = unit(jax.random.normal(ks[1], (B, Tn, H, dk)) + 1.0)   # correlated keys
        v = jax.random.normal(ks[2], (B, Tn, H, dv))
        g = -jnp.exp(jax.random.uniform(ks[3], (B, Tn, H), minval=-8.0, maxval=3.0))
        beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, Tn, H)) + 1)
        S0 = jax.random.normal(ks[5], (B, H, dv, dk))
        assert float(beta.max()) > 1.9 and float(jnp.exp(g).max()) > 0.99 \
            and float(jnp.exp(g).min()) < 1e-6
        want = [reference.recurrence(q[b], k[b], v[b], g[b], beta[b], S0[b])
                for b in range(B)]
        return (q, k, v, g, beta, S0), want

    @pytest.mark.parametrize("impl", [gated_delta.PALLAS_INTERPRET, gated_delta.XLA])
    def test_chunkwise_matches_recurrence(self, data, impl):
        args, want = data
        o, S = gated_delta.gated_delta_prefill(*args, impl=impl)
        for b, (o_ref, S_ref) in enumerate(want):
            # float32 throughout; the triangular solve of a 64-token
            # chunk amplifies rounding by a few units
            np.testing.assert_allclose(o[b], o_ref, atol=2e-5)
            np.testing.assert_allclose(S[b], S_ref, atol=5e-5)

    def test_identical_keys_full_strength_stays_stable(self, data):
        (q, k, v, g, beta, S0), _ = data
        k = jnp.broadcast_to(k[:, :1], k.shape)
        g, beta = jnp.full_like(g, -1e-3), jnp.full_like(beta, 1.95)
        o, S = gated_delta.gated_delta_prefill(q, k, v, g, beta, S0, gated_delta.XLA)
        o_ref, S_ref = reference.recurrence(q[0], k[0], v[0], g[0], beta[0], S0[0])
        np.testing.assert_allclose(o[0], o_ref, atol=5e-4)
        np.testing.assert_allclose(S[0], S_ref, atol=5e-4)

    def test_step_is_the_recurrence(self, data):
        (q, k, v, g, beta, S0), want = data
        S = S0
        for t in range(5):
            o, S = gated_delta.gated_delta_step(
                q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        np.testing.assert_allclose(o[0], want[0][0][4], atol=1e-5)

    def test_no_decay_no_write_leaves_state(self, data):
        (q, k, v, g, beta, S0), _ = data
        _, S = gated_delta.gated_delta_prefill(
            q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), S0, gated_delta.XLA)
        np.testing.assert_array_equal(S, S0)

    def test_unknown_impl_raises(self, data):
        with pytest.raises(ValueError, match="unknown impl"):
            gated_delta.gated_delta_prefill(*data[0], impl="fast")


class TestAgainstReference:
    LENGTHS = [190, 70, 130]

    @pytest.fixture(scope="class")
    def want(self):
        return reference_logits(rows(self.LENGTHS))

    def test_prefill_logits(self, want):
        toks = rows(self.LENGTHS)
        tokens, valid = left_padded(toks, 192)
        logits, _ = T.prefill(f32_params(), SPEC, jnp.asarray(tokens), jnp.asarray(valid),
                              T.init_kv_cache(SPEC, 3, 192, dtype=jnp.float32))
        for i, n in enumerate(self.LENGTHS):
            np.testing.assert_allclose(logits[i], want[i, n - 1], atol=F32_TOL)

    def test_prefill_then_decode_through_both_kinds_of_state(self, want):
        """Prefill a prefix of each row, then feed the row's next eight
        tokens one ``decode_step`` at a time: the logits after each are
        the full forward pass's at that position."""
        toks, steps = rows(self.LENGTHS), 8
        heads = [t[:-steps] for t in toks]
        L = 192
        tokens, valid = left_padded(heads, L)
        params = f32_params(stacked=True)
        cache = T.init_kv_cache(SPEC, 3, L + steps, dtype=jnp.float32, stacked=True)
        logits, cache = T.prefill(params, SPEC, jnp.asarray(tokens), jnp.asarray(valid), cache)
        mask = np.zeros((3, L + steps), bool)
        mask[:, :L] = valid
        for j in range(steps):
            for i, n in enumerate(self.LENGTHS):
                np.testing.assert_allclose(
                    logits[i], want[i, n - steps - 1 + j], atol=F32_TOL)
            mask[:, L + j] = True
            logits, cache = T.decode_step(
                params, SPEC, jnp.asarray([t[len(t) - steps + j] for t in toks]),
                jnp.int32(L + j), jnp.asarray([len(h) + j for h in heads], jnp.int32),
                cache, jnp.array(mask))   # a copy: the next step writes to `mask`

    def test_chunked_left_padded_prefill(self, want):
        """Four 64-wide chunk programs over a left-padded batch of
        unequal rows whose first chunk is pad in every row: against the
        reference, against the unchunked pass, against each row alone,
        and with the all-pad chunk skipped as the engine skips it."""
        toks = rows(self.LENGTHS)
        L, C = 256, 64
        tokens, valid = left_padded(toks, L)
        assert not valid[:, :C].any() and valid[:, C:2 * C].any()
        params = f32_params()
        fresh = lambda B, S: T.init_kv_cache(SPEC, B, S, dtype=jnp.float32)  # noqa: E731
        chunked, cache = chunked_prefill(params, tokens, valid, fresh(3, L), C)
        skipped, cache_s = chunked_prefill(params, tokens, valid, fresh(3, L), C,
                                           skip_dead=True)
        whole, cache_w = T.prefill(params, SPEC, jnp.asarray(tokens), jnp.asarray(valid),
                                   fresh(3, L))
        # a skipped all-pad chunk leaves the zeros it would have left (the
        # K/V it would have written lies in slots that stay masked)
        np.testing.assert_array_equal(chunked, skipped)
        for a, b, kind in zip(cache, cache_s, SPEC.layer_types):
            if kind == LINEAR_ATTENTION:
                np.testing.assert_array_equal(a["S"], b["S"])
                np.testing.assert_array_equal(a["conv"], b["conv"])
        np.testing.assert_allclose(chunked, whole, atol=F32_TOL)
        for i, n in enumerate(self.LENGTHS):
            np.testing.assert_allclose(chunked[i], want[i, n - 1], atol=F32_TOL)
            alone, cache_1 = T.prefill(
                params, SPEC, jnp.asarray(toks[i][None]), jnp.ones((1, n), bool), fresh(1, n))
            np.testing.assert_allclose(chunked[i], alone[0], atol=F32_TOL)
            # no pad position moved the row's state: it is the row's alone
            for li, kind in enumerate(SPEC.layer_types):
                if kind == LINEAR_ATTENTION:
                    np.testing.assert_allclose(
                        cache[li]["S"][i], cache_1[li]["S"][0], atol=F32_TOL)
                    np.testing.assert_allclose(
                        cache[li]["conv"][i], cache_1[li]["conv"][0], atol=F32_TOL)

    def test_a_pad_that_moved_the_state_would_show(self):
        """The guard of the case above bites: with the pad mask withheld
        from the linear layers alone (``valid`` all true there), a padded
        row's state is no longer the row's alone."""
        toks = rows([8])
        tokens, valid = left_padded(toks, 64)
        params = f32_params()
        layer, x = params["layers"][0], params["embed"][jnp.asarray(tokens)]
        entry = T.init_kv_cache(SPEC, 1, 64, dtype=jnp.float32)[0]
        ctx = T._Ctx(None, None, jnp.int32(0), None, 0, jnp.asarray(valid),
                     T.HybridImpl("xla", "xla"))
        _, kept = T._block_gated_delta(layer, SPEC, x, entry, ctx)
        _, moved = T._block_gated_delta(
            layer, SPEC, x, entry, ctx._replace(valid=jnp.ones_like(ctx.valid)))
        assert float(jnp.abs(kept["S"] - moved["S"]).max()) > 1e-3
        assert float(jnp.abs(kept["conv"] - moved["conv"]).max()) == 0.0   # 8 real inputs last

    @pytest.mark.parametrize("kv", [False, "int8"])
    def test_scan_matches_list_form(self, kv):
        """Three 64-wide chunk programs, then four decode steps: the
        scan over periods (the full layer's K/V written in place in the
        carry, the linear layers' state sliced out and written back)
        against the list form.  Same logits at every step; the same K,
        V, scales, ``S`` and conv tail at the end.  An int8 value may
        sit one step apart where its float lay on a rounding edge."""
        toks, steps = rows(self.LENGTHS), 4
        heads = [t[:-steps] for t in toks]
        L, C = 192, 64
        tokens, valid = left_padded(heads, L)
        got = {}
        for stacked in (False, True):
            params = f32_params(stacked=stacked)
            cache = T.init_kv_cache(SPEC, 3, L + steps, dtype=jnp.float32,
                                    quantized=kv, stacked=stacked)
            logits, cache = chunked_prefill(params, tokens, valid, cache, C)
            seen, mask = [logits], np.zeros((3, L + steps), bool)
            mask[:, :L] = valid
            for j in range(steps):
                mask[:, L + j] = True
                logits, cache = T.decode_step(
                    params, SPEC, jnp.asarray([t[len(t) - steps + j] for t in toks]),
                    jnp.int32(L + j), jnp.asarray([len(h) + j for h in heads], jnp.int32),
                    # A copy: on the CPU ``jnp.asarray`` aliases a NumPy
                    # array that lies on a 64-byte boundary, and the next
                    # step writes to ``mask`` while this one is in flight.
                    cache, jnp.array(mask))
                seen.append(logits)
            got[stacked] = (seen, cache)
        tol = 2e-2 if kv else F32_TOL   # logits of order 3; a flipped int8 step moves one 6e-3
        for one, scanned in zip(got[False][0], got[True][0]):
            np.testing.assert_allclose(one, scanned, atol=tol)
        cache, stacked = got[False][1], got[True][1]
        at = dict.fromkeys(stacked, 0)
        for entry, kind in zip(cache, SPEC.layer_types):
            for name, leaf in entry.items():
                there = stacked[kind][name][at[kind]]
                if leaf.dtype == jnp.int8:
                    diff = np.abs(np.asarray(leaf, np.int32) - np.asarray(there, np.int32))
                    assert diff.max() <= 1 and (diff > 0).mean() < 1e-3, name
                else:
                    np.testing.assert_allclose(leaf, there, atol=F32_TOL, err_msg=name)
            at[kind] += 1
        assert stacked[FULL_ATTENTION]["k"].shape[0] == 1

    def test_w8a8_tracks_bf16_within_the_quantiser(self):
        """int8 weights with per-token int8 activations against the
        bfloat16 model: each matmul's relative error is of the order of
        1/127 per operand, so the logits stay within a few percent of
        their norm (the dense family's own test holds the same cosine).
        Single logits move more than a dense model's: this architecture
        answers a 0.1% perturbation of its weights four to five times as
        strongly as the dense tiny model (0.08-0.16 against 0.02-0.04 on
        logits of order 3.5, float32), and W8A8 is a 1% perturbation:
        0.5-0.8 read at these widths, held under 0.3 of the largest."""
        toks = rows(self.LENGTHS)
        tokens, valid = left_padded(toks, 192)
        plain, quant = made_params(), made_params(quantized=True)
        run = lambda p, **kw: np.asarray(T.prefill(  # noqa: E731
            p, SPEC, jnp.asarray(tokens), jnp.asarray(valid),
            T.init_kv_cache(SPEC, 3, 192, **kw))[0], np.float64)
        a, b = run(plain), run(quant, quantized="int8")
        cos = (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.98
        assert np.abs(a - b).max() < 0.3 * np.abs(a).max()

    def test_int4_control_differs(self, want):
        low = reference_logits(rows(self.LENGTHS), weights="int4")
        assert np.abs(low[0, 100] - want[0, 100]).max() > 10 * F32_TOL


class TestCache:
    def test_two_kinds_of_state(self):
        cache = T.init_kv_cache(SPEC, 2, 128, quantized="int8", stacked=True)
        assert cache[FULL_ATTENTION]["k"].shape == (1, 2, 4, 128, 16)
        assert cache[LINEAR_ATTENTION]["S"].shape == (3, 2, 4, 16, 8)
        assert cache[LINEAR_ATTENTION]["S"].dtype == jnp.float32
        assert cache[LINEAR_ATTENTION]["conv"].shape == (3, 2, 3, 2 * 32 + 64)
        per_layer = T.init_kv_cache(SPEC, 2, 128)
        assert ["S" in e for e in per_layer] == [True, True, True, False]

    def test_byte_count_reads_the_allocation(self):
        for kw in ({}, {"quantized": "int8", "stacked": True}):
            cache = T.init_kv_cache(SPEC, 2, 128, **kw)
            by_kind = T.cache_bytes(SPEC, 2, 128, **kw)
            assert sum(by_kind.values()) == sum(a.nbytes for a in jax.tree.leaves(cache))
            # 3 layers x 2 rows x (4 x 16 x 8 float32 + 3 x 128 bfloat16)
            assert by_kind["linear_state"] == 3 * 2 * (4 * 16 * 8 * 4 + 3 * 128 * 2)
        dense = MODEL_SPECS["bcg-tpu/tiny-test"]
        assert T.cache_bytes(dense, 2, 128)["linear_state"] == 0

    def test_at_published_widths(self):
        spec = MODEL_SPECS["bcg-tpu/bench-olmo-hybrid-7b"]
        by_kind = T.cache_bytes(spec, 10, 5120, quantized="int8", stacked=True)
        # 8 layers x 2 x 30 x (128 + 4) bytes a token; 24 x (30 x 192 x 96 x 4
        # + 3 x 11520 x 2) bytes a row
        assert by_kind["kv"] == 10 * 5120 * 8 * 2 * 30 * 132
        assert by_kind["linear_state"] == 10 * 24 * (30 * 192 * 96 * 4 + 3 * 11520 * 2)

    @pytest.mark.parametrize("call", ["chunk", "prefix"])
    def test_unbuilt_call_forms_raise_at_trace_time(self, call):
        params = f32_params()
        cache = T.init_kv_cache(SPEC, 1, 64, dtype=jnp.float32)
        tokens, ok = jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), bool)
        with pytest.raises(NotImplementedError, match="layer_types"):
            if call == "chunk":
                T.decode_chunk(params, SPEC, tokens, ok, jnp.int32(8),
                               jnp.zeros((1, 4), jnp.int32), cache, jnp.zeros((1, 64), bool))
            else:
                T.prefill_with_prefix(params, SPEC, tokens, ok, cache,
                                      jnp.ones((1, 8), bool), jnp.full((1,), 8, jnp.int32))


class TestDecodeKernelBlock:
    """The all-heads int8 decode kernel holds a K and a V block of every
    kv head at once, double-buffered: the block follows the head count
    where that would pass a v5e's scoped VMEM (30 heads at a 1024 block
    did, before the block became 256 for every model the repo names)."""

    @pytest.mark.parametrize("hkv, dh, block", [
        (8, 128, 256), (30, 128, 256), (64, 128, 256), (128, 128, 128)])
    def test_block_follows_the_heads_held(self, hkv, dh, block):
        from bcg_tpu.ops.decode_attention import _pick_block, kernel_block

        assert _pick_block(None, hkv * dh) == kernel_block(hkv, dh) == block

    def test_requested_and_one_head_a_program(self):
        from bcg_tpu.ops.decode_attention import BLOCK_S, _pick_block

        assert _pick_block(512, 30 * 128) == 512
        assert _pick_block(None) == BLOCK_S == 256
