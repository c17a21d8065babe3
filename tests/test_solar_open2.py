"""The Solar-Open2 block on the hybrid path: gated NoPE GQA and KDA
(channel-gated delta-rule) layers three to one, every layer's MLP half
routed experts with a shared one, of which the chip holds a share.

Everything is held against the benchmark's plain reference of the
architecture (``benchmark/references/solar_open2.py``: float32 at
``highest``, KDA as the token-by-token recurrence, no cache, no import
of the program): the one reference the chip's ``correct`` uses.  Sizes
are ``bcg-tpu/tiny-solar``'s (one period, 8 of 16 experts held).
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
from jax.experimental import pallas as pl

from bcg_tpu.models import transformer as T
from bcg_tpu.models.configs import FULL_ATTENTION, KDA, MODEL_SPECS
from bcg_tpu.models.loader import init_random_params_sharded
from bcg_tpu.models.quantize import quantize_leaf_transform
from bcg_tpu.ops import gated_delta

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from references import solar_open2 as reference  # noqa: E402

SPEC = MODEL_SPECS["bcg-tpu/tiny-solar"]
SEED = 11
# float32 program against the float32 reference, both at ``highest`` on
# the CPU: what is left is summation order (the chunkwise form against
# the recurrence, the experts' sums, fused against unfused norms).
# Logits are of order 3.
F32_TOL = 2e-4

REFERENCE_CONFIG = json.load(open(os.path.join(BENCH, "tests", "configs", "tiny-solar.json")))
CELL_CONFIG = json.load(open(os.path.join(BENCH, "configs", "solar-open2-250b-int8.json")))


@functools.lru_cache(maxsize=None)
def made_params(quantized: bool = False):
    transform = quantize_leaf_transform(SPEC, "int8") if quantized else None
    return init_random_params_sharded(
        SPEC, jax.random.PRNGKey(SEED), leaf_transform=transform)


@functools.lru_cache(maxsize=None)
def f32_params(stacked: bool = False):
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


def reference_logits(toks, weights: str = "bf16"):
    n = max(map(len, toks))
    tokens = np.zeros((len(toks), -(-n // 512) * 512), np.int32)
    for i, t in enumerate(toks):
        tokens[i, :len(t)] = t
    return reference.logits(REFERENCE_CONFIG, SEED, tokens,
                            np.array([len(t) for t in toks]), SPEC.vocab_size, weights)


prefill = jax.jit(functools.partial(T.prefill, spec=SPEC))
chunk_at = jax.jit(functools.partial(T.prefill_chunk_at, spec=SPEC))
step = jax.jit(functools.partial(T.decode_step, spec=SPEC))


def chunked_prefill(params, tokens, valid, cache, C):
    B, L = tokens.shape
    for start in range(0, L, C):
        hist = np.zeros((B, L - C), bool)
        hist[:, :start] = valid[:, :start]
        logits, cache = chunk_at(
            params, tokens=jnp.asarray(tokens[:, start:start + C]),
            valid=jnp.asarray(valid[:, start:start + C]), cache=cache,
            hist_valid=jnp.asarray(hist),
            pos_offset=jnp.asarray(valid[:, :start].sum(axis=1), jnp.int32),
            write_pos=jnp.int32(start))
    return logits, cache


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


class TestSpec:
    def test_published_counts(self):
        spec = MODEL_SPECS["upstage/Solar-Open2-250B"]
        # 250B in all: the name's count, with every expert held
        assert 250.0e9 < spec.param_count < 250.5e9
        share = MODEL_SPECS["bcg-tpu/bench-solar-open2-250b"]
        assert share.layer_types == spec.layer_types[:12]
        assert share.layer_period == (FULL_ATTENTION, KDA, KDA, KDA)
        # one layer's experts: 40 held of 320, each 3 x 4096 x 1280
        expert = 3 * 4096 * 1280
        assert spec.block_matmul_params - share.block_matmul_params * 4 == \
            48 * (320 - 40) * expert
        assert 9.5e9 < share.weight_bytes("int8") < 9.8e9

    def test_the_file_holds_the_program_to_its_keys(self):
        from lib import system

        system.check_spec(CELL_CONFIG, MODEL_SPECS[CELL_CONFIG["program"]["model_name"]])
        wrong = dict(CELL_CONFIG, n_routed_experts=320)
        with pytest.raises(RuntimeError, match="n_routed_experts"):
            system.check_spec(wrong, MODEL_SPECS[CELL_CONFIG["program"]["model_name"]])

    @pytest.mark.parametrize("change", [
        {"experts_held": 0}, {"experts_held": 17}, {"shared_experts": 2},
        {"layer_types": None}])
    def test_malformed_experts_raise(self, change):
        with pytest.raises(ValueError, match="routed experts"):
            dataclasses.replace(SPEC, **change)

    def test_held_experts_are_the_first_of_the_published_stack(self):
        """One key a stack, drawn under the partitionable RNG: the first
        ``experts_held`` of the router's whole span, so the share is the
        published layer's and not a model of its own."""
        key = jax.random.PRNGKey(3)
        with jax.threefry_partitionable(True):
            whole = T.init_leaf("dense", (16, 64, 32), key, jnp.float32)
            held = T.init_leaf("dense", (8, 64, 32), key, jnp.float32)
        np.testing.assert_array_equal(whole[:8] * 1.0, held * 1.0)


class TestKernel:
    """The channel-gated chunkwise form against the reference's token
    recurrence, on data that holds what breaks it: per-channel decays
    down to g = -20 (whose running sum over a 64-token chunk leaves
    float32 as exp(+1280)), write strengths near 2, and pad positions."""

    @pytest.fixture(scope="class")
    def data(self):
        B, Tn, H, dk, dv = 2, 150, 3, 8, 16     # 150: the last chunk is padded
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
        q = unit(jax.random.normal(ks[0], (B, Tn, H, dk))) * dk ** -0.5
        k = unit(jax.random.normal(ks[1], (B, Tn, H, dk)) + 1.0)
        v = jax.random.normal(ks[2], (B, Tn, H, dv))
        g = -jnp.exp(jax.random.uniform(ks[3], (B, Tn, H, dk), minval=-8.0, maxval=3.0))
        beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, Tn, H)) + 1)
        # a stretch of pads in row 1: no decay, no write
        pad = (jnp.arange(Tn) >= 40) & (jnp.arange(Tn) < 60)
        g = g.at[1].set(jnp.where(pad[:, None, None], 0.0, g[1]))
        beta = beta.at[1].set(jnp.where(pad[:, None], 0.0, beta[1]))
        S0 = jax.random.normal(ks[5], (B, H, dv, dk))
        assert float(g.min()) < -19.0 and float(beta.max()) > 1.99
        want = [reference.recurrence(q[b], k[b], v[b], g[b], beta[b], S0[b])
                for b in range(B)]
        return (q, k, v, g, beta, S0), want

    @pytest.mark.parametrize("impl", [gated_delta.PALLAS_INTERPRET, gated_delta.XLA])
    def test_chunkwise_matches_recurrence(self, data, impl):
        args, want = data
        o, S = gated_delta.kda_prefill(*args, impl=impl)
        assert bool(jnp.isfinite(o).all() & jnp.isfinite(S).all())
        for b, (o_ref, S_ref) in enumerate(want):
            # float32 throughout; the triangular solve of a 64-token
            # chunk amplifies rounding by a few units
            np.testing.assert_allclose(o[b], o_ref, atol=5e-5)
            np.testing.assert_allclose(S[b], S_ref, atol=5e-5)

    def test_step_is_the_recurrence(self, data):
        (q, k, v, g, beta, S0), want = data
        S = S0
        for t in range(5):
            o, S = gated_delta.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        np.testing.assert_allclose(o[0], want[0][0][4], atol=1e-5)

    def test_pads_leave_the_state(self, data):
        (q, k, v, g, beta, S0), _ = data
        _, S = gated_delta.kda_prefill(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta),
                                       S0, gated_delta.XLA)
        np.testing.assert_array_equal(S, S0)

    @pytest.mark.parametrize("impl", [gated_delta.PALLAS_INTERPRET, gated_delta.XLA])
    def test_pairs_match_float64(self, impl):
        """``_kda_pairs``' two matrices at the cell's chunk and width (C
        64, dk 128) against the elementwise products in float64, from the
        same float32 inputs: decays down to -20 a channel and token, write
        strengths near 2, a stretch of pads."""
        C, dk = gated_delta.CHUNK, 128
        rng = np.random.default_rng(5)
        q = rng.standard_normal((C, dk)) * dk ** -0.5
        k = rng.standard_normal((C, dk)) + 1.0
        k /= np.linalg.norm(k, axis=1, keepdims=True)
        g = -np.exp(rng.uniform(-8.0, 3.0, (C, dk)))
        beta = 2 / (1 + np.exp(-2 * rng.standard_normal((C, 1)) - 1))
        g[20:30], beta[20:30] = 0.0, 0.0
        assert g.min() < -19.0 and beta.max() > 1.99
        q, k, G, beta = (np.float32(x) for x in (q, k, np.cumsum(g, axis=0), beta))

        i, j = np.tril_indices(C)
        decay = np.exp(G[i].astype(np.float64) - G[j])            # [pairs, dk]
        want_a, want_qk = np.zeros((C, C)), np.zeros((C, C))
        want_a[i, j] = beta[i, 0] * np.sum(k[i] * k[j].astype(np.float64) * decay, axis=1)
        want_a[np.arange(C), np.arange(C)] = 0.0
        want_qk[i, j] = np.sum(q[i] * k[j].astype(np.float64) * decay, axis=1)

        if impl == gated_delta.XLA:
            a, qk = jax.jit(gated_delta._kda_pairs)(q, k, G, beta)
        else:
            def kernel(q_ref, k_ref, G_ref, b_ref, a_ref, qk_ref):
                a_ref[...], qk_ref[...] = gated_delta._kda_pairs(
                    q_ref[...], k_ref[...], G_ref[...], b_ref[...])

            a, qk = pl.pallas_call(
                kernel, out_shape=[jax.ShapeDtypeStruct((C, C), jnp.float32)] * 2,
                interpret=True)(q, k, G, beta)
        # a few float32 units at the entries' size (|a| < 2, |qk| < 1)
        np.testing.assert_allclose(a, want_a, rtol=0, atol=1e-6)
        np.testing.assert_allclose(qk, want_qk, rtol=0, atol=1e-7)

    @pytest.mark.parametrize("impl", [gated_delta.PALLAS_INTERPRET, gated_delta.XLA])
    def test_extreme_decay_stays_finite(self, impl):
        """Decays near -60 a token in three channels of four: across two
        positions any positive exponent is exp(+120), past float32.  The
        chunkwise form stays finite and still is the recurrence."""
        Tn, H, dk, dv = 150, 2, 128, 128
        ks = jax.random.split(jax.random.PRNGKey(1), 7)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
        q = unit(jax.random.normal(ks[0], (1, Tn, H, dk))) * dk ** -0.5
        k = unit(jax.random.normal(ks[1], (1, Tn, H, dk)) + 1.0)
        v = jax.random.normal(ks[2], (1, Tn, H, dv))
        steep = jax.random.uniform(ks[3], (1, Tn, H, dk), minval=-60.0, maxval=-50.0)
        mild = -jnp.exp(jax.random.uniform(ks[6], (1, Tn, H, dk), minval=-8.0, maxval=0.0))
        g = jnp.where(jnp.arange(dk) % 4 == 0, mild, steep)
        beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (1, Tn, H)) + 1)
        pad = (jnp.arange(Tn) >= 70) & (jnp.arange(Tn) < 80)
        g = jnp.where(pad[None, :, None, None], 0.0, g)
        beta = jnp.where(pad[None, :, None], 0.0, beta)
        S0 = jax.random.normal(ks[5], (1, H, dv, dk))

        o, S = gated_delta.kda_prefill(q, k, v, g, beta, S0, impl=impl)
        assert bool(jnp.isfinite(o).all() & jnp.isfinite(S).all())
        o_ref, S_ref = reference.recurrence(q[0], k[0], v[0], g[0], beta[0], S0[0])
        np.testing.assert_allclose(o[0], o_ref, atol=5e-5)
        np.testing.assert_allclose(S[0], S_ref, atol=5e-5)

    def test_chunk_math_walks_no_positions(self):
        """The chunk's mathematics at the cell's [64, 128] is straight-line
        code: no ``scan`` or ``while`` anywhere in its jaxpr, sub-jaxprs
        included, so no step of it runs once per position."""
        C, d = gated_delta.CHUNK, 128
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
                  for s in ((C, d), (C, d), (C, d), (C, d), (C, 1), (d, d))]

        def primitives(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from primitives(sub)

        found = set(primitives(jax.make_jaxpr(gated_delta._kda_chunk_math)(*shapes).jaxpr))
        assert "dot_general" in found
        assert not found & {"scan", "while"}


class TestAgainstReference:
    LENGTHS = [190, 70, 130]

    @pytest.fixture(scope="class")
    def want(self):
        return reference_logits(rows(self.LENGTHS))

    def test_prefill_logits(self, want):
        tokens, valid = left_padded(rows(self.LENGTHS), 192)
        logits, _ = prefill(f32_params(), tokens=jnp.asarray(tokens), valid=jnp.asarray(valid),
                            cache=T.init_kv_cache(SPEC, 3, 192, dtype=jnp.float32))
        for i, n in enumerate(self.LENGTHS):
            np.testing.assert_allclose(logits[i], want[i, n - 1], atol=F32_TOL)

    def test_prefill_then_decode_through_the_cache_and_kda_state(self, want):
        """Chunked prefill of each row's head into the stacked cache (K/V
        and the float32 KDA state), then the row's next six tokens one
        ``decode_step`` at a time: the logits after each are the full
        forward pass's at that position.  Then the same through an int8
        KV cache: its K and V are the float cache's, each within half a
        step of its (token, head)'s absmax scale; the logits move more,
        for a token whose eighth expert and ninth lie closer than a KV
        rounding routes elsewhere (hence a cosine, as for W8A8)."""
        toks, steps = rows(self.LENGTHS), 6
        heads = [t[:-steps] for t in toks]
        L = 192
        tokens, valid = left_padded(heads, L)
        params = f32_params(stacked=True)
        got = {}
        for kv in (False, "int8"):
            cache = T.init_kv_cache(SPEC, 3, L + steps, dtype=jnp.float32, quantized=kv,
                                    stacked=True)
            logits, cache = chunked_prefill(params, tokens, valid, cache, 64)
            mask = np.zeros((3, L + steps), bool)
            mask[:, :L] = valid
            seen = []
            for j in range(steps):
                seen.append(np.asarray(logits, np.float64))
                mask[:, L + j] = True
                logits, cache = step(
                    params, token=jnp.asarray([t[len(t) - steps + j] for t in toks]),
                    write_pos=jnp.int32(L + j),
                    seq_positions=jnp.asarray([len(h) + j for h in heads], jnp.int32),
                    cache=cache, valid_mask=jnp.array(mask))
            got[kv] = seen, cache
        for j, logits in enumerate(got[False][0]):
            for i, n in enumerate(self.LENGTHS):
                np.testing.assert_allclose(logits[i], want[i, n - steps - 1 + j], atol=F32_TOL)
        exact, coarse = got[False][1][FULL_ATTENTION], got["int8"][1][FULL_ATTENTION]
        for name in ("k", "v"):
            scale = np.asarray(coarse[name + "_scale"])[..., None]
            error = np.abs(np.asarray(coarse[name], np.float32) * scale
                           - np.asarray(exact[name]).transpose(0, 1, 3, 2, 4))
            assert (error <= 0.5 * scale * (1 + 1e-5)).all(), name
        a, b = np.stack(got[False][0]), np.stack(got["int8"][0])
        assert (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b)) > 0.98

    def test_chunked_left_padded_prefill(self, want):
        """Four 64-wide chunk programs over a left-padded batch whose
        first chunk is pad in every row, against the reference and
        against each row alone."""
        toks = rows(self.LENGTHS)
        L, C = 256, 64
        tokens, valid = left_padded(toks, L)
        params = f32_params()
        chunked, _ = chunked_prefill(params, tokens, valid,
                                     T.init_kv_cache(SPEC, 3, L, dtype=jnp.float32), C)
        for i, n in enumerate(self.LENGTHS):
            np.testing.assert_allclose(chunked[i], want[i, n - 1], atol=F32_TOL)
        alone, _ = prefill(params, tokens=jnp.asarray(toks[1][None]),
                           valid=jnp.ones((1, 70), bool),
                           cache=T.init_kv_cache(SPEC, 1, 70, dtype=jnp.float32))
        np.testing.assert_allclose(chunked[1], alone[0], atol=F32_TOL)

    def test_int4_control_differs(self, want):
        low = reference_logits(rows(self.LENGTHS), weights="int4")
        assert np.abs(low[0, 100] - want[0, 100]).max() > 10 * F32_TOL
