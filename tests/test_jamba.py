"""The Jamba2 block on the hybrid path: Mamba-1 selective-scan layers
thirteen to one with NoPE multi-query attention, every layer's MLP half
a dense SwiGLU, the head tied to the embedding.

Everything is held against the benchmark's plain reference of the
architecture (``benchmark/references/jamba2.py``: float32 at
``highest``, the scan as the token-by-token recurrence, no cache, no
import of the program): the reference the configuration's file names.
Sizes are ``bcg-tpu/tiny-jamba``'s (one period of 14 layers, the
published state, conv and expansion at tiny widths).
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

from bcg_tpu.models import transformer as T
from bcg_tpu.models.configs import FULL_ATTENTION, MAMBA, MODEL_SPECS
from bcg_tpu.models.loader import init_random_params_sharded
from bcg_tpu.ops import selective_scan as ss

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from references import jamba2 as reference  # noqa: E402

SPEC = MODEL_SPECS["bcg-tpu/tiny-jamba"]
SEED = 13
# float32 program against the float32 reference, both at ``highest`` on
# the CPU: what is left is summation order (fused against unfused norms,
# the conv's taps, the attention's softmax).  Logits are of order 2.
F32_TOL = 2e-4
# The scan alone, float32 against float32: the same recurrence in the
# same order, within a few units of float32 at outputs of order 10.
SCAN_TOL = 2e-5

REFERENCE_CONFIG = json.load(open(os.path.join(BENCH, "tests", "configs", "tiny-jamba.json")))
CELL_CONFIG = json.load(open(os.path.join(BENCH, "configs", "jamba2-3b-bf16.json")))


@functools.lru_cache(maxsize=None)
def f32_params(stacked: bool = False):
    params = init_random_params_sharded(SPEC, jax.random.PRNGKey(SEED))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
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
        spec = MODEL_SPECS["ai21labs/AI21-Jamba2-3B"]
        # 3.03 B parameters, 6.05 GB in bfloat16: the whole model on one chip
        assert 3.02e9 < spec.param_count < 3.03e9
        assert 6.0e9 < spec.weight_bytes() < 6.1e9
        assert [i for i, k in enumerate(spec.layer_types) if k == FULL_ATTENTION] == [7, 21]
        assert spec.layers_of(MAMBA) == spec.recurrent_layers == 26
        assert (spec.attn_layer_period, spec.attn_layer_offset) == (14, 7)
        # one Mamba layer: 41.2 M in its mixer's matmuls, the SwiGLU beside
        mixer = sum(i * o for n, (i, o) in spec.matmul_shapes(MAMBA).items()
                    if n.startswith("ssm_"))
        assert mixer == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
        bench = MODEL_SPECS["bcg-tpu/bench-jamba2-3b"]
        assert dataclasses.replace(bench, name=spec.name, max_position=spec.max_position) == spec
        assert SPEC.layer_period == spec.layer_period

    def test_the_file_holds_the_program_to_its_keys(self):
        from lib import system

        spec = MODEL_SPECS[CELL_CONFIG["program"]["model_name"]]
        system.check_spec(CELL_CONFIG, spec)
        for key, value in (("mamba_d_state", 8), ("attn_layer_offset", 6),
                           ("tie_word_embeddings", False)):
            with pytest.raises(RuntimeError, match=key):
                system.check_spec(dict(CELL_CONFIG, **{key: value}), spec)

    def test_int4_rounding_is_grouped(self):
        """The control's rounding: seven levels a side, one scale a group
        of 128 along the contraction (the whole contraction where 128
        does not divide it), and each group's largest weight kept."""
        for K in (256, 160):
            w = jax.random.normal(jax.random.PRNGKey(K), (K, 8)) / 16.0
            G = 128 if K % 128 == 0 else K
            got = np.asarray(reference._round(w, "int4")).reshape(K // G, G, 8)
            top = np.abs(np.asarray(reference._round(w, "bf16")).reshape(K // G, G, 8)).max(axis=1)
            np.testing.assert_allclose(np.abs(got).max(axis=1), top, rtol=1e-6)
            levels = got / (top / 7.0)[:, None, :]
            np.testing.assert_allclose(levels, np.round(levels), atol=1e-4)

    def test_the_screen_keeps_one_vote_step_count(self):
        """The file's screen reads the vote call's decode row-steps over
        its prefill positions: with the traffic's 10 rows and a longest
        vote prompt in (2048, 2560] (five 512-wide chunk programs, 2,560
        positions a row), only a vote call of 23 steps lies on the band."""
        from lib import system

        traffic = json.load(open(os.path.join(BENCH, "traffic", "lockstep.json")))
        (screen,) = system.check_screens(CELL_CONFIG, traffic)
        rows, positions = traffic["calls"]["vote"]["rows"], 2560
        lo, hi = screen["band"]
        kept = [steps for steps in range(1, 200)
                if lo < rows * steps / (rows * positions) <= hi]
        assert kept == [23] and screen["kinds"] == ["vote"]

    @pytest.mark.parametrize("change", [
        {"mamba_d_state": 0}, {"mamba_d_conv": 1}, {"mamba_proj_bias": True},
        {"mamba_conv_bias": False}])
    def test_malformed_mamba_raises(self, change):
        """A size the layer cannot take raises at the spec; a bias it is
        not built with raises where a configuration's file states it."""
        (key,) = change
        if key.endswith("_bias"):
            from lib import system

            spec = MODEL_SPECS[CELL_CONFIG["program"]["model_name"]]
            system.check_spec(CELL_CONFIG, spec)
            with pytest.raises(RuntimeError, match=key):
                system.check_spec(dict(CELL_CONFIG, **change), spec)
            return
        with pytest.raises(ValueError, match="mamba layer"):
            dataclasses.replace(SPEC, **change)

    def test_mamba_leaves_follow_mamba1_init(self):
        plan = {name.split(".", 2)[-1]: (kind, shape) for name, kind, shape in
                T.param_plan(SPEC) if name.startswith("layers.0.")}
        kind, shape = plan["ssm_a_log"]
        a_log = T.init_leaf(kind, shape, None, jnp.bfloat16)
        assert a_log.dtype == jnp.float32
        np.testing.assert_allclose(np.exp(a_log[5]), np.arange(1, 17), rtol=1e-6)
        b = T.init_leaf("ssm_dt_bias", (4096,), jax.random.PRNGKey(0), jnp.float32)
        dt = np.asarray(jax.nn.softplus(b))
        assert 0.00099 < dt.min() and dt.max() < 0.1001
        # log-uniform: as many steps below 0.01 as above
        assert abs(np.mean(dt < 0.01) - 0.5) < 0.05


class TestScan:
    """The chunk kernel and its XLA twin against the reference's
    token-by-token recurrence: a chunk longer than the kernel's group of
    128 positions, rows of different lengths (a stretch of pads, whose
    step size is 0), a state carried in and out."""

    @pytest.fixture(scope="class")
    def data(self):
        B, Tn, C, N = 3, 150, 256, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        u = jax.random.normal(ks[0], (B, Tn, C))
        delta = jax.nn.softplus(jax.random.normal(ks[1], (B, Tn, C)) - 2.0)
        pads = jnp.arange(Tn)[None, :] < jnp.array([[0], [40], [110]])
        delta = jnp.where(pads[..., None], 0.0, delta)
        Bm = jax.random.normal(ks[2], (B, Tn, N))
        Cm = jax.random.normal(ks[3], (B, Tn, N))
        A = -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(1.0, N + 1)), (C, N)))
        D = jax.random.normal(ks[4], (C,))
        S0 = jax.random.normal(ks[5], (B, N, C))
        want = [reference.recurrence(u[b], delta[b], Bm[b], Cm[b], A, D, S0[b].T)
                for b in range(B)]
        return (u, delta, Bm, Cm, A, D, S0), want

    @pytest.mark.parametrize("impl", [ss.PALLAS_INTERPRET, ss.XLA])
    def test_prefill_matches_recurrence(self, data, impl):
        args, want = data
        y, S = ss.mamba_prefill(*args, impl=impl)
        for b, (y_ref, S_ref) in enumerate(want):
            np.testing.assert_allclose(y[b], y_ref, atol=SCAN_TOL, rtol=1e-5)
            np.testing.assert_allclose(S[b], S_ref.T, atol=SCAN_TOL, rtol=1e-5)

    @pytest.mark.parametrize("impl", [ss.PALLAS_INTERPRET, ss.XLA])
    def test_chunks_hand_the_state_on(self, data, impl):
        (u, delta, Bm, Cm, A, D, S0), want = data
        cut = lambda x, a, b: x[:, a:b]  # noqa: E731
        y1, S = ss.mamba_prefill(*(cut(x, 0, 70) for x in (u, delta, Bm, Cm)), A, D, S0,
                                 impl=impl)
        y2, S = ss.mamba_prefill(*(cut(x, 70, 150) for x in (u, delta, Bm, Cm)), A, D, S,
                                 impl=impl)
        for b, (y_ref, S_ref) in enumerate(want):
            np.testing.assert_allclose(jnp.concatenate([y1[b], y2[b]]), y_ref,
                                       atol=SCAN_TOL, rtol=1e-5)
            np.testing.assert_allclose(S[b], S_ref.T, atol=SCAN_TOL, rtol=1e-5)

    def test_step_is_the_recurrence(self, data):
        (u, delta, Bm, Cm, A, D, S0), want = data
        S = S0
        for t in range(5):
            y, S = ss.mamba_step(u[:, t], delta[:, t], Bm[:, t], Cm[:, t], A, D, S)
        np.testing.assert_allclose(y[0], want[0][0][4], atol=SCAN_TOL, rtol=1e-5)

    @pytest.mark.parametrize("impl", [ss.PALLAS_INTERPRET, ss.XLA])
    def test_pads_leave_the_state(self, data, impl):
        (u, delta, Bm, Cm, A, D, S0), _ = data
        _, S = ss.mamba_prefill(u, jnp.zeros_like(delta), Bm, Cm, A, D, S0, impl=impl)
        np.testing.assert_array_equal(S, S0)

    def test_a_bfloat16_state_is_told_apart(self, data):
        """The tolerance is tight enough to refuse the state kept in
        bfloat16: the same recurrence with the state rounded at every
        position misses by far more than :data:`SCAN_TOL`."""
        (u, delta, Bm, Cm, A, D, S0), want = data
        S = S0.astype(jnp.bfloat16).astype(jnp.float32)
        ys = []
        for t in range(u.shape[1]):
            y, S = ss.mamba_step(u[:, t], delta[:, t], Bm[:, t], Cm[:, t], A, D, S)
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
            ys.append(y[0])
        assert np.abs(np.stack(ys) - want[0][0]).max() > 100 * SCAN_TOL

    def test_unknown_impl_raises(self, data):
        args, _ = data
        with pytest.raises(ValueError, match="unknown impl"):
            ss.mamba_prefill(*args, impl="cuda")


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

    def test_prefill_then_decode_through_the_cache_and_scan_state(self, want):
        """Chunked prefill of each row's head into the stacked cache (K/V
        and the float32 scan state with its conv tail), then the row's
        next six tokens one ``decode_step`` at a time: the logits after
        each are the full forward pass's at that position.  Then the same
        through an int8 KV cache: its K and V are the float cache's, each
        within half a step of its (token, head)'s absmax scale, and the
        logits keep their direction."""
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
        state = got[False][1][MAMBA]
        assert state["S"].shape == (13, 3, 16, 128) and state["S"].dtype == jnp.float32
        assert state["conv"].shape == (13, 3, 3, 128)
        exact, coarse = got[False][1][FULL_ATTENTION], got["int8"][1][FULL_ATTENTION]
        for name in ("k", "v"):
            scale = np.asarray(coarse[name + "_scale"])[..., None]
            error = np.abs(np.asarray(coarse[name], np.float32) * scale
                           - np.asarray(exact[name]).transpose(0, 1, 3, 2, 4))
            assert (error <= 0.5 * scale * (1 + 1e-5)).all(), name
        a, b = np.stack(got[False][0]), np.stack(got["int8"][0])
        assert (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b)) > 0.999

    def test_chunked_left_padded_prefill(self, want):
        """Four 64-wide chunk programs over a left-padded batch whose
        first chunk is pad in every row, against the reference and
        against one-shot prefill of each row alone."""
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

    def test_int8_control_differs(self, want):
        low = reference_logits(rows(self.LENGTHS), weights="int8")
        assert np.abs(low[0, 100] - want[0, 100]).max() > 10 * F32_TOL

    def test_int4_control_differs_more_than_int8(self, want):
        """The configuration's control, grouped int4 weights, lies
        farther from the served bfloat16 model than int8 weights with a
        scale per output channel."""
        assert CELL_CONFIG["control"]["weights"] == "int4"
        toks = rows(self.LENGTHS)
        int4 = reference_logits(toks, weights="int4")
        int8 = reference_logits(toks, weights="int8")
        for i, n in enumerate(self.LENGTHS):
            far = np.abs(int4[i, :n] - want[i, :n]).mean()
            assert far > 10 * F32_TOL
            assert far > 1.5 * np.abs(int8[i, :n] - want[i, :n]).mean()
