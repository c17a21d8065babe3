"""Multi-device tests on the virtual 8-CPU mesh: mesh/sharding, ring
attention exactness, SPMD game step parity with the host game."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.comm import NetworkTopology
from bcg_tpu.game import ByzantineConsensusGame
from bcg_tpu.models import init_params, spec_for_model
from bcg_tpu.parallel import build_mesh, shard_params
from bcg_tpu.parallel.game_step import (
    check_consensus_spmd,
    exchange_values,
    spmd_round_arrays,
    tally_votes,
)
from bcg_tpu.ops.ring_attention import ring_attention
from bcg_tpu.models.transformer import _xla_attention

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


class TestMesh:
    def test_build_mesh_shapes(self):
        mesh = build_mesh(dp=2, tp=2, sp=2)
        assert dict(mesh.shape) == {"dp": 2, "tp": 2, "sp": 2}

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="devices"):
            build_mesh(dp=4, tp=4, sp=4)

    def test_shard_params_tp(self):
        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        mesh = build_mesh(dp=1, tp=2, sp=1)
        sharded = shard_params(params, spec, mesh)
        wq = sharded["layers"][0]["wq"]
        # Column-parallel: output dim split over tp.
        assert wq.sharding.spec == jax.sharding.PartitionSpec(None, "tp")
        norm = sharded["layers"][0]["attn_norm"]
        assert norm.sharding.spec == jax.sharding.PartitionSpec(None)


class TestRingAttention:
    @pytest.mark.parametrize("sp", [2, 4, 8])
    def test_matches_full_attention(self, sp):
        mesh = build_mesh(dp=1, tp=1, sp=sp)
        B, T, H, Hkv, Dh = 2, 32, 4, 2, 16
        key = jax.random.PRNGKey(1)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, T, H, Dh), jnp.float32)
        k = jax.random.normal(kk, (B, T, Hkv, Dh), jnp.float32)
        v = jax.random.normal(kv, (B, T, Hkv, Dh), jnp.float32)

        ring = ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        full = _xla_attention(q, k, v, jnp.broadcast_to(causal, (B, T, T)),
                              1.0 / np.sqrt(Dh))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)

    def test_non_causal(self):
        mesh = build_mesh(dp=1, tp=1, sp=4)
        B, T, H, Dh = 1, 16, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, Dh))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, Dh))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, Dh))
        ring = ring_attention(q, k, v, mesh, causal=False)
        full = _xla_attention(q, k, v, jnp.ones((B, T, T), bool), 1.0 / np.sqrt(Dh))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)

    def test_indivisible_length_raises(self):
        mesh = build_mesh(dp=1, tp=1, sp=8)
        x = jnp.zeros((1, 12, 2, 8))
        with pytest.raises(ValueError, match="divisible"):
            ring_attention(x, x, x, mesh)

    def test_composed_mesh_dp_tp_sp(self):
        """On a dp x tp x sp mesh the batch shards over dp and heads
        over tp (replicating them would all-gather tp-sharded heads into
        every device and defeat the O(L/sp) memory point); results must
        still match full attention."""
        mesh = build_mesh(dp=2, tp=2, sp=2)
        B, T, H, Hkv, Dh = 4, 16, 4, 2, 8
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(kq, (B, T, H, Dh), jnp.float32)
        k = jax.random.normal(kk, (B, T, Hkv, Dh), jnp.float32)
        v = jax.random.normal(kv, (B, T, Hkv, Dh), jnp.float32)
        pad = jnp.array([0, 3, 9, 1])
        valid = jnp.arange(T)[None, :] >= pad[:, None]

        ring = ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                              kv_valid=valid)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        mask = causal & valid[:, None, :] & valid[:, :, None]
        full = _xla_attention(q, k, v, mask, 1.0 / np.sqrt(Dh))
        vmask = np.asarray(valid)
        np.testing.assert_allclose(
            np.asarray(ring)[vmask], np.asarray(full)[vmask],
            rtol=2e-4, atol=2e-4,
        )

    @pytest.mark.parametrize("sp", [2, 4])
    def test_kv_valid_matches_masked_full_attention(self, sp):
        """Left-padded rows (the engine's batch layout): ring with a
        kv_valid mask must equal full attention under causal & validity
        masking, and fully-padded query rows must output 0."""
        mesh = build_mesh(dp=1, tp=1, sp=sp)
        B, T, H, Hkv, Dh = 3, 32, 4, 2, 16
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(kq, (B, T, H, Dh), jnp.float32)
        k = jax.random.normal(kk, (B, T, Hkv, Dh), jnp.float32)
        v = jax.random.normal(kv, (B, T, Hkv, Dh), jnp.float32)
        pad = jnp.array([0, 5, 19])  # row pad counts (left-padding)
        valid = jnp.arange(T)[None, :] >= pad[:, None]  # [B, T]

        ring = ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                              kv_valid=valid)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        mask = causal & valid[:, None, :] & valid[:, :, None]
        full = _xla_attention(q, k, v, mask, 1.0 / np.sqrt(Dh))
        r, f = np.asarray(ring), np.asarray(full)
        # Pad q rows: engine's flash path zeroes them; _xla_attention's
        # f32 softmax over all -inf is NaN there — compare valid rows.
        vmask = np.asarray(valid)
        np.testing.assert_allclose(r[vmask], f[vmask], rtol=2e-4, atol=2e-4)
        assert not np.isnan(r).any()
        np.testing.assert_array_equal(r[~vmask], 0.0)

    def test_strongly_negative_logits_survive_empty_blocks(self):
        """Underflow regression: with heavy left-padding most ring steps
        see a fully-masked kv block.  A 0.0 sentinel max from those
        blocks would inflate the running max, underflowing exp() when
        every VALID logit is below ~-87; the merge must reference only
        finite block maxima."""
        sp = 4
        mesh = build_mesh(dp=1, tp=1, sp=sp)
        B, T, H, Hkv, Dh = 2, 32, 2, 2, 16
        # q·k * scale ≈ -25*16/4 = -100 on every valid pair.
        q = jnp.full((B, T, H, Dh), 5.0, jnp.float32)
        k = jnp.full((B, T, Hkv, Dh), -5.0, jnp.float32)
        kv0 = jax.random.normal(jax.random.PRNGKey(9), (B, T, Hkv, Dh))
        v = kv0.astype(jnp.float32)
        pad = jnp.array([28, 30])  # only the last shard holds valid kv
        valid = jnp.arange(T)[None, :] >= pad[:, None]
        ring = ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                              kv_valid=valid)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        mask = causal & valid[:, None, :] & valid[:, :, None]
        full = _xla_attention(q, k, v, mask, 1.0 / np.sqrt(Dh))
        r, f = np.asarray(ring), np.asarray(full)
        vmask = np.asarray(valid)
        # All valid logits equal → softmax = running mean of valid v;
        # any underflow collapses the output to 0 instead.
        assert np.abs(r[vmask]).max() > 0.1
        np.testing.assert_allclose(r[vmask], f[vmask], rtol=2e-4, atol=2e-4)


class TestSpDecodeAttention:
    """Flash-decoding over a sequence-sharded cache: partials merge via
    pmax/psum of O(B*H) stats; must equal full-cache attention exactly,
    including rows whose valid slots all live on one shard."""

    def _ref(self, q, k, v, mask, scale):
        from bcg_tpu.models.transformer import _xla_attention

        return _xla_attention(q[:, None], k, v, mask[:, None, :], scale)[:, 0]

    @pytest.mark.parametrize("sp", [2, 4, 8])
    def test_matches_full_cache_attention(self, sp):
        from bcg_tpu.ops.ring_attention import sp_decode_attention

        mesh = build_mesh(dp=1, tp=1, sp=sp)
        B, S, H, Hkv, Dh = 3, 64, 4, 2, 16
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(kq, (B, H, Dh), jnp.float32)
        k = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.float32)
        v = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.float32)
        # Row 0: all slots; row 1: a short prefix (one shard's worth);
        # row 2: a scattered window.
        mask = jnp.stack([
            jnp.ones(S, bool),
            jnp.arange(S) < 6,
            (jnp.arange(S) % 3 == 0) & (jnp.arange(S) < 40),
        ])
        scale = 1.0 / np.sqrt(Dh)
        out = sp_decode_attention(q, k, v, mask, mesh, scale=scale)
        ref = self._ref(q, k, v, mask, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_strongly_negative_logits_survive_empty_shards(self):
        """Underflow regression (advisor r4): a short left-padded row on
        large sp leaves most cache shards fully masked.  pmax of a 0.0
        sentinel from the empty shards inflates the global max; when
        every valid logit is below ~-87 the f32 exp underflows and the
        output collapses to 0 instead of the true softmax average."""
        from bcg_tpu.ops.ring_attention import sp_decode_attention

        sp = 8
        mesh = build_mesh(dp=1, tp=1, sp=sp)
        B, S, H, Hkv, Dh = 2, 64, 4, 2, 16
        # q·k * scale ≈ -100 on every valid slot (all logits equal).
        q = jnp.full((B, H, Dh), 5.0, jnp.float32)
        k = jnp.full((B, S, Hkv, Dh), -5.0, jnp.float32)
        v = jax.random.normal(
            jax.random.PRNGKey(11), (B, S, Hkv, Dh), jnp.float32
        )
        # Valid slots confined to the LAST shard (slots 56..) — the
        # other 7 shards are empty and must not poison the merge.
        mask = jnp.arange(S)[None, :] >= jnp.array([56, 62])[:, None]
        scale = 1.0 / np.sqrt(Dh)
        out = sp_decode_attention(q, k, v, mask, mesh, scale=scale)
        ref = self._ref(q, k, v, mask, scale)
        assert np.abs(np.asarray(out)).max() > 0.1
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_composed_mesh(self):
        from bcg_tpu.ops.ring_attention import sp_decode_attention

        mesh = build_mesh(dp=2, tp=2, sp=2)
        B, S, H, Hkv, Dh = 4, 32, 4, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, H, Dh))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, Dh))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, Dh))
        mask = jnp.arange(S)[None, :] < jnp.array([32, 5, 17, 1])[:, None]
        scale = 1.0 / np.sqrt(Dh)
        out = sp_decode_attention(q, k, v, mask, mesh, scale=scale)
        ref = self._ref(q, k, v, mask, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_indivisible_cache_raises(self):
        from bcg_tpu.ops.ring_attention import sp_decode_attention

        mesh = build_mesh(dp=1, tp=1, sp=8)
        with pytest.raises(ValueError, match="divisible"):
            sp_decode_attention(
                jnp.zeros((1, 2, 8)), jnp.zeros((1, 12, 2, 8)),
                jnp.zeros((1, 12, 2, 8)), jnp.ones((1, 12), bool), mesh,
            )

    @pytest.mark.parametrize("dims", [(1, 1, 4), (2, 2, 2)])
    def test_int8_cache_local_dequant_matches(self, dims):
        """The int8 storage layout [B, Hkv, S, Dh]: each shard
        dequantizes only its local slice; result must equal full-cache
        attention over the fully-dequantized cache.  Parametrized over a
        composed dp x tp x sp mesh so the quantized kv/scales shard
        specs execute with dp/tp actually bound."""
        from bcg_tpu.models.transformer import _xla_attention
        from bcg_tpu.ops.decode_attention import dequantize_kv, quantize_kv
        from bcg_tpu.ops.ring_attention import sp_decode_attention

        dp, tp, sp = dims
        mesh = build_mesh(dp=dp, tp=tp, sp=sp)
        B, S, H, Hkv, Dh = 2, 32, 4, 2, 16
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(kq, (B, H, Dh), jnp.float32)
        k_full = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.float32)
        v_full = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.float32)
        # Engine storage layout: [B, Hkv, S, Dh] + scales [B, Hkv, S].
        kq8, ks = quantize_kv(k_full.transpose(0, 2, 1, 3))
        vq8, vs = quantize_kv(v_full.transpose(0, 2, 1, 3))
        mask = jnp.arange(S)[None, :] < jnp.array([32, 11])[:, None]
        scale = 1.0 / np.sqrt(Dh)

        out = sp_decode_attention(q, kq8, vq8, mask, mesh, scale=scale,
                                  k_scale=ks, v_scale=vs)
        k_deq = dequantize_kv(kq8, ks).transpose(0, 2, 1, 3)
        v_deq = dequantize_kv(vq8, vs).transpose(0, 2, 1, 3)
        ref = _xla_attention(q[:, None], k_deq, v_deq,
                             mask[:, None, :], scale)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("sp", [2, 4])
    def test_chunk_queries_match_full_attention(self, sp):
        """K>1 chunks (the fast-forward loop's shape): per-query masks
        over the sharded cache, incl. intra-chunk causal structure."""
        from bcg_tpu.models.transformer import _xla_attention
        from bcg_tpu.ops.ring_attention import sp_chunk_decode_attention

        mesh = build_mesh(dp=1, tp=1, sp=sp)
        B, K, S, H, Hkv, Dh = 2, 4, 32, 4, 2, 16
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(kq, (B, K, H, Dh), jnp.float32)
        k = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.float32)
        v = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.float32)
        # Each chunk query attends a row-specific prefix plus its own
        # causally-visible chunk slots (as decode_chunk builds it).
        prior = [10, 3]
        mask_np = np.zeros((B, K, S), bool)
        for b in range(B):
            for j in range(K):
                mask_np[b, j, :prior[b] + j + 1] = True
        mask = jnp.asarray(mask_np)
        out = sp_chunk_decode_attention(q, k, v, mask, mesh)
        ref = _xla_attention(q, k, v, mask, 1.0 / np.sqrt(Dh))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestSequenceParallelPrefill:
    """prefill_sp (ring attention over the sp mesh axis) must reproduce
    the single-device prefill exactly: same last-position logits, same
    KV cache — for list-form layers and for the stacked lax.scan form."""

    @pytest.mark.parametrize("stacked", [False, True])
    def test_matches_plain_prefill(self, stacked):
        from bcg_tpu.models.transformer import (
            init_kv_cache, prefill, prefill_sp, stack_layer_params,
        )

        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        if stacked:
            params = stack_layer_params(params)
        mesh = build_mesh(dp=1, tp=1, sp=4)
        B, L, S = 3, 64, 96
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                                    spec.vocab_size)
        pad = jnp.array([0, 7, 33])
        valid = jnp.arange(L)[None, :] >= pad[:, None]
        tokens = jnp.where(valid, tokens, 0)

        ref_logits, ref_cache = prefill(
            params, spec, tokens, valid,
            init_kv_cache(spec, B, S, stacked=stacked),
        )
        sp_logits, sp_cache = prefill_sp(
            params, spec, tokens, valid,
            init_kv_cache(spec, B, S, stacked=stacked),
            mesh,
        )
        # bf16 activations accumulate ~0.05 abs noise through the layers
        # when the reduction order changes; greedy choice must not move.
        np.testing.assert_allclose(
            np.asarray(sp_logits, np.float32),
            np.asarray(ref_logits, np.float32),
            rtol=5e-2, atol=6e-2,
        )
        assert (np.argmax(np.asarray(sp_logits), -1)
                == np.argmax(np.asarray(ref_logits), -1)).all()
        # Compare cache only at valid token slots: pad positions hold
        # whatever the masked attention produced there (never attended
        # later — suffix calls mask prefix slots by validity).
        vmask = np.zeros((B, S), bool)
        vmask[:, :L] = np.asarray(valid)
        for a, b in zip(jax.tree.leaves(sp_cache), jax.tree.leaves(ref_cache)):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            if a.ndim == 4 and a.shape[:2] == (B, S):  # [B, S, Hkv, Dh]
                a, b = a[vmask], b[vmask]
            elif a.ndim == 5:  # stacked [Lyr, B, S, Hkv, Dh]
                a, b = a[:, vmask], b[:, vmask]
            np.testing.assert_allclose(a, b, rtol=5e-2, atol=6e-2)

    @pytest.mark.slow
    def test_chunked_ring_matches_one_pass_ring(self):
        """prefill_chunk_at's ring branch (chunk attends the WHOLE
        sp-sharded cache) must reproduce one-pass prefill_sp: same final
        logits, same cache at written slots — chunk boundaries invisible
        under sp."""
        from bcg_tpu.models.transformer import (
            init_kv_cache, prefill_chunk_at, prefill_sp,
        )

        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        mesh = build_mesh(dp=1, tp=1, sp=4)
        B, L, C, S = 2, 64, 32, 96
        tokens = jax.random.randint(jax.random.PRNGKey(2), (B, L), 0,
                                    spec.vocab_size)
        valid = jnp.ones((B, L), bool)

        ref_logits, ref_cache = prefill_sp(
            params, spec, tokens, valid, init_kv_cache(spec, B, S), mesh,
        )

        cache = init_kv_cache(spec, B, S)
        H = L - C  # fixed history window, as the engine drives it
        ring = (mesh, "sp")
        for start in (0, C):
            hist = jnp.zeros((B, H), bool).at[:, :start].set(True)
            logits, cache = prefill_chunk_at(
                params, spec, tokens[:, start:start + C],
                valid[:, start:start + C], cache, hist,
                jnp.full((B,), start, jnp.int32), jnp.int32(start),
                ring=ring,
            )
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(ref_logits, np.float32), rtol=5e-2, atol=6e-2,
        )
        assert (np.argmax(np.asarray(logits), -1)
                == np.argmax(np.asarray(ref_logits), -1)).all()
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
            a = np.asarray(a, np.float32)[:, :L]
            b = np.asarray(b, np.float32)[:, :L]
            np.testing.assert_allclose(a, b, rtol=5e-2, atol=6e-2)

    def test_suffix_via_ring_chunk_matches_prefill_with_prefix(self):
        """The cached-prefix suffix path under sp: the suffix served as
        ONE ring chunk (prefill_chunk_at, whole-sharded-cache mask) must
        match prefill_with_prefix — same final logits and suffix cache —
        including rows with DIFFERENT cached-prefix lengths."""
        from bcg_tpu.models.transformer import (
            init_kv_cache, prefill, prefill_chunk_at, prefill_with_prefix,
        )

        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        mesh = build_mesh(dp=1, tp=1, sp=4)
        B, P, Ls, S = 2, 32, 32, 96
        key = jax.random.PRNGKey(4)
        kp, ks = jax.random.split(key)
        # Per-row prefix lengths 32 and 20 (row 1 left-padded).
        plens = jnp.array([32, 20])
        prefix_valid = jnp.arange(P)[None, :] >= (P - plens)[:, None]
        ptoks = jnp.where(
            prefix_valid,
            jax.random.randint(kp, (B, P), 0, spec.vocab_size), 0,
        )
        suffix = jax.random.randint(ks, (B, Ls), 0, spec.vocab_size)
        sv = jnp.ones((B, Ls), bool)

        def with_prefix_cache(f):
            cache = init_kv_cache(spec, B, S)
            _, cache = prefill(params, spec, ptoks, prefix_valid, cache)
            return f(cache)

        ref_logits, ref_cache = with_prefix_cache(lambda c: prefill_with_prefix(
            params, spec, suffix, sv, c, prefix_valid, plens,
        ))
        sp_logits, sp_cache = with_prefix_cache(lambda c: prefill_chunk_at(
            params, spec, suffix, sv, c, prefix_valid,
            plens.astype(jnp.int32), jnp.int32(P), ring=(mesh, "sp"),
        ))
        np.testing.assert_allclose(
            np.asarray(sp_logits, np.float32),
            np.asarray(ref_logits, np.float32), rtol=5e-2, atol=6e-2,
        )
        assert (np.argmax(np.asarray(sp_logits), -1)
                == np.argmax(np.asarray(ref_logits), -1)).all()
        for a, b in zip(jax.tree.leaves(sp_cache), jax.tree.leaves(ref_cache)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32)[:, P:P + Ls],
                np.asarray(b, np.float32)[:, P:P + Ls],
                rtol=5e-2, atol=6e-2,
            )

    def test_indivisible_length_raises(self):
        from bcg_tpu.models.transformer import init_kv_cache, prefill_sp

        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        mesh = build_mesh(dp=1, tp=1, sp=4)
        tokens = jnp.zeros((1, 30), jnp.int32)
        with pytest.raises(ValueError, match="divisible"):
            prefill_sp(params, spec, tokens, jnp.ones((1, 30), bool),
                       init_kv_cache(spec, 1, 32), mesh)


class TestSPMDGameStep:
    def setup_method(self):
        self.mesh = build_mesh(dp=8, tp=1, sp=1)

    def test_exchange_matches_topology(self):
        topo = NetworkTopology.ring(8)
        mask = jnp.asarray(topo.neighbor_mask())
        values = jnp.asarray([10, 11, 12, 13, 14, -1, 16, 17], jnp.int32)
        received = np.asarray(exchange_values(values, mask, self.mesh))
        # agent 0 hears only ring neighbours 1 and 7
        assert received[0, 1] == 11 and received[0, 7] == 17
        assert received[0, 2] == -1  # non-neighbour
        assert received[4, 5] == -1  # agent 5 abstained
        assert received[3, 3] == -1  # no self-delivery

    @staticmethod
    def _topology_n64(topo_name):
        return {
            "ring": lambda: NetworkTopology.ring(64),
            "grid": lambda: NetworkTopology.grid(8, 8),
            "full": lambda: NetworkTopology.fully_connected(64),
        }[topo_name]()

    @pytest.mark.parametrize("topo_name", ["ring", "grid", "full"])
    def test_masked_exchange_matches_spmd_body_n64(self, topo_name):
        """The shard_map exchange (exchange_values) at the 64-agent
        one-agent-per-chip scale, for every stock topology, against the
        host expression of the same mask in NumPy: a received cell is
        the sender's value where ``mask[i, j]`` and the sender did not
        abstain, else -1, and a receiver's deliveries are the count of
        such cells (what the orchestrator's message accounting reads)."""
        n = 64
        mask = self._topology_n64(topo_name).receiver_mask()
        rng = np.random.default_rng(16)
        values_np = rng.integers(0, 50, size=n).astype(np.int32)
        values_np[rng.choice(n, size=7, replace=False)] = -1  # abstainers
        spmd = np.asarray(exchange_values(
            jnp.asarray(values_np), jnp.asarray(mask), self.mesh
        ))
        delivered = mask & (values_np >= 0)[None, :]
        np.testing.assert_array_equal(
            spmd, np.where(delivered, values_np[None, :], -1)
        )
        np.testing.assert_array_equal(
            (spmd >= 0).sum(axis=1), delivered.sum(axis=1)
        )

    @pytest.mark.parametrize("topo_name", ["ring", "grid", "full"])
    def test_matrix_exchange_matches_spmd_form_n64(self, topo_name):
        """The equivocation-capable proposal-MATRIX exchange
        (exchange_proposals) at the 64-agent scale against the host
        expression in NumPy: the equivocated matrix is built from
        ``scenarios/strategies.py::equivocation_value`` as the host
        path builds it, and a received cell is the matrix's where
        ``mask[i, j]`` and the sender did not abstain, else -1; with
        nobody equivocating the matrix form reduces to the
        scalar-broadcast exchange."""
        from bcg_tpu.parallel.game_step import exchange_proposals
        from bcg_tpu.scenarios.strategies import equivocation_value

        n, lo, hi = 64, 0, 50
        mask_np = self._topology_n64(topo_name).receiver_mask()
        mask = jnp.asarray(mask_np)
        rng = np.random.default_rng(18)
        values_np = rng.integers(lo, hi + 1, size=n).astype(np.int32)
        values_np[rng.choice(n, size=7, replace=False)] = -1  # abstainers
        equiv_np = np.zeros(n, dtype=bool)
        equiv_np[rng.choice(n, size=9, replace=False)] = True
        plain_np = np.broadcast_to(values_np[None, :], (n, n))
        matrix_np = np.where(
            equiv_np[None, :] & (values_np >= 0)[None, :],
            equivocation_value(
                values_np[None, :], np.arange(n, dtype=np.int32)[:, None],
                lo, hi,
            ),
            plain_np,
        ).astype(np.int32)
        spmd = np.asarray(
            exchange_proposals(jnp.asarray(matrix_np), mask, self.mesh)
        )
        np.testing.assert_array_equal(
            spmd, np.where(mask_np & (matrix_np >= 0), matrix_np, -1)
        )
        # An equivocating non-abstaining sender delivers receiver-
        # dependent values to its delivered cells; receiver 0's cell
        # (when delivered) carries the base value.
        for j in np.flatnonzero(equiv_np & (values_np >= 0)):
            delivered = spmd[mask_np[:, j], j]
            if delivered.size > 1:
                assert len(set(delivered.tolist())) > 1, j
            if mask_np[0, j]:
                assert spmd[0, j] == values_np[j]
        # Nobody equivocating: the matrix path reduces to the scalar form.
        scalar = np.asarray(exchange_values(
            jnp.asarray(values_np), mask, self.mesh
        ))
        np.testing.assert_array_equal(
            np.asarray(exchange_proposals(
                jnp.asarray(plain_np), mask, self.mesh
            )),
            scalar,
        )

    def test_exchange_values_global_matches_sharded_form(self):
        """The sweep tier's cooperative (dp-across-hosts) exchange
        (exchange_values_global: host inputs -> global placement ->
        masked gather -> replicated output) must be value-identical to
        the sharded single-host form on the same mesh — the hermetic
        pin for the arm a multi-process backend runs across DCN."""
        from bcg_tpu.parallel.game_step import exchange_values_global

        topo = NetworkTopology.ring(8)
        mask_np = np.asarray(topo.neighbor_mask())
        values_np = np.asarray([10, 11, 12, 13, 14, -1, 16, 17], np.int32)
        sharded = np.asarray(exchange_values(
            jnp.asarray(values_np), jnp.asarray(mask_np), self.mesh
        ))
        replicated = exchange_values_global(values_np, mask_np, self.mesh)
        np.testing.assert_array_equal(sharded, replicated)

    def test_tally_matches_host_game(self):
        game = ByzantineConsensusGame(num_honest=8, num_byzantine=0, seed=0)
        votes_py = {f"agent_{i}": (True if i < 6 else (None if i == 6 else False))
                    for i in range(8)}
        info = game.get_all_termination_votes(votes_py)
        votes = jnp.asarray([1] * 6 + [-1, 0], jnp.int32)
        tally = tally_votes(votes, self.mesh)
        assert int(tally["stop"]) == info["total_stop_votes"]
        assert int(tally["abstain"]) == info["total_abstentions"]
        assert bool(tally["terminate"]) == game.should_terminate_by_vote(votes_py)

    def test_termination_threshold_edge(self):
        # 5/8 < 2/3, 6/8 >= 2/3 — must match reference arithmetic.
        for stops, expect in ((5, False), (6, True)):
            votes = jnp.asarray([1] * stops + [0] * (8 - stops), jnp.int32)
            assert bool(tally_votes(votes, self.mesh)["terminate"]) is expect

    def test_consensus_check_matches_host_game(self):
        game = ByzantineConsensusGame(num_honest=6, num_byzantine=2, seed=5)
        ids = sorted(game.agents)
        target = next(
            st.initial_value for st in game.agents.values() if not st.is_byzantine
        )
        for aid in ids:
            game.update_agent_proposal(aid, target)
        game.apply_proposals()
        expect_ok, expect_pct = game.check_consensus()

        values = jnp.asarray(
            [game.agents[a].current_value for a in ids], jnp.int32
        )
        byz = jnp.asarray([game.agents[a].is_byzantine for a in ids])
        inits = jnp.asarray(
            [game.agents[a].initial_value if game.agents[a].initial_value is not None
             else -1 for a in ids], jnp.int32,
        )
        out = check_consensus_spmd(values, byz, inits, self.mesh)
        assert bool(out["has_consensus"]) == expect_ok
        assert abs(float(out["agreement_pct"]) - expect_pct) < 1e-5

    def test_agreement_pct_uses_modal_value(self):
        # Host: Counter([1,2,2,...]).most_common -> agreement = mode share.
        game = ByzantineConsensusGame(num_honest=8, num_byzantine=0, seed=2)
        ids = sorted(game.agents)
        vals = [1, 2, 2, 2, 3, 3, 2, 1]
        for aid, v in zip(ids, vals):
            game.update_agent_proposal(aid, v)
        game.apply_proposals()
        _, expect_pct = game.check_consensus()

        values = jnp.asarray(vals, jnp.int32)
        byz = jnp.zeros(8, bool)
        inits = jnp.asarray(
            [game.agents[a].initial_value for a in ids], jnp.int32
        )
        out = check_consensus_spmd(values, byz, inits, self.mesh)
        assert abs(float(out["agreement_pct"]) - expect_pct) < 1e-4
        assert int(out["consensus_value"]) == 2  # modal value

    def test_consensus_rejects_non_initial_value(self):
        byz = jnp.zeros(8, bool)
        inits = jnp.asarray([1, 2, 3, 4, 5, 6, 7, 8], jnp.int32)
        values = jnp.full((8,), 25, jnp.int32)  # unanimous but not initial
        out = check_consensus_spmd(values, byz, inits, self.mesh)
        assert not bool(out["has_consensus"])

    def test_full_round_arrays_jit(self):
        topo = NetworkTopology.fully_connected(8)
        mask = jnp.asarray(topo.neighbor_mask())
        proposals = jnp.full((8,), 7, jnp.int32)
        votes = jnp.ones((8,), jnp.int32)
        byz = jnp.zeros(8, bool)
        inits = jnp.asarray([7, 3, 9, 7, 5, 2, 8, 4], jnp.int32)
        received, tally, consensus = spmd_round_arrays(
            proposals, votes, mask, byz, inits, self.mesh
        )
        assert received.shape == (8, 8)
        assert bool(tally["terminate"])
        assert bool(consensus["has_consensus"])  # 7 is agent_0's initial


class TestSPMDExchangeIntegration:
    """The orchestrator's SPMD broadcast/receive path must be
    indistinguishable from the host A2A protocol at the game level."""

    def _run(self, spmd: bool, topology: str = "fully_connected"):
        import dataclasses

        from bcg_tpu.config import BCGConfig
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        base = BCGConfig()
        cfg = dataclasses.replace(
            base,
            game=dataclasses.replace(
                base.game, num_honest=6, num_byzantine=2, max_rounds=6, seed=3
            ),
            network=dataclasses.replace(
                base.network, topology_type=topology, spmd_exchange=spmd
            ),
            engine=dataclasses.replace(base.engine, backend="fake"),
            metrics=dataclasses.replace(base.metrics, save_results=False),
        )
        sim = BCGSimulation(config=cfg)
        try:
            while not sim.game.game_over:
                sim.run_round()
            stats = sim.game.get_statistics()
            msgs = (sim.network.protocol.get_total_message_count()
                    + sim._spmd_message_count)
            return stats, msgs
        finally:
            sim.close()

    def test_identical_game_stats_fully_connected(self):
        host_stats, host_msgs = self._run(spmd=False)
        spmd_stats, spmd_msgs = self._run(spmd=True)
        assert spmd_stats == host_stats
        assert spmd_msgs == host_msgs

    def test_identical_game_stats_ring(self):
        host_stats, host_msgs = self._run(spmd=False, topology="ring")
        spmd_stats, spmd_msgs = self._run(spmd=True, topology="ring")
        assert spmd_stats == host_stats
        assert spmd_msgs == host_msgs

    def test_identical_game_stats_asymmetric_custom(self):
        # Directed adjacency: delivery must follow the SENDER's out-edges
        # (host protocol semantics), not the receiver's rows.
        import dataclasses

        from bcg_tpu.config import BCGConfig
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        adj = {0: [1, 2], 1: [2], 2: [0], 3: [0, 1, 2]}
        results = []
        for spmd in (False, True):
            base = BCGConfig()
            cfg = dataclasses.replace(
                base,
                game=dataclasses.replace(
                    base.game, num_honest=3, num_byzantine=1, max_rounds=5, seed=9
                ),
                network=dataclasses.replace(
                    base.network, topology_type="custom",
                    custom_adjacency=adj, spmd_exchange=spmd,
                ),
                engine=dataclasses.replace(base.engine, backend="fake"),
                metrics=dataclasses.replace(base.metrics, save_results=False),
            )
            sim = BCGSimulation(config=cfg)
            try:
                while not sim.game.game_over:
                    sim.run_round()
                results.append((
                    sim.game.get_statistics(),
                    sim.network.protocol.get_total_message_count()
                    + sim._spmd_message_count,
                    {aid: a.received_proposals for aid, a in sim.agents.items()},
                ))
            finally:
                sim.close()
        assert results[0][0] == results[1][0]
        assert results[0][1] == results[1][1]
        assert results[0][2] == results[1][2]
