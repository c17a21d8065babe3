"""Pallas decode-attention kernel (interpret mode on CPU) + int8 KV."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.models.transformer import _xla_attention
from bcg_tpu.ops.decode_attention import (
    decode_attention,
    dequantize_kv,
    quantize_kv,
)


def _case(key, B, S, H, Hkv, Dh):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    mask = jnp.arange(S)[None, :] < lens[:, None]   # [B, S]
    return q, k, v, mask


def _reference(q, k, v, mask, scale):
    # decode step == T=1 full attention
    out = _xla_attention(q[:, None], k, v, mask[:, None, :], scale)
    return out[:, 0]


@pytest.mark.parametrize("shape", [
    (2, 256, 4, 2, 128),    # GQA
    (1, 512, 8, 8, 128),    # MHA, exact block
    (3, 700, 4, 1, 128),    # ragged S, all heads share one kv head
])
def test_matches_reference(shape):
    B, S, H, Hkv, Dh = shape
    q, k, v, mask = _case(jax.random.PRNGKey(0), B, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = _reference(q, k, v, mask, scale)
    out = decode_attention(q, k, v, mask, scale, block_s=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_int8_kv_close_to_fp():
    B, S, H, Hkv, Dh = 2, 384, 4, 2, 128
    q, k, v, mask = _case(jax.random.PRNGKey(1), B, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = _reference(q, k, v, mask, scale)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    # decode_attention consumes the cache's int8 layout: k/v
    # [B, Hkv, S, Dh], scales [B, Hkv, S]
    out = decode_attention(q, kq.transpose(0, 2, 1, 3),
                           vq.transpose(0, 2, 1, 3), mask, scale,
                           k_scale=ks.transpose(0, 2, 1),
                           v_scale=vs.transpose(0, 2, 1),
                           block_s=128, interpret=True)
    # int8 with per-(token, head) scales: ~1% relative error budget
    err = np.abs(np.asarray(out) - np.asarray(ref)).max()
    assert err < 0.05, err


def _chunk_case(key, B, K, S, H, Hkv, Dh):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, K, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    lens = jax.random.randint(ks[3], (B,), 1, S - K)
    # Per-position mask: each chunk position additionally sees its causal
    # predecessors, mirroring decode_chunk's mask construction.
    base = jnp.arange(S)[None, None, :] < lens[:, None, None]  # [B, 1, S]
    causal = (
        jnp.arange(S)[None, None, :]
        <= (lens[:, None] + jnp.arange(K)[None, :])[:, :, None]
    )
    mask = jnp.broadcast_to(base, (B, K, S)) | (causal & ~base)
    return q, k, v, mask


@pytest.mark.parametrize("shape", [
    (2, 4, 256, 4, 2, 128),   # GQA, FF_CHUNK-sized chunk
    (1, 4, 300, 8, 8, 128),   # MHA, ragged S
    (3, 2, 256, 4, 1, 128),   # group=4, K=2
])
def test_chunk_matches_reference(shape):
    from bcg_tpu.ops.decode_attention import chunk_decode_attention

    B, K, S, H, Hkv, Dh = shape
    q, k, v, mask = _chunk_case(jax.random.PRNGKey(4), B, K, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = _xla_attention(q, k, v, mask, scale)
    out = chunk_decode_attention(q, k, v, mask, scale, block_s=128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunk_int8_close_to_fp():
    from bcg_tpu.ops.decode_attention import chunk_decode_attention

    B, K, S, H, Hkv, Dh = 2, 4, 256, 4, 2, 128
    q, k, v, mask = _chunk_case(jax.random.PRNGKey(5), B, K, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = _xla_attention(q, k, v, mask, scale)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    out = chunk_decode_attention(q, kq.transpose(0, 2, 1, 3),
                                 vq.transpose(0, 2, 1, 3), mask, scale,
                                 k_scale=ks.transpose(0, 2, 1),
                                 v_scale=vs.transpose(0, 2, 1),
                                 block_s=128, interpret=True)
    err = np.abs(np.asarray(out) - np.asarray(ref)).max()
    assert err < 0.05, err


def _int8_stack(key, layers, B, S, Hkv, Dh):
    """A stacked int8 cache's leaves, every layer drawn differently:
    k/v [Lyr, B, Hkv, S, Dh] int8, scales [Lyr, B, Hkv, S] f32."""
    kk, kv = jax.random.split(key)
    out = []
    for kx in (kk, kv):
        q, sc = quantize_kv(jax.random.normal(kx, (layers, B, S, Hkv, Dh)))
        out += [q.transpose(0, 1, 3, 2, 4), sc.transpose(0, 1, 3, 2)]
    k, ks, v, vs = out
    return k, v, ks, vs


@pytest.mark.parametrize("group", [4, 1, 5])     # 5: rows padded to 8
@pytest.mark.parametrize("chunk", [False, True], ids=["step", "chunk"])
def test_stacked_layer_equals_per_entry(chunk, group):
    """The stacked form (the layer scan's carry with a prefetched layer
    index: no layer's entry sliced out) reads the same blocks as the
    per-entry form handed that layer's slice — bit for bit, at every
    layer index."""
    from bcg_tpu.ops.decode_attention import chunk_decode_attention

    layers, B, K, S, Hkv, Dh = 3, 2, 4, 256, 2, 128
    key = jax.random.PRNGKey(21)
    if chunk:
        q, _, _, mask = _chunk_case(key, B, K, S, Hkv * group, Hkv, Dh)
        attend = chunk_decode_attention
    else:
        q, _, _, mask = _case(key, B, S, Hkv * group, Hkv, Dh)
        attend = decode_attention
    k, v, ks, vs = _int8_stack(jax.random.PRNGKey(22), layers, B, S, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    outs = []
    for li in range(layers):
        entry = attend(q, k[li], v[li], mask, scale, k_scale=ks[li],
                       v_scale=vs[li], block_s=128, interpret=True)
        stacked = attend(q, k, v, mask, scale, k_scale=ks, v_scale=vs,
                         block_s=128, interpret=True, layer=jnp.int32(li))
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(entry))
        outs.append(np.asarray(entry))
    assert not np.array_equal(outs[0], outs[1])   # the layers do differ


def test_stacked_cache_off_the_block_is_an_error():
    """A per-entry cache off the kernel's block is padded; padding a
    stacked one would copy the whole cache every layer and step."""
    layers, B, S, Hkv, Dh = 2, 1, 200, 2, 128
    q, _, _, mask = _case(jax.random.PRNGKey(23), B, S, Hkv, Hkv, Dh)
    k, v, ks, vs = _int8_stack(jax.random.PRNGKey(24), layers, B, S, Hkv, Dh)
    decode_attention(q, k[0], v[0], mask, 1.0, k_scale=ks[0], v_scale=vs[0],
                     block_s=128, interpret=True)
    with pytest.raises(ValueError, match="ALIGN_S"):
        decode_attention(q, k, v, mask, 1.0, k_scale=ks, v_scale=vs,
                         block_s=128, interpret=True, layer=0)
    with pytest.raises(ValueError, match="int8"):   # no bf16 stacked form
        decode_attention(q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                         mask, 1.0, block_s=128, interpret=True, layer=0)


# ------------------------------------------------- the live-block bound

_S, _BLK, _K = 512, 128, 4          # four blocks of the kernel a row


def _ranged_masks(chunk):
    """Masks of four rows whose live ranges differ, and each row's
    (first, last) live block: (1, 2) behind a left pad; (0, 3), which
    touches both ends of the grid; no attendable slot at all; and live
    slots in blocks 0 and 3 alone, a hole of two dead blocks between."""
    slots = np.arange(_S)
    base = np.stack([
        (slots >= 130) & (slots <= 300),
        slots <= _S - 1 - _K,
        np.zeros(_S, bool),
        ((slots >= 5) & (slots <= 90)) | ((slots >= 400) & (slots <= 440)),
    ])
    blocks = [(1, 2), (0, 3), None, (0, 3)]
    if not chunk:
        return jnp.array(base), blocks
    # chunk position k also sees the k + 1 slots written after the row's
    # last live one (decode_chunk's causal part); the dead row sees none
    mask = np.repeat(base[:, None, :], _K, axis=1)
    for b, row in enumerate(base):
        if row.any():
            last = int(np.flatnonzero(row)[-1])
            for k in range(_K):
                mask[b, k, last + 1: last + 2 + k] = True
    return jnp.array(mask), blocks


def _bounded_case(chunk, stacked, group, seed=31):
    """``(attend, q, mask, blocks, cache)``: ``attend(mask, cache)``
    runs the int8 kernel at block 128 over S = 512 in the form the
    parameters name (``cache`` is ``k, v, k_scale, v_scale``)."""
    from bcg_tpu.ops.decode_attention import chunk_decode_attention

    Hkv, Dh, B = 2, 128, 4
    mask, blocks = _ranged_masks(chunk)
    shape = (B, _K, Hkv * group, Dh) if chunk else (B, Hkv * group, Dh)
    q = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    k, v, ks, vs = _int8_stack(jax.random.PRNGKey(seed + 1), 2, B, _S, Hkv, Dh)
    fn = chunk_decode_attention if chunk else decode_attention
    kw = dict(block_s=_BLK, interpret=True)
    if stacked:
        kw["layer"] = jnp.int32(1)
        cache = (k, v, ks, vs)
    else:
        cache = (k[1], v[1], ks[1], vs[1])

    def attend(mask, cache):
        k, v, ks, vs = cache
        return np.asarray(fn(q, k, v, mask, 1.0 / np.sqrt(Dh), k_scale=ks,
                             v_scale=vs, **kw))

    return attend, q, mask, blocks, cache


_FORMS = [
    pytest.param(c, s, id=f"{'chunk' if c else 'step'}-{'stacked' if s else 'entry'}")
    for c in (False, True) for s in (False, True)
]


@pytest.mark.parametrize("group", [4, 1, 5])
@pytest.mark.parametrize("chunk, stacked", _FORMS)
def test_bounded_equals_the_full_range(chunk, stacked, group):
    """The kernel told each row's live blocks serves what the same
    kernel serves told that every block is live, bit for bit: a wholly
    masked block leaves the online softmax as it was.  Rows whose
    ranges differ, among them one that touches block 0 and block
    nS - 1, one with a hole of dead blocks inside its range (masked in
    the body as before) and one with no attendable slot (zeros)."""
    from bcg_tpu.ops.decode_attention import LiveMask, live_block_range, live_slots

    attend, q, mask, blocks, cache = _bounded_case(chunk, stacked, group)
    B = mask.shape[0]
    first, last = live_block_range(*live_slots(mask), _BLK)
    for b, want in enumerate(blocks):
        got = (int(first[b]), int(last[b]))
        assert got == (want or (1, 0)), (b, got)
    out = attend(mask, cache)
    every = jnp.stack([jnp.zeros(B, jnp.int32), jnp.full(B, _S - 1, jnp.int32)])
    full = attend(LiveMask(mask, every), cache)
    np.testing.assert_array_equal(out, full)
    assert not out[2].any() and out[0].any()
    # and it is the attention: the dequantised cache under the same mask
    k, v = (
        dequantize_kv(a[-1] if stacked else a, sc[-1] if stacked else sc)
        .transpose(0, 2, 1, 3) for a, sc in ((cache[0], cache[2]), (cache[1], cache[3])))
    m3 = mask if chunk else mask[:, None, :]
    ref = np.asarray(_xla_attention(q if chunk else q[:, None], k, v, m3,
                                    1.0 / np.sqrt(q.shape[-1])))
    ref = ref if chunk else ref[:, 0]
    live = [b for b, r in enumerate(blocks) if r]
    np.testing.assert_allclose(out[live], ref[live], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("group", [4, 1, 5])
@pytest.mark.parametrize("chunk, stacked", _FORMS)
def test_blocks_outside_the_range_are_never_read(chunk, stacked, group):
    """NaN in the scales (and 127 in K and V) of every block outside a
    row's live range: the output is finite and bit-equal to the clean
    cache's.  An unbounded grid multiplies those blocks by a zero
    probability and ``0 x NaN`` reaches the accumulator."""
    attend, _, mask, blocks, cache = _bounded_case(chunk, stacked, group)
    clean = attend(mask, cache)
    dead = np.ones((mask.shape[0], _S), bool)        # [B, S]
    for b, r in enumerate(blocks):
        if r:
            dead[b, r[0] * _BLK: (r[1] + 1) * _BLK] = False
    dead = jnp.array(dead)[:, None, :]               # over the kv heads

    def poison(a, value):
        at = dead[..., None] if a.ndim > dead.ndim + stacked else dead
        return jnp.where(at, jnp.asarray(value, a.dtype), a)

    k, v, ks, vs = cache
    dirty = attend(mask, (poison(k, 127), poison(v, 127),
                          poison(ks, np.nan), poison(vs, np.nan)))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


# ------------------------------------- scales on the scores and the weights


def _spread_stack(key, layers, B, S, Hkv, Dh):
    """A stacked int8 cache whose K and V scales are drawn apart per
    slot and per KV head, log-uniform over 1e-3 to 1: a scale read on
    the wrong axis or head, or carried into the softmax's denominator,
    moves the output far past rounding."""
    kk, kv, ks, vs = jax.random.split(key, 4)
    shape = (layers, B, Hkv, S, Dh)
    k, v = (jax.random.randint(x, shape, -127, 128).astype(jnp.int8)
            for x in (kk, kv))
    ks, vs = (10.0 ** jax.random.uniform(x, shape[:-1], minval=-3.0, maxval=0.0)
              for x in (ks, vs))
    return k, v, ks, vs


def _spread_case(chunk, stacked, group, dtype=jnp.float32, seed=41):
    """``(attend, q, mask, blocks, cache)`` as :func:`_bounded_case`,
    over :func:`_spread_stack`'s cache and the masks of
    :func:`_ranged_masks`, with ``q`` in ``dtype``."""
    from bcg_tpu.ops.decode_attention import chunk_decode_attention

    Hkv, Dh, B = 2, 128, 4
    mask, blocks = _ranged_masks(chunk)
    shape = (B, _K, Hkv * group, Dh) if chunk else (B, Hkv * group, Dh)
    # small queries, so scores of order one and a softmax that is not
    # one slot's alone
    q = (0.02 * jax.random.normal(jax.random.PRNGKey(seed), shape)).astype(dtype)
    k, v, ks, vs = _spread_stack(jax.random.PRNGKey(seed + 1), 2, B, _S, Hkv, Dh)
    fn = chunk_decode_attention if chunk else decode_attention
    kw = dict(block_s=_BLK, interpret=True)
    if stacked:
        kw["layer"] = jnp.int32(1)
        cache = (k, v, ks, vs)
    else:
        cache = (k[1], v[1], ks[1], vs[1])

    def attend(mask, cache):
        k, v, ks, vs = cache
        return np.asarray(fn(q, k, v, mask, 1.0 / np.sqrt(Dh), k_scale=ks,
                             v_scale=vs, **kw).astype(jnp.float32))

    return attend, q, mask, blocks, cache


def _dequantized_reference(q, mask, cache, chunk, stacked):
    """float32 attention over :func:`dequantize_kv`'s cache, every
    matmul at ``highest``: the entry the kernel attends (layer 1 of a
    stack), q [B, (K,) H, Dh] in float32."""
    k, v, ks, vs = (a[1] if stacked else a for a in cache)
    k, v = (dequantize_kv(x, sc).transpose(0, 2, 1, 3) for x, sc in ((k, ks), (v, vs)))
    q = q.astype(jnp.float32)
    m3 = mask if chunk else mask[:, None, :]
    with jax.default_matmul_precision("highest"):
        ref = _xla_attention(q if chunk else q[:, None], k, v, m3,
                             1.0 / np.sqrt(q.shape[-1]))
    return np.asarray(ref if chunk else ref[:, 0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 4, 8, 5])     # 5: rows padded to 8
@pytest.mark.parametrize("chunk, stacked", _FORMS)
def test_scales_on_scores_and_weights_are_the_dequantized_attention(
        chunk, stacked, group, dtype):
    """The kernel multiplies a slot's K scale into its score and its V
    scale into its softmax weight, never into the denominator: held to
    the float32 attention over the dequantised cache at ``highest``,
    with scales three orders of magnitude apart, slot by slot and head
    by head.  float32 queries keep the matmuls in float32 (rounding
    alone is left); bfloat16 ones round the queries, the weights and the
    output to bfloat16, 2**-8 of a value each."""
    attend, q, mask, blocks, cache = _spread_case(chunk, stacked, group, dtype)
    out = attend(mask, cache)
    ref = _dequantized_reference(q, mask, cache, chunk, stacked)
    live = [b for b, r in enumerate(blocks) if r]
    assert not out[2].any()                          # the row with no slot
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out[live], ref[live], rtol=tol,
                               atol=tol * np.abs(ref[live]).max())


@pytest.mark.parametrize("chunk, stacked", _FORMS)
def test_a_masked_slots_scale_is_never_applied(chunk, stacked):
    """A masked slot inside a live block (the left pad in row 0's first
    block, the hole in row 3's range) whose K and V scales read 1e4: the
    output is bit-equal to the same call with those scales at 1.  The
    score's mask and the weight's zero hold them out."""
    attend, _, mask, blocks, cache = _spread_case(chunk, stacked, group=4)
    k, v, ks, vs = cache
    rows = np.asarray(mask if not chunk else mask.any(axis=1))
    inside = np.zeros_like(rows)
    for b, r in enumerate(blocks):
        if r:
            inside[b, r[0] * _BLK: (r[1] + 1) * _BLK] = True
    masked = jnp.array(inside & ~rows)[:, None, :]    # over the kv heads
    assert masked[0].any() and masked[3].any()

    def scaled(value):
        def put(a):
            return jnp.where(masked, jnp.asarray(value, a.dtype), a)
        return (k, v, put(ks), put(vs))

    big = attend(mask, scaled(1e4))
    assert np.isfinite(big).all()
    np.testing.assert_array_equal(big, attend(mask, scaled(1.0)))


@pytest.mark.parametrize("block", [1024, 512])
@pytest.mark.parametrize("step", [0, 22, 298])
def test_live_block_range_at_the_cells_shape(step, block):
    """The range helper against a plain count, at the benchmark cells'
    shape: ten left-padded prompts of 2,049 to 2,560 tokens on the 4096
    rung, S = 5120, after ``step`` decoded tokens — in both namespaces,
    and :func:`live_block_count` (the engine's counter) beside it."""
    from bcg_tpu.ops.decode_attention import (
        live_block_count, live_block_range, live_slots,
    )

    L, S = 4096, 5120
    lens = np.linspace(2049, 2560, 10).astype(int)
    slots = np.arange(S)
    mask = (slots[None, :] >= (L - lens)[:, None]) & (slots[None, :] <= L + step)
    per_block = mask.reshape(len(lens), S // block, block).any(axis=-1)
    want_first = per_block.argmax(axis=1)
    want_count = per_block.sum(axis=1)              # contiguous: no hole here
    for xp, m in ((np, mask), (jnp, jnp.array(mask))):
        first_slot, last_slot = live_slots(m, xp=xp)
        first, last = live_block_range(first_slot, last_slot, block, xp=xp)
        np.testing.assert_array_equal(np.asarray(first), want_first)
        np.testing.assert_array_equal(np.asarray(last - first + 1), want_count)
    assert live_block_count(L - lens, L + step, block) == want_count.sum()
    # the share of the grid a 299-step decide call works on: at most 4 of
    # 5 blocks at 1024, 6 of 10 at 512 (a prompt of 2,048 or fewer: less)
    assert want_count.max() <= (4 if block == 1024 else 6)


def test_quantize_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 16, 2, 64)) * 4.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 16, 2)
    back = dequantize_kv(q, s)
    # round() error is at most half a quantization step of the row scale;
    # the global absmax bounds every row's scale.
    atol = float(np.abs(np.asarray(x)).max()) / 127 * 0.51
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=atol)


def test_quantize_zero_row_safe():
    x = jnp.zeros((1, 4, 1, 32))
    q, s = quantize_kv(x)
    assert np.isfinite(np.asarray(s)).all()
    assert (np.asarray(dequantize_kv(q, s)) == 0).all()


def test_fully_masked_rows_finite():
    B, S, H, Hkv, Dh = 1, 128, 2, 2, 128
    q, k, v, _ = _case(jax.random.PRNGKey(3), B, S, H, Hkv, Dh)
    mask = jnp.zeros((B, S), bool)
    out = decode_attention(q, k, v, mask, 0.1, block_s=128, interpret=True)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
class TestInt8CacheEndToEnd:
    def test_decode_logits_close_to_bf16(self):
        import jax
        from bcg_tpu.models import init_params, prefill, spec_for_model
        from bcg_tpu.models.transformer import decode_step, init_kv_cache

        spec = spec_for_model("bcg-tpu/tiny-test")
        params = init_params(spec, jax.random.PRNGKey(0))
        B, L = 2, 32
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, spec.vocab_size)
        valid = jnp.ones((B, L), bool)

        outs = []
        for quant in (False, True):
            cache = init_kv_cache(spec, B, L + 4, quantized=quant)
            logits, cache = prefill(params, spec, tokens, valid, cache)
            vm = jnp.zeros((B, L + 4), bool).at[:, : L + 1].set(True)
            tok = jnp.argmax(logits, -1)
            step_logits, _ = decode_step(
                params, spec, tok, jnp.int32(L), jnp.full((B,), L), cache, vm
            )
            outs.append(np.asarray(step_logits))
        # int8 KV introduces small quantization noise; logits must stay
        # close and the argmax should (at tiny scale) agree.
        assert np.abs(outs[0] - outs[1]).max() < 0.15
        assert (outs[0].argmax(-1) == outs[1].argmax(-1)).mean() >= 0.5

    def test_guided_generation_with_int8_cache(self):
        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine

        eng = JaxEngine(EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=1024, kv_cache_dtype="int8",
        ))
        schema = {
            "type": "object",
            "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
            "required": ["decision"],
            "additionalProperties": False,
        }
        out = eng.batch_generate_json(
            [("sys", f"p{i}", schema) for i in range(3)],
            temperature=0.5, max_tokens=48,
        )
        for r in out:
            assert r.get("decision") in ("stop", "continue"), r


class TestServing8BShapes:
    """The exact kernel configuration bench_8b serves (Qwen3-8B dims:
    H=32, Hkv=8, Dh=128, group=4; S a multiple of ALIGN_S, the block
    the all-heads grid picks for itself) — interpret-mode ground truth
    for the shapes whose Mosaic lowering the hardware probes
    (scripts/probe_int8_decode.py) validate.  Round-3 verdict weak #2:
    every kernel must have its serving shape pinned hermetically, so a
    hardware probe failure isolates Mosaic lowering, not math."""

    def test_int8_allheads_8b_serving_shape(self):
        B, S, H, Hkv, Dh = 2, 2048, 32, 8, 128
        q, k, v, mask = _case(jax.random.PRNGKey(11), B, S, H, Hkv, Dh)
        scale = 1.0 / np.sqrt(Dh)
        ref = _reference(q, k, v, mask, scale)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        # block_s=None exercises _pick_block: BLOCK_S at 8 heads of 128.
        out = decode_attention(q, kq.transpose(0, 2, 1, 3),
                               vq.transpose(0, 2, 1, 3), mask, scale,
                               k_scale=ks.transpose(0, 2, 1),
                               v_scale=vs.transpose(0, 2, 1),
                               block_s=None, interpret=True)
        err = np.abs(np.asarray(out) - np.asarray(ref)).max()
        assert err < 0.05, err

    def test_chunk_int8_8b_serving_shape(self):
        from bcg_tpu.ops.decode_attention import chunk_decode_attention

        B, K, S, H, Hkv, Dh = 2, 4, 2048, 32, 8, 128
        q, k, v, mask = _chunk_case(jax.random.PRNGKey(12), B, K, S, H, Hkv, Dh)
        scale = 1.0 / np.sqrt(Dh)
        ref = _xla_attention(q, k, v, mask, scale)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        out = chunk_decode_attention(q, kq.transpose(0, 2, 1, 3),
                                     vq.transpose(0, 2, 1, 3), mask, scale,
                                     k_scale=ks.transpose(0, 2, 1),
                                     v_scale=vs.transpose(0, 2, 1),
                                     block_s=None, interpret=True)
        err = np.abs(np.asarray(out) - np.asarray(ref)).max()
        assert err < 0.05, err

    def test_int8_14b_group5_pads_rows(self):
        """14B dims (H=40, Hkv=8 -> GQA group 5): the wrapper pads the
        query-row axis to the next power of two so the kernel only sees
        probe-validated row counts; outputs must still match the
        unpadded reference exactly (padded rows sliced away)."""
        B, S, H, Hkv, Dh = 2, 2048, 40, 8, 128
        q, k, v, mask = _case(jax.random.PRNGKey(13), B, S, H, Hkv, Dh)
        scale = 1.0 / np.sqrt(Dh)
        ref = _reference(q, k, v, mask, scale)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        out = decode_attention(q, kq.transpose(0, 2, 1, 3),
                               vq.transpose(0, 2, 1, 3), mask, scale,
                               k_scale=ks.transpose(0, 2, 1),
                               v_scale=vs.transpose(0, 2, 1),
                               block_s=None, interpret=True)
        assert out.shape == (B, H, Dh)
        err = np.abs(np.asarray(out) - np.asarray(ref)).max()
        assert err < 0.05, err

    def test_chunk_int8_14b_group5_pads_rows(self):
        from bcg_tpu.ops.decode_attention import chunk_decode_attention

        B, K, S, H, Hkv, Dh = 2, 4, 2048, 40, 8, 128
        q, k, v, mask = _chunk_case(jax.random.PRNGKey(14), B, K, S, H, Hkv, Dh)
        scale = 1.0 / np.sqrt(Dh)
        ref = _xla_attention(q, k, v, mask, scale)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        out = chunk_decode_attention(q, kq.transpose(0, 2, 1, 3),
                                     vq.transpose(0, 2, 1, 3), mask, scale,
                                     k_scale=ks.transpose(0, 2, 1),
                                     v_scale=vs.transpose(0, 2, 1),
                                     block_s=None, interpret=True)
        assert out.shape == (B, K, H, Dh)
        err = np.abs(np.asarray(out) - np.asarray(ref)).max()
        assert err < 0.05, err


class TestEngineCountsLiveBlocks:
    """The tiny engine under a benchmark cell's options with the int8
    kernel attending (interpret mode, block 128: the test steers both,
    the engine has no such option): the block counter against a plain
    count, and the served tokens against a grid with no bound."""

    SCHEMA = {
        "type": "object",
        "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
        "required": ["decision"],
        "additionalProperties": False,
    }
    # prompts a block and more apart, so the rows' first live blocks differ
    ROWS = [("sys " * 90, "user " * 40, SCHEMA), ("sys", "short", SCHEMA),
            ("sys " * 30, "user", SCHEMA)]

    def _engine(self, monkeypatch, options, seen):
        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine
        from bcg_tpu.ops import decode_attention as da

        monkeypatch.setattr(da, "BLOCK_S", 128)     # the block _pick_block picks
        kernel = functools.partial(da.decode_attention, interpret=True)

        def attend(*a, **kw):
            seen["masks"] = seen.get("masks", ()) + (type(a[3]).__name__,)
            return kernel(*a, **kw)

        monkeypatch.setattr(da, "decode_attention", attend)
        engine = JaxEngine(EngineConfig(
            backend="jax", max_model_len=1024, **options))
        engine.decode_attention_impl = "pallas"     # a CPU boot resolves "xla"
        engine._kv_align = 128
        prepare, allocate = engine._prepare_batch, engine._init_cache_sharded

        def prepared(*a):
            seen["tokens"], seen["valid"], seen["L"] = prepare(*a)
            return seen["tokens"], seen["valid"], seen["L"]

        def allocated(B, S):
            seen["S"] = S
            return allocate(B, S)

        monkeypatch.setattr(engine, "_prepare_batch", prepared)
        monkeypatch.setattr(engine, "_init_cache_sharded", allocated)
        return engine

    @pytest.mark.parametrize("cell", ["qwen3-8b-int8", "olmo-hybrid-7b-int8"])
    def test_counter_is_the_plain_count_and_tokens_are_the_full_grids(
            self, cell, cell_engine_options, monkeypatch):
        from bcg_tpu.obs import counters
        from bcg_tpu.ops import decode_attention as da

        seen = {}
        engine = self._engine(monkeypatch, cell_engine_options(cell), seen)
        before = counters.snapshot()
        try:
            served = engine.batch_generate_json(
                self.ROWS, temperature=0.0, max_tokens=40)
            steps = engine.last_decode_steps
        finally:
            engine.shutdown()
        moved = counters.delta(before)
        assert all(o.get("decision") in ("stop", "continue") for o in served)
        # the kernel attended, its range handed over by the decode step
        assert set(seen["masks"]) == {"LiveMask"}

        # the plain count: a block is live in a step if any of its slots
        # is a prompt token or one of the step's decoded ones
        L, S, valid = seen["L"], seen["S"], seen["valid"]
        B, nS = valid.shape[0], S // 128
        assert S % 128 == 0 and nS >= 4 and steps > 1
        live = 0
        for i in range(steps):
            mask = np.zeros((B, S), bool)
            mask[:, :L] = valid
            mask[:, L: L + i + 1] = True
            live += int(mask.reshape(B, nS, 128).any(axis=-1).sum())
        layers = engine._kv_layers
        assert moved["engine.decode.kv_blocks_grid"] == steps * B * nS * layers
        assert moved["engine.decode.kv_blocks_live"] == live * layers
        assert 0 < live < steps * B * nS      # some of the grid is dead
        starts = (~valid).sum(axis=1) // 128  # and the rows start apart
        assert len(set(starts.tolist())) > 1, starts

        # the same call on a grid told that every block is live
        monkeypatch.setattr(da, "live_slots", lambda mask, xp=jnp: xp.stack([
            xp.zeros(mask.shape[0], xp.int32),
            xp.full(mask.shape[0], mask.shape[-1] - 1, xp.int32)]))
        full = self._engine(monkeypatch, cell_engine_options(cell), {})
        before = counters.snapshot()
        try:
            assert full.batch_generate_json(
                self.ROWS, temperature=0.0, max_tokens=40) == served
        finally:
            full.shutdown()
        moved = counters.delta(before)
        assert (moved["engine.decode.kv_blocks_live"]
                == moved["engine.decode.kv_blocks_grid"])    # reads 1.0
