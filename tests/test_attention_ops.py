"""Flash / blockwise attention vs the stock XLA einsum path.

The Pallas kernel itself runs on TPU (and in interpret mode in CI);
the blockwise scan is its everywhere-fallback — both must match
``_xla_attention`` bit-for-reasonable-tolerance on random GQA shapes
with the engine's real masking pattern (left-padded prompts + causal
over a longer KV cache).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.models.transformer import _xla_attention
from bcg_tpu.ops.attention import _pad_to, blockwise_attention, flash_attention


def _random_case(key, B, T, S, H, Hkv, Dh, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, T, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    # Engine-shaped mask: left-padded valid prompt + causal into a cache
    # that is longer than the prompt (decode slots not yet written).
    lens = jax.random.randint(ks[3], (B,), 1, T + 1)
    t_idx = jnp.arange(T)[None, :, None]
    s_idx = jnp.arange(S)[None, None, :]
    start = (T - lens)[:, None, None]
    mask = (t_idx >= start) & (s_idx >= start) & (s_idx <= t_idx)
    # Rows with no attendable key (pad rows) are meaningless: the XLA
    # reference softmaxes uniform over -1e30 there while flash returns 0.
    # Compare only rows that attend to something.
    row_valid = mask.any(axis=-1)[..., None, None]  # [B, T, 1, 1]
    return q, k, v, mask, row_valid


@pytest.mark.parametrize("shape", [
    (2, 64, 64, 4, 2, 32),      # GQA, square
    (1, 17, 40, 4, 4, 16),      # MHA, ragged sizes, cache longer than T
    (3, 128, 200, 8, 2, 64),    # cache longer than prompt
])
def test_blockwise_matches_xla(shape):
    B, T, S, H, Hkv, Dh = shape
    q, k, v, mask, rv = _random_case(jax.random.PRNGKey(0), B, T, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = np.asarray(_xla_attention(q, k, v, mask, scale) * rv)
    out = np.asarray(blockwise_attention(q, k, v, mask, scale, block_kv=64) * rv)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_blockwise_fully_masked_rows_are_finite():
    B, T, S, H, Hkv, Dh = 1, 8, 8, 2, 2, 16
    q, k, v, _, _ = _random_case(jax.random.PRNGKey(1), B, T, S, H, Hkv, Dh)
    mask = jnp.zeros((B, T, S), bool)  # pad rows attend to nothing
    out = blockwise_attention(q, k, v, mask, 0.25, block_kv=8)
    assert np.isfinite(np.asarray(out)).all()


def test_flash_refuses_unaligned_head_dim():
    # flash_attention never stands an XLA path in for itself: a head dim
    # the kernel cannot tile is the caller's (the engine's boot rule's)
    # to route to blockwise_attention by name.
    B, T, S, H, Hkv, Dh = 2, 32, 48, 4, 2, 32
    q, k, v, mask, _ = _random_case(jax.random.PRNGKey(2), B, T, S, H, Hkv, Dh)
    with pytest.raises(ValueError, match="head_dim % 128"):
        flash_attention(q, k, v, mask, 1.0 / np.sqrt(Dh))


def test_pallas_kernel_interpret_mode():
    """Run the production Pallas launch config (interpret=True) on CPU."""
    from bcg_tpu.ops.attention import _pallas_flash

    B, T, S, H, Hkv, Dh = 1, 128, 256, 2, 1, 128
    q, k, v, mask, rv = _random_case(jax.random.PRNGKey(3), B, T, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = _xla_attention(q, k, v, mask, scale) * rv
    out = _pallas_flash(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), mask, scale,
        block_q=128, block_kv=128, interpret=True,
    )
    out = out.transpose(0, 2, 1, 3) * rv
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_pallas_kernel_interpret_14b_chunk_dims():
    """The 14B chunked-prefill geometry (H=40, GQA group 5) through the
    production Pallas launch config in interpret mode — pins the MATH at
    the exact shape scripts/probe_flash_prefill.py lowers on hardware,
    so a probe failure isolates Mosaic lowering, not the kernel logic
    (the same split the int8 serving-shape tests make)."""
    from bcg_tpu.ops.attention import _pallas_flash

    B, T, S, H, Hkv, Dh = 2, 128, 256, 40, 8, 128
    q, k, v, mask, rv = _random_case(jax.random.PRNGKey(7), B, T, S, H, Hkv, Dh)
    scale = 1.0 / np.sqrt(Dh)
    ref = _xla_attention(q, k, v, mask, scale) * rv
    out = _pallas_flash(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), mask, scale,
        block_q=128, block_kv=128, interpret=True,
    )
    out = out.transpose(0, 2, 1, 3) * rv
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _chunk_case(seed, B, C, hist, H, Hkv, lens, dtype=jnp.float32):
    """One chunk program's attention as ``transformer.prefill_chunk_at``
    builds it: a left-padded window of ``hist + C`` positions whose rows
    hold ``lens`` tokens, this chunk its last ``C``; query row ``i``
    attends the valid history slots and the valid chunk keys ``j <= i``.
    Returns q, k, v, the [B, C, hist + C] mask, the rows that attend
    something, and the host's ``hist_valid`` ([B, hist]) and ``valid``
    ([B, C]) the mask is built from."""
    Dh = 128
    window = np.arange(hist + C)[None, :] >= (hist + C - np.asarray(lens))[:, None]
    hist_valid, valid = window[:, :hist], window[:, hist:]
    causal = np.tril(np.ones((C, C), bool))
    mask = np.concatenate([hist_valid[:, None, :] & valid[:, :, None],
                           causal[None] & valid[:, None, :] & valid[:, :, None]],
                          axis=2)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, C, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, hist + C, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, hist + C, Hkv, Dh), dtype)
    row_valid = jnp.asarray(mask.any(axis=-1))[..., None, None]
    return q, k, v, jnp.asarray(mask), row_valid, hist_valid, valid


# (B, C, hist, H, Hkv, lens): the folded grid (a step holds the whole
# GQA group's query block) at the geometries it adapts to.
FOLDED = {
    "group4_chunk": (2, 512, 256, 8, 2, (700, 300)),
    "group1": (2, 512, 256, 2, 2, (768, 100)),
    "group5_two_q_blocks": (1, 512, 256, 10, 2, (600,)),
    "t_below_a_block": (2, 8, 248, 8, 2, (256, 5)),
    "group16_three_q_blocks": (1, 384, 128, 16, 1, (500,)),
    "dead_first_q_block": (2, 384, 128, 16, 1, (200, 130)),
}


@pytest.mark.parametrize("case", list(FOLDED), ids=list(FOLDED))
def test_folded_grid_matches_xla(case):
    from bcg_tpu.ops.attention import flash_blocks

    B, C, hist, H, Hkv, lens = FOLDED[case]
    q, k, v, mask, rv, _, _ = _chunk_case(4, B, C, hist, H, Hkv, lens)
    block_q, _ = flash_blocks(H // Hkv, C, 128)
    if case.endswith("q_blocks"):
        assert -(-C // 128) * 128 // block_q > 1
    scale = 1.0 / np.sqrt(128)
    ref = _xla_attention(q, k, v, mask, scale) * rv
    out = flash_attention(q, k, v, mask, scale, interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out * rv), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    if case == "dead_first_q_block":
        # rows whose whole query block attends nothing: the kernel's
        # guard skips every step of it and the block reads zeros
        assert not np.asarray(mask)[:, :128].any()
        np.testing.assert_array_equal(np.asarray(out[:, :128]), 0.0)


def test_flash_blocks_follow_the_shapes():
    from bcg_tpu.ops.attention import BLOCK_KV, flash_blocks

    assert flash_blocks(4, 512, 128) == (512, BLOCK_KV)    # one query block
    assert flash_blocks(1, 512, 128) == (512, BLOCK_KV)
    assert flash_blocks(5, 512, 128) == (256, BLOCK_KV)
    assert flash_blocks(4, 8, 128) == (128, BLOCK_KV)      # T pads to 128
    assert flash_blocks(4, 8192, 128) == (512, BLOCK_KV)   # 16 query blocks
    assert flash_blocks(1, 8192, 128) == (2048, BLOCK_KV)
    assert flash_blocks(4, 512, 256) == (256, BLOCK_KV)    # wider head, fewer rows
    assert flash_blocks(32, 512, 128) == (128, BLOCK_KV)   # the least block


@pytest.mark.parametrize("H, Hkv, config", [
    (32, 8, "qwen3-8b-int8"), (30, 30, "olmo-hybrid-7b-int8"),
])
def test_chunk_kernel_output_is_what_the_benchmark_reads(H, Hkv, config):
    """At a cell's chunk shape the kernel writes ``bf16[10, H, 512,
    128]``: the shape the configuration's ``trace_names.flash_prefill``
    finds the kernel by and its ``decode_attention`` passes over."""
    import json
    import os
    import re

    B, T, S, Dh = 10, 512, 4096, 128
    args = (jax.ShapeDtypeStruct((B, T, H, Dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, S, Hkv, Dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, S, Hkv, Dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, T, S), jnp.bool_))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, m: flash_attention(q, k, v, m, 0.088))(*args)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    aval = calls[0].outvars[0].aval
    assert (aval.shape, aval.dtype) == ((B, H, T, Dh), jnp.bfloat16)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        names = json.load(f)["trace_names"]
    event = f"closed_call.14 custom-call bf16[{B},{H},{T},{Dh}]"
    assert re.search(names["flash_prefill"], event)
    assert not re.search(names["decode_attention"], event)


@pytest.mark.parametrize("B, C, hist, group, seed", [
    (10, 512, 3584, 4, 0),     # the dense cell's chunk program
    (10, 512, 3584, 1, 1),     # the hybrid's
    (3, 512, 1024, 5, 2),      # two query blocks
    (4, 8, 504, 4, 3),         # T below a block
    (2, 384, 200, 16, 4),      # a history that ends inside a key block
    # a key block that starts at the chunk key equal to the last query
    # of a query block: live by that one diagonal pair
    (4, 384, 129, 16, 5),
])
def test_chunk_steps_count_the_kernels_own_guard(B, C, hist, group, seed):
    """The engine's host count of the flash kernel's live grid steps
    equals the kernel's own ``blk_any`` table from the whole mask."""
    from bcg_tpu.ops.attention import _pad_to, chunk_steps, flash_blocks

    rng = np.random.default_rng(seed)
    for _ in range(3):
        lens = rng.integers(0, hist + C + 1, B)
        start = rng.integers(0, hist + 1)      # the chunk's offset
        window = np.arange(hist + C)[None, :] >= (hist + C - lens)[:, None]
        hist_valid = window[:, :hist].copy()
        hist_valid[:, start:] = False          # past the chunk's own slots
        valid = window[:, hist:]
        causal = np.tril(np.ones((C, C), bool))
        mask = np.concatenate(
            [hist_valid[:, None, :] & valid[:, :, None],
             causal[None] & valid[:, None, :] & valid[:, :, None]], axis=2)
        block_q, block_kv = flash_blocks(group, C, 128)
        mp = np.asarray(_pad_to(_pad_to(jnp.asarray(mask), 1, block_q), 2, block_kv))
        nT, nS = mp.shape[1] // block_q, mp.shape[2] // block_kv
        blk_any = mp.reshape(B, nT, block_q, nS, block_kv).any(axis=(2, 4))
        assert chunk_steps(hist_valid, valid, group, 128) == (
            int(blk_any.sum()), B * nT * nS)


def test_pad_to():
    x = jnp.ones((2, 3))
    assert _pad_to(x, 1, 4).shape == (2, 4)
    assert _pad_to(x, 0, 2).shape == (2, 3)
