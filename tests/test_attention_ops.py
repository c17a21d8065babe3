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


def test_pad_to():
    x = jnp.ones((2, 3))
    assert _pad_to(x, 1, 4).shape == (2, 4)
    assert _pad_to(x, 0, 2).shape == (2, 3)
