"""Plain float32 reference of the decoder the configurations describe:
pre-norm blocks of grouped-query attention (per-head RMS norm on q and k,
rotate-half RoPE) and a SwiGLU MLP, RMS norm and an untied head on top.

It imports nothing of the program and takes nothing the program made.
The weights are the deployment's own recipe, restated here from the
configuration file: leaf ``k`` of the plan is ``normal(key_k, shape) /
sqrt(fan_in)`` rounded to bfloat16, ``key_k`` the k-th of
``split(PRNGKey(seed), 4 + 7 * layers)`` in the order embed, then per
layer wq wk wv wo w_gate w_up w_down, then the head; norm vectors are
ones.  ``weights="bf16"`` is the model the configuration serves in W8A8;
``weights="int4"`` rounds every matmul weight to grouped 4-bit first (the
precision below the stated one: the control).

Everything runs layer by layer: one block's weights are made inside the
jitted block from their keys, used on all rows, and dropped, so an 8B
model needs well under a chip.  Matmuls run at ``highest`` precision.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 512
_INT4_GROUP = 128


def _leaf(key, shape, weights: str):
    w = (jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0]))
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    if weights == "bf16":
        return w
    if weights == "int4":
        g = math.gcd(_INT4_GROUP, shape[0])
        wg = w.reshape(shape[0] // g, g, shape[1])
        scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True), 1e-12) / 7.0
        return (jnp.clip(jnp.round(wg / scale), -7, 7) * scale).reshape(shape)
    raise ValueError(f"unknown weight rounding {weights!r}")


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, 2 * half, 2, dtype=jnp.float32) / (2 * half)))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, length, groups):
    """One row.  q [T, H, Dh]; k, v [T, Hkv, Dh]; causal, keys below
    ``length``.  Query blocks keep the score matrix small."""
    T, H, Dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(T // _QUERY_BLOCK, _QUERY_BLOCK, hkv, groups, Dh)
    kpos = jnp.arange(T)

    def block(args):
        i, qb = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=_HIGHEST) / math.sqrt(Dh)
        qpos = i * _QUERY_BLOCK + jnp.arange(_QUERY_BLOCK)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < length)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=_HIGHEST)

    out = jax.lax.map(block, (jnp.arange(T // _QUERY_BLOCK), qg))
    return out.reshape(T, H * Dh)


@partial(jax.jit, static_argnames=("dims", "weights"))
def _block(x, lengths, keys, dims, weights):
    """x [R, T, D] -> [R, T, D]: one decoder block, weights made here."""
    D, F, H, Hkv, Dh, eps, theta = dims
    mm = partial(jnp.matmul, precision=_HIGHEST)
    wq, wk, wv = (_leaf(keys[i], (D, n), weights)
                  for i, n in ((0, H * Dh), (1, Hkv * Dh), (2, Hkv * Dh)))
    R, T, _ = x.shape
    h = _rms(x, eps)
    pos = jnp.arange(T)
    q = _rms(mm(h, wq).reshape(R, T, H, Dh), eps)
    k = _rms(mm(h, wk).reshape(R, T, Hkv, Dh), eps)
    v = mm(h, wv).reshape(R, T, Hkv, Dh)
    q = jax.vmap(lambda a: _rope(a, pos, theta))(q)
    k = jax.vmap(lambda a: _rope(a, pos, theta))(k)
    attn = jax.lax.map(
        lambda a: _attention(a[0], a[1], a[2], a[3], H // Hkv), (q, k, v, lengths)
    )
    x = x + mm(attn, _leaf(keys[3], (H * Dh, D), weights))
    h = _rms(x, eps)
    gate = jax.nn.silu(mm(h, _leaf(keys[4], (D, F), weights)))
    up = mm(h, _leaf(keys[5], (D, F), weights))
    return x + mm(gate * up, _leaf(keys[6], (F, D), weights))


@partial(jax.jit, static_argnames=("shape",))
def _embed(tokens, key, shape):
    return _leaf(key, shape, "bf16")[tokens]


@partial(jax.jit, static_argnames=("shape", "cols", "eps", "weights"))
def _head(x, key, shape, cols, eps, weights):
    w = _leaf(key, shape, weights)[:, :cols]
    return jnp.matmul(_rms(x, eps), w, precision=_HIGHEST)


def logits(cfg: dict, seed: int, tokens: np.ndarray, lengths: np.ndarray,
           cols: int, weights: str = "bf16") -> np.ndarray:
    """Logits of the first ``cols`` vocabulary entries at every position.

    ``tokens`` [R, T] holds each row's ids from position 0, right-padded;
    ``lengths`` [R] the count of real ids.  T must be a multiple of 512.
    Returns float32 [R, T, cols]; positions at or beyond a row's length
    are meaningless.
    """
    R, T = tokens.shape
    if T % _QUERY_BLOCK:
        raise ValueError(f"T={T} is not a multiple of {_QUERY_BLOCK}")
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    dims = (D, cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]))
    # The recipe splits the keys under the process's own RNG setting and
    # draws every leaf under the partitionable one (the same values on
    # one chip and on a mesh).
    keys = jax.random.split(jax.random.PRNGKey(seed), 4 + 7 * L)
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        x = _embed(jnp.asarray(tokens, jnp.int32), keys[0], (V, D))
        lens = jnp.asarray(lengths, jnp.int32)
        for layer in range(L):
            x = _block(x, lens, keys[1 + 7 * layer: 8 + 7 * layer], dims, weights)
        out = _head(x, keys[1 + 7 * L], (D, V), cols, dims[5], weights)
        return np.asarray(out, np.float32)
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
