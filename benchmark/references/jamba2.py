"""Plain float32 reference of the decoder the configuration
``jamba2-3b-bf16`` describes: Mamba-1 layers with full attention at
every layer ``i % attn_layer_period == attn_layer_offset``, each ``x +
mixer(rmsnorm(x))`` then ``x + swiglu(rmsnorm(x))`` (pre-norm), an RMS
norm and the embedding's transpose as the head (tied).

* ``mamba``: the selective scan, computed here as the TOKEN-BY-TOKEN
  recurrence, a ``lax.scan`` over positions, whatever algorithm the
  program uses.  With x_t the layer's (normed) input::

      [u, z] = x W_in                           (u first; no bias)
      u = silu(conv4(u) + b_conv)
          conv4(u)_t = sum_{i<4} c_i u_{t-3+i} per channel, u_{<0} = 0
      [d, B, C] = u W_x                         (dt_rank, d_state, d_state)
      d, B, C = rmsnorm(d), rmsnorm(B), rmsnorm(C)
      delta = softplus(d W_dt + b_dt) ;  A = -exp(A_log)      [C, N]
      S_t[c, n] = exp(delta_t[c] A[c, n]) S_{t-1}[c, n] + delta_t[c] B_t[n] u_t[c]
      y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] u_t[c] ,  S_0 = 0
      out = (y * silu(z)) W_out

* ``attention``: causal multi-query attention (``num_key_value_heads``
  of ``hidden_size / num_attention_heads``), no rotary embedding, no q/k
  norm, no bias.

It imports nothing of the program and takes nothing the program made.
The weights are the deployment's own recipe, restated here from the
configuration file: the k-th random leaf takes the k-th key of
``split(PRNGKey(seed), n)``, n the count of random leaves, in the order
embed; per Mamba layer W_in, the conv's taps, its bias, W_x, W_dt,
b_dt, W_out, per attention layer W_q W_k W_v W_o, then each layer's
W_gate W_up W_down (no head: it is the embedding).  A matrix is
``normal / sqrt(fan_in)``, an attention layer's W_o ``16 normal /
sqrt(fan_in)``, the embedding ``normal / sqrt(hidden_size)``, the conv's
taps and bias ``normal / 2``, b_dt the inverse softplus of a step size
log-uniform in [0.001, 0.1], each rounded to bfloat16; ``A_log = log(1
.. N)`` in every channel (float32), ``D`` and the norm vectors ones but
the final one, 2 (see the configuration's ``assumed``).
``weights="bf16"`` is the model the configuration serves;
``weights="int4"`` rounds every matmul weight (the head included) to
grouped 4-bit first: the control, the siblings' format.  The 8-bit step
below bfloat16 is no control: ``weights="int8"`` rounds to int8 with a
scale per output channel, and no limit on the harness's comparisons
refuses it (nor float8 weights) on every seed (the configuration's
``limits_from``).

Everything runs layer by layer: one half-layer's weights are made inside
its jitted function from their keys, used on all rows, and dropped.
Matmuls run at ``highest`` precision.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 512
_MAMBA, _ATTN = "mamba", "attention"
_KEYS = {_MAMBA: 10, _ATTN: 7}      # random leaves of one layer, by kind
_ATTN_OUT_GAIN = 16.0               # an attention layer's W_o: 16 normal / sqrt(fan_in)
_FINAL_NORM = 2.0                   # the norm vector under the head
_INT4_GROUP = 128                   # the control's weights: a scale a group along the contraction

_mm = partial(jnp.matmul, precision=_HIGHEST)


def _round(w, weights: str):
    """bfloat16, then, for a lower precision, int4 (``int4``: a scale a
    group of 128 along the contraction axis, the second to last, or the
    whole axis where 128 does not divide it; the maximum at 7) or int8
    (``int8``: a scale per output channel; the maximum at 127)."""
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    if weights == "bf16":
        return w
    if weights == "int4":
        K = w.shape[-2]
        G = _INT4_GROUP if K % _INT4_GROUP == 0 else K
        g = w.reshape(w.shape[:-2] + (K // G, G, w.shape[-1]))
        scale = jnp.maximum(jnp.max(jnp.abs(g), axis=-2, keepdims=True), 1e-12) / 7.0
        return (jnp.clip(jnp.round(g / scale), -7, 7) * scale).reshape(w.shape)
    if weights == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    raise ValueError(f"unknown weight rounding {weights!r}")


def _matrix(key, shape, weights: str, gain: float = 1.0):
    return _round(
        jax.random.normal(key, shape, jnp.float32) * gain / math.sqrt(shape[-2]), weights)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def recurrence(u, delta, B, C, A, D, S0=None):
    """The selective scan, one position after the other.  u, delta [T,
    Di]; B, C [T, N]; A [Di, N]; D [Di].  Returns y [T, Di] and the state
    [Di, N] after the last position."""
    def step(S, x):
        u, d, b, c = x
        S = jnp.exp(d[:, None] * A) * S + (d * u)[:, None] * b[None, :]
        return S, jnp.sum(S * c[None, :], axis=1) + D * u

    S0 = jnp.zeros(A.shape, jnp.float32) if S0 is None else S0
    S, y = jax.lax.scan(step, S0, (u, delta, B, C))
    return y, S


def _mamba_mixer(x, keys, dims, eps, weights):
    """x [R, T, D] (normed) -> [R, T, D]."""
    D, Di, N, R_dt, K = dims
    R, T, _ = x.shape
    u, z = jnp.split(_mm(x, _matrix(keys[0], (D, 2 * Di), weights)), 2, axis=-1)
    taps = _round(jax.random.normal(keys[1], (K, Di), jnp.float32) / 2.0, "bf16")
    bias = _round(jax.random.normal(keys[2], (Di,), jnp.float32) / 2.0, "bf16")
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(taps[i] * padded[:, i:i + T] for i in range(K)) + bias)
    dbc = _mm(u, _matrix(keys[3], (Di, R_dt + 2 * N), weights))
    d, B, C = (_rms(a, eps) for a in jnp.split(dbc, [R_dt, R_dt + N], axis=-1))
    dt = jnp.exp(jax.random.uniform(keys[5], (Di,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    dt_bias = _round(dt + jnp.log(-jnp.expm1(-dt)), "bf16")
    delta = jax.nn.softplus(_mm(d, _matrix(keys[4], (R_dt, Di), weights)) + dt_bias)
    A = -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (Di, N)))
    ones = jnp.ones((Di,), jnp.float32)
    y = jax.vmap(lambda *row: recurrence(*row, A, ones)[0])(u, delta, B, C)
    return _mm(y * jax.nn.silu(z), _matrix(keys[6], (Di, D), weights))


def _attention(q, k, v, length):
    """One row.  q [T, H, Dh]; k, v [T, Hkv, Dh]; causal, keys below
    ``length``.  Query blocks keep the score matrix small."""
    T, H, Dh = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    qb = q.reshape(T // _QUERY_BLOCK, _QUERY_BLOCK, H, Dh)
    kpos = jnp.arange(T)

    def block(args):
        i, qb = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=_HIGHEST) / math.sqrt(Dh)
        qpos = i * _QUERY_BLOCK + jnp.arange(_QUERY_BLOCK)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < length)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=_HIGHEST)

    return jax.lax.map(block, (jnp.arange(T // _QUERY_BLOCK), qb)).reshape(T, H * Dh)


def _attention_mixer(x, lengths, keys, dims, weights):
    D, H, Hkv, Dh = dims
    R, T, _ = x.shape
    q = _mm(x, _matrix(keys[0], (D, H * Dh), weights)).reshape(R, T, H, Dh)
    k = _mm(x, _matrix(keys[1], (D, Hkv * Dh), weights)).reshape(R, T, Hkv, Dh)
    v = _mm(x, _matrix(keys[2], (D, Hkv * Dh), weights)).reshape(R, T, Hkv, Dh)
    attn = jax.lax.map(lambda a: _attention(*a), (q, k, v, lengths))
    return _mm(attn, _matrix(keys[3], (H * Dh, D), weights, _ATTN_OUT_GAIN))


@partial(jax.jit, static_argnames=("kind", "dims", "eps", "weights"))
def _mixer(x, lengths, keys, kind, dims, eps, weights):
    """x [R, T, D] -> [R, T, D]: one layer's first half, weights made here."""
    if kind == _MAMBA:
        return x + _mamba_mixer(_rms(x, eps), keys, dims, eps, weights)
    return x + _attention_mixer(_rms(x, eps), lengths, keys, dims, weights)


@partial(jax.jit, static_argnames=("dims", "eps", "weights"))
def _mlp_half(x, keys, dims, eps, weights):
    D, F = dims
    h = _rms(x, eps)
    y = jax.nn.silu(_mm(h, _matrix(keys[0], (D, F), weights))) \
        * _mm(h, _matrix(keys[1], (D, F), weights))
    return x + _mm(y, _matrix(keys[2], (F, D), weights))


def _embedding(key, shape):
    V, D = shape
    return _round(jax.random.normal(key, shape, jnp.float32) / math.sqrt(D), "bf16")


@partial(jax.jit, static_argnames=("shape",))
def _embed(tokens, key, shape):
    return _embedding(key, shape)[tokens]


@partial(jax.jit, static_argnames=("shape", "cols", "eps", "weights"))
def _head(x, key, shape, cols, eps, weights):
    head = _round(_embedding(key, shape)[:cols].T, weights)      # [D, cols]
    return _mm(_FINAL_NORM * _rms(x, eps), head)


def layer_kinds(cfg: dict) -> list:
    """Each layer's kind: attention at ``i % period == offset``."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return [_ATTN if i % period == offset else _MAMBA
            for i in range(cfg["num_hidden_layers"])]


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
    kinds = layer_kinds(cfg)
    D, V, eps = cfg["hidden_size"], cfg["vocab_size"], float(cfg["rms_norm_eps"])
    H = cfg["num_attention_heads"]
    dims = {
        _ATTN: (D, H, cfg["num_key_value_heads"], D // H),
        _MAMBA: (D, cfg["mamba_expand"] * D, cfg["mamba_d_state"], cfg["mamba_dt_rank"],
                 cfg["mamba_d_conv"]),
    }
    mlp = (D, cfg["intermediate_size"])
    # The recipe splits the keys under the process's own RNG setting and
    # draws every leaf under the partitionable one.
    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + sum(_KEYS[k] for k in kinds))
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        with jax.default_matmul_precision("highest"):
            x = _embed(jnp.asarray(tokens, jnp.int32), keys[0], (V, D))
            lens = jnp.asarray(lengths, jnp.int32)
            at = 1
            for kind in kinds:
                layer = keys[at: at + _KEYS[kind]]
                x = _mixer(x, lens, layer, kind, dims[kind], eps, weights)
                x = _mlp_half(x, layer[_KEYS[kind] - 3:], mlp, eps, weights)
                at += _KEYS[kind]
            out = _head(x, keys[0], (V, D), cols, eps, weights)
        return np.asarray(out, np.float32)
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
