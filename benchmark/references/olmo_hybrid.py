"""Plain float32 reference of the hybrid decoder the configuration
``olmo-hybrid-7b-int8`` describes: layers of two kinds in a repeating
period (``layer_types``), each ``x + rmsnorm(mixer(x))`` then ``x +
rmsnorm(swiglu(x))`` (the Olmo 2/3 placement: no norm on a sublayer's
input), an RMS norm and an untied head on top.

* ``full_attention``: multi-head causal attention with no rotary
  embedding (``rope_theta`` is null) and an RMS norm over the whole q and
  the whole k projection before the split into heads.
* ``linear_attention``: the gated delta rule (Yang, Kautz and
  Hatamizadeh, "Gated Delta Networks"), computed here as the
  TOKEN-BY-TOKEN recurrence, a ``lax.scan`` over positions, whatever
  algorithm the program uses.  Per head, with x_t the layer's input::

      q, k, v = silu(conv4(x W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))
          conv4(u)_t = sum_{i<4} c_i u_{t-3+i} per channel, u_{<0} = 0
      q = q / |q| * dk^-1/2 ;  k = k / |k|            (eps 1e-6 under the root)
      beta_t = 2 sigmoid(x_t W_b) ;  g_t = -exp(A_log) softplus(x_t W_a + dt_bias)
      S_t = exp(g_t) S_{t-1} + beta_t (v_t - exp(g_t) S_{t-1} k_t) k_t^T,  S_0 = 0
      o_t = S_t q_t
      y_t = (rmsnorm(o_t) * w_norm * silu(x_t W_gate)) W_o

It imports nothing of the program and takes nothing the program made.
The weights are the deployment's own recipe, restated here from the
configuration file: the k-th random leaf of the plan takes the k-th key
of ``split(PRNGKey(seed), n)``, n the count of random leaves, in the
order embed; per layer, for a linear layer W_q W_k W_v W_a W_b conv
A_log dt_bias W_gate W_o and for a full layer W_q W_k W_v W_o, then
w_gate w_up w_down of the MLP; then the head.  A matrix is ``normal /
sqrt(fan_in)``, but the two per-head gate projections W_a and W_b
``normal / (8 sqrt(fan_in))`` (the un-normalised residual stream of a
post-norm stack reaches an RMS of 8 at 32 layers: the gates'
pre-activations stay of order 1, so the decays span long and short
memory instead of saturating at 0); the conv's taps ``normal / 2``,
``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of ``U(0.001,
0.1)``, each rounded to bfloat16; norm vectors are ones, but for the one
on a full-attention mixer's output, 4, and the final one, 2 (with all at
ones the recurrent layers' answer to the last few tokens outweighs
attention's answer to the prompt's mean, the next-byte distribution is
drawn afresh at every step and every seed's model closes its guided
strings alike; see the configuration's ``assumed``).  ``weights="bf16"`` is the
model the configuration serves in W8A8; ``weights="int4"`` rounds every
matmul weight the deployment quantises (the five projections of a
linear layer, the four of a full one, the MLP, the head) to grouped
4-bit first: the precision below the stated one, the control.

Everything runs layer by layer: one block's weights are made inside the
jitted block from their keys, used on all rows, and dropped.  Matmuls
run at ``highest`` precision.
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
_LINEAR, _FULL = "linear_attention", "full_attention"
_KEYS = {_LINEAR: 13, _FULL: 7}     # random leaves of one layer, by kind
_GATE_DAMP = 8.0                    # the two per-head gate projections: normal / (8 sqrt(fan_in))
_MIXER_NORM = {_LINEAR: 1.0, _FULL: 4.0}    # the norm vector on a mixer's output, by layer kind
_FINAL_NORM = 2.0                   # the norm vector under the head

_mm = partial(jnp.matmul, precision=_HIGHEST)


def _round(w, weights: str):
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    if weights == "bf16":
        return w
    if weights == "int4":
        g = math.gcd(_INT4_GROUP, w.shape[0])
        wg = w.reshape(w.shape[0] // g, g, w.shape[1])
        scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True), 1e-12) / 7.0
        return (jnp.clip(jnp.round(wg / scale), -7, 7) * scale).reshape(w.shape)
    raise ValueError(f"unknown weight rounding {weights!r}")


def _matrix(key, shape, weights: str, damp: float = 1.0):
    return _round(
        jax.random.normal(key, shape, jnp.float32) / (damp * math.sqrt(shape[0])), weights)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, S0=None):
    """The gated delta rule, one position after the other.  q, k [T, H,
    dk]; v [T, H, dv]; g, beta [T, H].  Returns o [T, H, dv] and the
    state after the last position."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hvk,hk->hv", S, k, precision=_HIGHEST))
        S = S + u[:, :, None] * k[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q, precision=_HIGHEST)

    S0 = jnp.zeros((H, dv, dk), jnp.float32) if S0 is None else S0
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def _delta_mixer(x, keys, dims, eps, weights):
    """x [R, T, D] -> [R, T, D]: the linear layer's token mixer."""
    D, H, dk, dv, K, neg = dims
    R, T, _ = x.shape
    u = jnp.concatenate(
        [_mm(x, _matrix(keys[i], (D, n), weights))
         for i, n in ((0, H * dk), (1, H * dk), (2, H * dv))], axis=-1)
    a = _mm(x, _matrix(keys[3], (D, H), "bf16", _GATE_DAMP))
    b = _mm(x, _matrix(keys[4], (D, H), "bf16", _GATE_DAMP))
    taps = _round(jax.random.normal(keys[5], (K, u.shape[-1]), jnp.float32) / 2.0, "bf16")
    a_log = _round(jnp.log(jax.random.uniform(keys[6], (H,), jnp.float32, 1.0, 16.0)), "bf16")
    dt = jax.random.uniform(keys[7], (H,), jnp.float32, 0.001, 0.1)
    dt_bias = _round(dt + jnp.log(-jnp.expm1(-dt)), "bf16")

    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(taps[i] * padded[:, i:i + T] for i in range(K)))
    q = _unit(y[..., : H * dk].reshape(R, T, H, dk)) * dk ** -0.5
    k = _unit(y[..., H * dk: 2 * H * dk].reshape(R, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(R, T, H, dv)
    beta = (2.0 if neg else 1.0) * jax.nn.sigmoid(b)
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    o = jax.lax.map(lambda row: recurrence(*row)[0], (q, k, v, g, beta))
    gate = jax.nn.silu(_mm(x, _matrix(keys[8], (D, H * dv), weights)))
    o = _rms(o, eps) * gate.reshape(R, T, H, dv)
    return _mm(o.reshape(R, T, H * dv), _matrix(keys[9], (H * dv, D), weights))


def _attention(q, k, v, length):
    """One row.  q, k, v [T, H, Dh]; causal, keys below ``length``.
    Query blocks keep the score matrix small."""
    T, H, Dh = q.shape
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


def _attention_mixer(x, lengths, keys, dims, eps, weights):
    D, H, Dh = dims
    R, T, _ = x.shape
    q = _rms(_mm(x, _matrix(keys[0], (D, H * Dh), weights)), eps).reshape(R, T, H, Dh)
    k = _rms(_mm(x, _matrix(keys[1], (D, H * Dh), weights)), eps).reshape(R, T, H, Dh)
    v = _mm(x, _matrix(keys[2], (D, H * Dh), weights)).reshape(R, T, H, Dh)
    attn = jax.lax.map(lambda a: _attention(*a), (q, k, v, lengths))
    return _mm(attn, _matrix(keys[3], (H * Dh, D), weights))


@partial(jax.jit, static_argnames=("kind", "dims", "ffn", "eps", "weights"))
def _block(x, lengths, keys, kind, dims, ffn, eps, weights):
    """x [R, T, D] -> [R, T, D]: one layer, weights made here."""
    D = dims[0]
    if kind == _LINEAR:
        y = _delta_mixer(x, keys, dims, eps, weights)
    else:
        y = _attention_mixer(x, lengths, keys, dims, eps, weights)
    x = x + _MIXER_NORM[kind] * _rms(y, eps)
    m = keys[_KEYS[kind] - 3:]
    gate = jax.nn.silu(_mm(x, _matrix(m[0], (D, ffn), weights)))
    up = _mm(x, _matrix(m[1], (D, ffn), weights))
    return x + _rms(_mm(gate * up, _matrix(m[2], (ffn, D), weights)), eps)


@partial(jax.jit, static_argnames=("shape",))
def _embed(tokens, key, shape):
    return _matrix(key, shape, "bf16")[tokens]


@partial(jax.jit, static_argnames=("shape", "cols", "eps", "weights"))
def _head(x, key, shape, cols, eps, weights):
    return _mm(_FINAL_NORM * _rms(x, eps), _matrix(key, shape, weights)[:, :cols])


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
    kinds = cfg["layer_types"]
    D, V, eps = cfg["hidden_size"], cfg["vocab_size"], float(cfg["rms_norm_eps"])
    H = cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != H or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("this reference is of equal query and key/value head counts")
    dims = {
        _FULL: (D, H, D // H),
        _LINEAR: (D, cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
                  bool(cfg["linear_allow_neg_eigval"])),
    }
    # The recipe splits the keys under the process's own RNG setting and
    # draws every leaf under the partitionable one (the same values on
    # one chip and on a mesh).
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + sum(_KEYS[k] for k in kinds))
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        x = _embed(jnp.asarray(tokens, jnp.int32), keys[0], (V, D))
        lens = jnp.asarray(lengths, jnp.int32)
        at = 1
        for kind in kinds:
            x = _block(x, lens, keys[at: at + _KEYS[kind]], kind, dims[kind],
                       cfg["intermediate_size"], eps, weights)
            at += _KEYS[kind]
        out = _head(x, keys[at], (D, V), cols, eps, weights)
        return np.asarray(out, np.float32)
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
