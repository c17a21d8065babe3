"""Plain float32 reference of the decoder the configuration
``solar-open2-250b-int8`` describes: periods of one gated full-attention
layer and three KDA layers (``layer_types``), each ``x + mixer(rmsnorm(x))``
then ``x + moe(rmsnorm(x))`` (pre-norm), an RMS norm and an untied head
on top.

* ``full_attention``: grouped-query causal attention (``num_key_value_heads``
  of ``head_dim``) with no rotary embedding (``use_rope`` false), its
  output gated elementwise, ``o * sigmoid(x W_g)``, before ``W_o``.
* ``kda``: the channel-gated delta rule (Kimi Delta Attention), computed
  here as the TOKEN-BY-TOKEN recurrence, a ``lax.scan`` over positions,
  whatever algorithm the program uses.  Per head, with x_t the layer's
  (normed) input::

      q, k, v = silu(conv4(x W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))
          conv4(u)_t = sum_{i<4} c_i u_{t-3+i} per channel, u_{<0} = 0
      q = q / |q| * dk^-1/2 ;  k = k / |k|            (eps 1e-6 under the root)
      beta_t = 2 sigmoid(x_t W_b)
      g_t = -exp(A_log) softplus(x_t W_fa W_fb + dt_bias)       (in R^dk)
      S_t = S_{t-1} Diag(exp g_t) + beta_t (v_t - S_{t-1} Diag(exp g_t) k_t) k_t^T
      o_t = S_t q_t ,  S_0 = 0
      y_t = (rmsnorm(o_t) * sigmoid(x_t W_ga W_gb)) W_o

* the MLP half: routed experts.  Router scores ``sigmoid(x W_r)`` over
  all ``router_experts`` (the published count), the top
  ``num_experts_per_tok``, renormalised to sum 1 and times
  ``routed_scaling_factor``; ``moe(x) = shared(x) + sum_{e chosen and
  held} w_e expert_e(x)``, every expert a SwiGLU of
  ``moe_intermediate_size``.  Only the ``n_routed_experts`` experts this
  chip holds (0 to n - 1) are computed, one after the other, each over
  the tokens that chose it (gathered: the host reads how many chose each
  expert and sizes the gather, a power of two and at least 512, from the
  largest count).

It imports nothing of the program and takes nothing the program made.
The weights are the deployment's own recipe, restated here from the
configuration file: the k-th random leaf takes the k-th key of
``split(PRNGKey(seed), n)``, n the count of random leaves, in the order
embed; per layer, for a full layer W_q W_k W_v W_g W_o, for a KDA layer
W_q W_k W_v conv W_b W_fa W_fb A_log dt_bias W_ga W_gb W_o, then the
router, the shared expert's W_gate W_up W_down and the held experts'
stacks of W_gate, W_up, W_down (one key a stack ``[experts, in,
out]``); then the head.  A matrix is ``normal / sqrt(fan_in)`` (a
stack's: each expert's), but a full layer's W_o ``16 normal /
sqrt(fan_in)``, the conv's taps ``normal / 2``, ``A_log = log U(1,
16)`` per head, ``dt_bias`` the inverse softplus of ``U(0.001, 0.1)``
per channel, each rounded to bfloat16; norm vectors are ones but the
final one, 2 (with all matrices alike the prompt's mean, attention's
answer, carries a ninth of the logits' variance over a string's
positions and the game's vote prompts overshoot the traffic file's band
on most fresh models; see the configuration's ``assumed``).
``weights="bf16"`` is the model the configuration serves in W8A8;
``weights="int4"`` rounds every matmul weight the deployment quantises
(the full layer's five projections, the KDA layer's q, k, v and output
projections, the shared and held experts, the head) to grouped 4-bit
first: the precision below the stated one, the control.

Everything runs layer by layer: one half-layer's weights are made inside
its jitted function from their keys, used on all rows, and dropped.  Matmuls
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
_KDA, _FULL = "kda", "full_attention"
_KEYS = {_KDA: 19, _FULL: 12}       # random leaves of one layer, by kind
_ATTN_OUT_GAIN = 16.0               # a full layer's W_o: 16 normal / sqrt(fan_in)
_FINAL_NORM = 2.0                   # the norm vector under the head

_mm = partial(jnp.matmul, precision=_HIGHEST)


def _round(w, weights: str):
    """bfloat16, then, for the control, grouped int4 along the
    contraction axis (the second to last)."""
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    if weights == "bf16":
        return w
    if weights == "int4":
        n = w.shape[-2]
        g = math.gcd(_INT4_GROUP, n)
        wg = w.reshape(*w.shape[:-2], n // g, g, w.shape[-1])
        scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=-2, keepdims=True), 1e-12) / 7.0
        return (jnp.clip(jnp.round(wg / scale), -7, 7) * scale).reshape(w.shape)
    raise ValueError(f"unknown weight rounding {weights!r}")


def _matrix(key, shape, weights: str, gain: float = 1.0):
    return _round(
        jax.random.normal(key, shape, jnp.float32) * gain / math.sqrt(shape[-2]), weights)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, S0=None):
    """The channel-gated delta rule, one position after the other.  q, k
    [T, H, dk]; v [T, H, dv]; g [T, H, dk]; beta [T, H].  Returns o [T,
    H, dv] and the state [H, dv, dk] after the last position."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[:, None, :]
        u = beta[:, None] * (v - jnp.einsum("hvk,hk->hv", S, k, precision=_HIGHEST))
        S = S + u[:, :, None] * k[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q, precision=_HIGHEST)

    S0 = jnp.zeros((H, dv, dk), jnp.float32) if S0 is None else S0
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def _kda_mixer(x, keys, dims, eps, weights):
    """x [R, T, D] (normed) -> [R, T, D]."""
    D, H, dk, dv, K, r, neg = dims
    R, T, _ = x.shape
    u = jnp.concatenate(
        [_mm(x, _matrix(keys[i], (D, n), weights))
         for i, n in ((0, H * dk), (1, H * dk), (2, H * dv))], axis=-1)
    taps = _round(jax.random.normal(keys[3], (K, u.shape[-1]), jnp.float32) / 2.0, "bf16")
    b = _mm(x, _matrix(keys[4], (D, H), "bf16"))
    f = _mm(_mm(x, _matrix(keys[5], (D, r), "bf16")), _matrix(keys[6], (r, H * dk), "bf16"))
    a_log = _round(jnp.log(jax.random.uniform(keys[7], (H,), jnp.float32, 1.0, 16.0)), "bf16")
    dt = jax.random.uniform(keys[8], (H * dk,), jnp.float32, 0.001, 0.1)
    dt_bias = _round(dt + jnp.log(-jnp.expm1(-dt)), "bf16")
    gate = jax.nn.sigmoid(_mm(_mm(x, _matrix(keys[9], (D, r), "bf16")),
                              _matrix(keys[10], (r, H * dv), "bf16")))

    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(taps[i] * padded[:, i:i + T] for i in range(K)))
    q = _unit(y[..., : H * dk].reshape(R, T, H, dk)) * dk ** -0.5
    k = _unit(y[..., H * dk: 2 * H * dk].reshape(R, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(R, T, H, dv)
    beta = (2.0 if neg else 1.0) * jax.nn.sigmoid(b)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias).reshape(R, T, H, dk)
    o = jax.vmap(lambda *row: recurrence(*row)[0])(q, k, v, g, beta)
    o = _rms(o, eps) * gate.reshape(R, T, H, dv)
    return _mm(o.reshape(R, T, H * dv), _matrix(keys[11], (H * dv, D), weights))


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
    attn = attn * jax.nn.sigmoid(_mm(x, _matrix(keys[3], (D, H * Dh), weights)))
    return _mm(attn, _matrix(keys[4], (H * Dh, D), weights, _ATTN_OUT_GAIN))


def _route(xf, key, dims):
    """Each token's top k experts and their renormalised weights."""
    D, E, held, k, F, norm, scaling = dims
    scores = jax.nn.sigmoid(_mm(xf, _matrix(key, (D, E), "bf16")))
    top, chosen = jax.lax.top_k(scores, k)
    if norm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top * scaling, chosen


@partial(jax.jit, static_argnames=("dims", "eps"))
def _held_counts(x, key, dims, eps):
    """How many tokens chose each held expert: the host sizes the
    expert's gather from the largest."""
    _, chosen = _route(_rms(x, eps).reshape(-1, x.shape[-1]), key, dims)
    return jnp.sum(chosen[..., None] == jnp.arange(dims[2]), axis=(0, 1))


def _moe(x, keys, dims, weights, cap):
    """x [R, T, D] (normed) -> [R, T, D]: the shared expert and the held
    experts' part.  Each held expert runs over the ``cap`` tokens of
    largest weight on it, which hold every token that chose it (``cap``
    is at least the largest count; the rest weigh 0)."""
    D, E, held, k, F, norm, scaling = dims
    xf = x.reshape(-1, D)
    top, chosen = _route(xf, keys[0], dims)
    shared = _mm(jax.nn.silu(_mm(xf, _matrix(keys[1], (D, F), weights)))
                 * _mm(xf, _matrix(keys[2], (D, F), weights)),
                 _matrix(keys[3], (F, D), weights))
    gate, up = (_matrix(keys[i], (held, D, F), weights) for i in (4, 5))
    down = _matrix(keys[6], (held, F, D), weights)

    def expert(e, acc):
        w = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        w, at = jax.lax.top_k(w, cap)
        xe = xf[at]
        h = jax.nn.silu(_mm(xe, gate[e])) * _mm(xe, up[e])
        return acc.at[at].add(w[:, None] * _mm(h, down[e]))

    out = jax.lax.fori_loop(0, held, expert, shared)
    return out.reshape(x.shape)


@partial(jax.jit, static_argnames=("kind", "dims", "eps", "weights"))
def _mixer(x, lengths, keys, kind, dims, eps, weights):
    """x [R, T, D] -> [R, T, D]: one layer's first half, weights made here."""
    if kind == _KDA:
        return x + _kda_mixer(_rms(x, eps), keys, dims, eps, weights)
    return x + _attention_mixer(_rms(x, eps), lengths, keys, dims, weights)


@partial(jax.jit, static_argnames=("dims", "eps", "weights", "cap"))
def _expert_half(x, keys, dims, eps, weights, cap):
    return x + _moe(_rms(x, eps), keys, dims, weights, cap)


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
    lin = cfg["linear_attn_config"]
    dims = {
        _FULL: (D, cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]),
        _KDA: (D, lin["num_heads"], lin["head_dim"], lin["head_dim"],
               lin["short_conv_kernel_size"], cfg["kda_gate_rank"],
               bool(cfg["kda_allow_neg_eigval"])),
    }
    moe = (D, cfg["router_experts"], cfg["n_routed_experts"], cfg["num_experts_per_tok"],
           cfg["moe_intermediate_size"], bool(cfg["norm_topk_prob"]),
           float(cfg["routed_scaling_factor"]))
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
            layer = keys[at: at + _KEYS[kind]]
            x = _mixer(x, lens, layer, kind, dims[kind], eps, weights)
            mine = layer[_KEYS[kind] - 7:]   # router, shared expert, held stacks
            most = int(np.max(np.asarray(_held_counts(x, mine[0], moe, eps))))
            cap = min(R * T, max(512, 1 << max(most - 1, 1).bit_length()))
            x = _expert_half(x, mine, moe, eps, weights, cap)
            at += _KEYS[kind]
        out = _head(x, keys[at], (D, V), cols, eps, weights)
        return np.asarray(out, np.float32)
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
