"""The gated delta rule (Yang, Kautz and Hatamizadeh, "Gated Delta
Networks"): the recurrent half of a hybrid model's linear-attention
layers.

Per head, with a state ``S`` in R^{dv x dk}, a decay ``g_t <= 0`` and a
write strength ``beta_t`` (in (0, 2) when negative eigenvalues are
allowed)::

    S_t = exp(g_t) S_{t-1} + beta_t (v_t - exp(g_t) S_{t-1} k_t) k_t^T
    o_t = S_t q_t

Two entry points, one per phase of serving:

* :func:`gated_delta_prefill` — the CHUNKWISE form for a prefill chunk:
  the time axis is cut into recurrence chunks of :data:`CHUNK` tokens;
  inside one, the ``CHUNK`` rank-one updates are folded into dense
  matmuls (the WY form: solve a unit lower-triangular system for the
  pseudo-values ``u_i = beta_i (v_i - exp(g_i) S_{i-1} k_i)``), and the
  state is handed from one recurrence chunk to the next.  On a TPU it is
  one Pallas program per (row, head) that walks the chunks with ``S`` in
  VMEM; the same chunk mathematics (:func:`_chunk_math`) runs as a
  ``lax.scan`` under ``vmap`` anywhere else.  Which of the two a program
  runs is the CALLER's choice, made once (the engine resolves it at
  boot): nothing here looks at the backend.
* :func:`gated_delta_step` — the recurrence itself for one decoded
  token, plain XLA (two passes over ``S``; see PERF.md for why it is not
  a kernel of its own).

A position with ``g = 0`` and ``beta = 0`` leaves the state as it was
(``S_t = S_{t-1}``): that is how pad positions of a left-padded batch,
and the positions the wrapper appends to fill the last recurrence chunk,
are made exact no-ops.

All arithmetic is float32 (the MXU dots at ``highest``): the state is
kept in float32, and the triangular solve amplifies rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens per recurrence chunk: the C x C intra-chunk matrices cost
# O(C^2 (dk + dv)) a chunk beside the O(C dk dv) state terms, and the
# triangular inverse is log2(C) levels deep; 64 keeps both small at
# dk 96 / dv 192 while a 512-token prefill chunk is eight of them.
CHUNK = 64

PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"   # the kernel in interpret mode (tests)
XLA = "xla"

_PRECISION = jax.lax.Precision.HIGHEST


def _mm(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _unit_lower_inverse(a):
    """``(I + a)^{-1}`` for a strictly lower-triangular ``a`` [C, C], C a
    power of two, by block doubling: with ``t`` the inverse of the
    block-diagonal part at block size s, the inverse at 2s is ``t - t
    (a * m) t`` where ``m`` keeps the lower-left s-block of every
    2s-block (``[[A, 0], [B, D]]^-1 = [[A', 0], [-D' B A', D']]``).  As
    stable as block forward substitution — the Neumann product
    ``(I - a)(I + a^2)...`` is not: its powers of ``a`` cancel
    catastrophically when beta is near 2 and the decay near 1."""
    C = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    t = jnp.where(row == col, 1.0, 0.0).astype(jnp.float32)
    s = 1
    while s < C:
        m = (row // (2 * s) == col // (2 * s)) & (row // s % 2 == 1) & (col // s % 2 == 0)
        am = jnp.where(m, a, 0.0)
        if s == 1:          # t is the identity: t - t am t = t - am
            t = t - am
        else:
            t = t - _mm(_mm(t, am, ((1,), (0,))), t, ((1,), (0,)))
        s *= 2
    return t


def _chunk_math(q, k, v, gcol, grow, bcol, S):
    """One recurrence chunk of one head.  q, k [C, dk]; v [C, dv]; gcol
    [C, 1] and grow [1, C] the INCLUSIVE running sum of g inside the
    chunk, in both orientations; bcol [C, 1] beta; S [dv, dk] the state
    before the chunk.  Returns o [C, dv] and the state after it.  Every
    decay is exp of a non-positive number: nothing overflows however
    fast a head forgets."""
    C = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # decay from position j to position i >= j; zero above the diagonal
    D = jnp.exp(jnp.where(row >= col, gcol - grow, -jnp.inf))
    kk = _mm(k, k, ((1,), (1,)))                          # [C, C]
    a = jnp.where(row > col, bcol * D * kk, 0.0)
    t = _unit_lower_inverse(a)
    gam = jnp.exp(gcol)                                   # decay from the chunk's start
    # u = t diag(beta) (v - diag(gam) k S^T)
    w = _mm(t, bcol * gam * k, ((1,), (0,)))              # [C, dk]
    u = _mm(t, bcol * v, ((1,), (0,))) - _mm(w, S, ((1,), (1,)))       # [C, dv]
    qk = jnp.where(row >= col, D * _mm(q, k, ((1,), (1,))), 0.0)
    o = gam * _mm(q, S, ((1,), (1,))) + _mm(qk, u, ((1,), (0,)))
    g_end = grow[:, C - 1:]                               # [1, 1]
    S_new = jnp.exp(g_end) * S + _mm(u, jnp.exp(g_end - gcol) * k, ((0,), (0,)))
    return o, S_new


def _kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, b_ref, s0_ref,
            o_ref, s_out_ref, s_scr, *, chunks):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _load():
        s_scr[...] = s0_ref[0, 0]

    o, S_new = _chunk_math(
        q_ref[0, 0].astype(jnp.float32), k_ref[0, 0].astype(jnp.float32),
        v_ref[0, 0].astype(jnp.float32), gcol_ref[0, 0], grow_ref[0, 0, 0],
        b_ref[0, 0], s_scr[...],
    )
    o_ref[0, 0] = o.astype(o_ref.dtype)
    s_scr[...] = S_new

    @pl.when(c == chunks - 1)
    def _store():
        s_out_ref[0, 0] = S_new


def _pallas_prefill(q, k, v, gcol, grow, bcol, S0, interpret: bool):
    """Head-major operands: q, k [B, H, T, dk]; v [B, H, T, dv]; gcol,
    bcol [B, H, T, 1]; grow [B, H, T/C, 1, C]; S0 [B, H, dv, dk]."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    n = T // CHUNK
    seq = lambda d: pl.BlockSpec((1, 1, CHUNK, d), lambda b, h, c: (b, h, c, 0))  # noqa: E731
    state = pl.BlockSpec((1, 1, dv, dk), lambda b, h, c: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, chunks=n),
        grid=(B, H, n),
        in_specs=[
            seq(dk), seq(dk), seq(dv), seq(1),
            pl.BlockSpec((1, 1, 1, 1, CHUNK), lambda b, h, c: (b, h, c, 0, 0)),
            seq(1), state,
        ],
        out_specs=[seq(dv), state],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, dv, dk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # findable in the device trace beside the attention kernels
        name="gated_delta_prefill",
    )(q, k, v, gcol, grow, bcol, S0)


def _xla_prefill(q, k, v, gcol, grow, bcol, S0):
    """The same chunk mathematics with no kernel: a scan over the
    recurrence chunks, vmapped over rows and heads."""
    B, H, T, _ = q.shape
    n = T // CHUNK

    def head(q, k, v, gcol, grow, bcol, S0):
        cut = lambda x: x.reshape(n, CHUNK, x.shape[-1]).astype(jnp.float32)  # noqa: E731

        def step(S, xs):
            o, S = _chunk_math(*xs, S)
            return S, o

        S, o = jax.lax.scan(
            step, S0, (cut(q), cut(k), cut(v), cut(gcol), grow, cut(bcol)))
        return o.reshape(T, -1).astype(v.dtype), S

    return jax.vmap(jax.vmap(head))(q, k, v, gcol, grow, bcol, S0)


def gated_delta_prefill(q, k, v, g, beta, S0, impl: str = XLA):
    """The gated delta rule over T positions from the state ``S0``.

    q, k [B, T, H, dk] and v [B, T, H, dv] (any float dtype; q already
    scaled); g, beta [B, T, H] float32; S0 [B, H, dv, dk] float32.
    Returns o [B, T, H, dv] in v's dtype and the state after position
    T - 1.  ``impl`` is the caller's resolved choice: :data:`PALLAS`,
    :data:`PALLAS_INTERPRET` or :data:`XLA`."""
    B, T, H, _ = q.shape
    pad = (-T) % CHUNK
    if pad:   # g = 0, beta = 0: the appended positions leave S as it is
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    Tp = T + pad
    heads = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    gsum = jnp.cumsum(
        g.astype(jnp.float32).transpose(0, 2, 1).reshape(B, H, Tp // CHUNK, CHUNK),
        axis=-1)
    operands = (
        heads(q), heads(k), heads(v), gsum.reshape(B, H, Tp, 1),
        gsum[:, :, :, None, :],
        beta.astype(jnp.float32).transpose(0, 2, 1)[..., None], S0,
    )
    if impl == XLA:
        o, S = _xla_prefill(*operands)
    elif impl in (PALLAS, PALLAS_INTERPRET):
        o, S = _pallas_prefill(*operands, interpret=impl == PALLAS_INTERPRET)
    else:
        raise ValueError(f"gated_delta_prefill: unknown impl {impl!r}")
    return o[:, :, :T].transpose(0, 2, 1, 3), S


def gated_delta_step(q, k, v, g, beta, S):
    """The recurrence for one token.  q, k [B, H, dk]; v [B, H, dv]; g,
    beta [B, H]; S [B, H, dv, dk] float32.  Returns o [B, H, dv] float32
    and the new state."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    decayed = jnp.exp(g.astype(jnp.float32))[..., None, None] * S
    u = beta.astype(jnp.float32)[..., None] * (
        v - jnp.einsum("bhvk,bhk->bhv", decayed, k, precision=_PRECISION))
    S = decayed + u[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvk,bhk->bhv", S, q, precision=_PRECISION), S
