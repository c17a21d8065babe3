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

The CHANNEL-gated form (Kimi Delta Attention, Kimi Linear, arXiv
2510.26692) decays each key channel by its own ``a_t = exp(g_t)``, a
vector in R^dk per head and token::

    S_t = S_{t-1} Diag(a_t) + beta_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T
    o_t = S_t q_t

(``S_t^T = (I - beta k k^T) Diag(a_t) S_{t-1}^T + beta k v^T`` in the
paper's orientation).  :func:`kda_prefill` and :func:`kda_step` are its
two entry points; the chunkwise form shares the triangular inverse and
the walk over recurrence chunks (:func:`_pallas_walk`,
:func:`_xla_walk`) with the scalar form, whose mathematics is untouched.
Inside a chunk a channel's decay products ``exp(G_i - G_j)`` (``G`` the
running sum of ``g``) are formed with exponents that are never positive,
by recursive halving of the chunk: a pair whose positions lie in the
two halves of a block meets through the first position of the later
half, one masked matmul a matrix and level, no step per position
(:func:`_kda_pairs`).  A per-channel decay of ``g = -20`` a token (or
-60) thus loses nothing to overflow, where the scalar form's single
running sum would need ``exp(+1280)``.

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


def _walk_kernel(*refs, math, chunks, n_seq):
    """One grid step (row, head, chunk): the chunk's operands as float32
    (a 4-D operand's block is ``[1, 1, CHUNK, w]``, a 5-D one's ``[1, 1,
    1, 1, w]`` read as ``[1, w]``), the state from VMEM scratch."""
    seq, s0_ref = refs[:n_seq], refs[n_seq]
    o_ref, s_out_ref, s_scr = refs[n_seq + 1:]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _load():
        s_scr[...] = s0_ref[0, 0]

    o, S_new = math(
        *(r[0, 0, 0] if len(r.shape) == 5 else r[0, 0].astype(jnp.float32)
          for r in seq),
        s_scr[...],
    )
    o_ref[0, 0] = o.astype(o_ref.dtype)
    s_scr[...] = S_new

    @pl.when(c == chunks - 1)
    def _store():
        s_out_ref[0, 0] = S_new


def _pallas_walk(math, seq, S0, out_dtype, name: str, interpret: bool):
    """One Pallas program per (row, head) that walks the recurrence
    chunks with ``S`` in VMEM.  ``seq``: head-major per-position
    operands ``[B, H, T, w]`` (a chunk's ``CHUNK`` rows each) or per-
    chunk ones ``[B, H, T/C, 1, w]``; the first and third are q and v.
    Returns o ``[B, H, T, dv]`` and the state after the last chunk."""
    B, H, T, _ = seq[0].shape
    dv, dk = S0.shape[-2:]
    n = T // CHUNK

    def spec(x):
        if x.ndim == 5:
            return pl.BlockSpec((1, 1, 1, 1, x.shape[-1]), lambda b, h, c: (b, h, c, 0, 0))
        return pl.BlockSpec((1, 1, CHUNK, x.shape[-1]), lambda b, h, c: (b, h, c, 0))

    state = pl.BlockSpec((1, 1, dv, dk), lambda b, h, c: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_walk_kernel, math=math, chunks=n, n_seq=len(seq)),
        grid=(B, H, n),
        in_specs=[*map(spec, seq), state],
        out_specs=[pl.BlockSpec((1, 1, CHUNK, dv), lambda b, h, c: (b, h, c, 0)), state],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, dv), out_dtype),
            jax.ShapeDtypeStruct((B, H, dv, dk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # findable in the device trace beside the attention kernels
        name=name,
    )(*seq, S0)


def _xla_walk(math, seq, S0, out_dtype):
    """The same chunk mathematics with no kernel: a scan over the
    recurrence chunks, vmapped over rows and heads."""
    B, H, T, _ = seq[0].shape
    n = T // CHUNK

    def head(*xs):
        *seq, S0 = xs
        cut = lambda x: (x if x.ndim == 3 else  # noqa: E731
                         x.reshape(n, CHUNK, x.shape[-1]).astype(jnp.float32))

        def step(S, xs):
            o, S = math(*xs, S)
            return S, o

        S, o = jax.lax.scan(step, S0, tuple(map(cut, seq)))
        return o.reshape(T, -1).astype(out_dtype), S

    return jax.vmap(jax.vmap(head))(*seq, S0)


def _walk(math, seq, S0, out_dtype, impl: str, name: str):
    if impl == XLA:
        return _xla_walk(math, seq, S0, out_dtype)
    if impl in (PALLAS, PALLAS_INTERPRET):
        return _pallas_walk(math, seq, S0, out_dtype, name, impl == PALLAS_INTERPRET)
    raise ValueError(f"{name}: unknown impl {impl!r}")


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
    seq = (
        heads(q), heads(k), heads(v), gsum.reshape(B, H, Tp, 1),
        gsum[:, :, :, None, :],
        beta.astype(jnp.float32).transpose(0, 2, 1)[..., None],
    )
    o, S = _walk(_chunk_math, seq, S0, v.dtype, impl, "gated_delta_prefill")
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


# ------------------------------------------------ the channel-gated form


def _row(x, i: int):
    """Row ``i`` of ``x`` [n, w] as ``[1, w]``, by a masked sum (exact:
    the other rows add zeros), which a kernel lowers at any offset."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == i, x, 0.0), axis=0, keepdims=True)


def _kda_pairs(q, k, G, bcol):
    """The chunk's two matrices of channel-decayed products, ``[C, C]``:
    ``a[i, j] = beta_i sum_c k_i k_j exp(G_i - G_j)`` for ``j < i`` and
    ``qk[i, j] = sum_c q_i k_j exp(G_i - G_j)`` for ``j <= i``; zero
    elsewhere.

    By recursive halving of the chunk, C a power of two: at level s (C/2,
    ..., 2, 1) the pairs with ``i`` in the later half of a 2s-block and
    ``j`` in its earlier half meet through the first position ``m`` of
    that later half, in one matmul a matrix, ``(x_i exp(G_i - G_m)) (k_j
    exp(G_m - G_j))^T`` with ``x = k`` for ``a`` and ``q`` for ``qk``;
    every pair ``j < i`` is met at exactly one level.  ``G`` falls along
    the chunk, so both exponents are ``-|G_p - G_m|``, never positive;
    the products outside the level's pairs are finite and masked out."""
    C = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    a = jnp.zeros((C, C), jnp.float32)
    qk = jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    s = C // 2
    while s:
        # every position's m at this level, gathered by a one-hot matmul
        at_m = col == row // (2 * s) * (2 * s) + s
        e = jnp.exp(-jnp.abs(G - _mm(jnp.where(at_m, 1.0, 0.0), G, ((1,), (0,)))))
        ke = k * e
        m = (row // (2 * s) == col // (2 * s)) & (row // s % 2 == 1) & (col // s % 2 == 0)
        a = jnp.where(m, _mm(ke, ke, ((1,), (1,))), a)
        qk = jnp.where(m, _mm(q * e, ke, ((1,), (1,))), qk)
        s //= 2
    return bcol * a, qk


def _kda_chunk_math(q, k, v, G, bcol, S):
    """One recurrence chunk of one head of the channel-gated rule.  q, k
    [C, dk]; v [C, dv]; G [C, dk] the INCLUSIVE running sum of g inside
    the chunk; bcol [C, 1] beta; S [dv, dk] the state before the chunk.
    With ``Gam_i = exp(G_i)`` (the decay from the chunk's start) the
    pseudo-values ``u = (I + a)^-1 diag(beta) (v - (Gam * k) S^T)`` give
    ``o_i = S (Gam_i * q_i) + sum_{j<=i} qk[i, j] u_j`` and the state
    after the chunk ``S Diag(Gam_C) + sum_j u_j (k_j exp(G_C - G_j))^T``."""
    C = q.shape[0]
    a, qk = _kda_pairs(q, k, G, bcol)
    t = _unit_lower_inverse(a)
    gam = jnp.exp(G)                                      # [C, dk], <= 1
    w = _mm(t, bcol * gam * k, ((1,), (0,)))              # [C, dk]
    u = _mm(t, bcol * v, ((1,), (0,))) - _mm(w, S, ((1,), (1,)))      # [C, dv]
    o = _mm(gam * q, S, ((1,), (1,))) + _mm(qk, u, ((1,), (0,)))
    g_end = _row(G, C - 1)                                # [1, dk]
    S_new = jnp.exp(g_end) * S + _mm(u, jnp.exp(g_end - G) * k, ((0,), (0,)))
    return o, S_new


def kda_prefill(q, k, v, g, beta, S0, impl: str = XLA):
    """The channel-gated delta rule over T positions from the state
    ``S0``.  q, k [B, T, H, dk] and v [B, T, H, dv] (q already scaled);
    g [B, T, H, dk] float32, each channel's log decay (``<= 0``); beta [B,
    T, H] float32; S0 [B, H, dv, dk] float32.  Returns o [B, T, H, dv] in
    v's dtype and the state after position T - 1.  A position with ``g =
    0`` and ``beta = 0`` leaves the state as it was.  The kernel is named
    ``kda_prefill`` in the device trace."""
    B, T, H, dk = q.shape
    pad = (-T) % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    Tp = T + pad
    heads = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    G = jnp.cumsum(
        heads(g.astype(jnp.float32)).reshape(B, H, Tp // CHUNK, CHUNK, dk), axis=-2)
    seq = (heads(q), heads(k), heads(v), G.reshape(B, H, Tp, dk),
           beta.astype(jnp.float32).transpose(0, 2, 1)[..., None])
    # float32 out of the kernel: its device-trace name (``kda_prefill.N
    # custom-call f32[...]``) then takes no attention pattern of a
    # configuration's file, whose outputs are bfloat16
    o, S = _walk(_kda_chunk_math, seq, S0, jnp.float32, impl, "kda_prefill")
    return o[:, :, :T].transpose(0, 2, 1, 3).astype(v.dtype), S


def kda_step(q, k, v, g, beta, S):
    """The channel-gated recurrence for one token.  q, k [B, H, dk]; v
    [B, H, dv]; g [B, H, dk]; beta [B, H]; S [B, H, dv, dk] float32.
    Returns o [B, H, dv] float32 and the new state."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    decayed = S * jnp.exp(g.astype(jnp.float32))[..., None, :]
    u = beta.astype(jnp.float32)[..., None] * (
        v - jnp.einsum("bhvk,bhk->bhv", decayed, k, precision=_PRECISION))
    S = decayed + u[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvk,bhk->bhv", S, q, precision=_PRECISION), S
