"""The selective scan of a Mamba-1 layer (Gu and Dao, "Mamba: Linear-Time
Sequence Modeling with Selective State Spaces"): the recurrent half of a
hybrid model's state-space layers.

Per channel ``c`` of the layer's inner stream, with a state ``S[:, c]``
in R^N (``N`` the state size), a step size ``delta_t[c] > 0``, a fixed
``A[n, c] < 0`` and the input-dependent ``B_t``, ``C_t`` in R^N shared by
every channel::

    S_t[n, c] = exp(delta_t[c] A[n, c]) S_{t-1}[n, c] + delta_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] S_t[n, c] + D[c] u_t[c]

The decay differs per channel AND per state index, so no matmul form
holds (the delta rule's one decay a head does, ``ops/gated_delta.py``):
the work is elementwise, on the vector unit.  What matters is that the
``[T, N, C]`` expansion of the discretised ``A`` and ``B`` never reaches
memory (1.7 GB a layer for a 10 x 512 chunk at C 5120, N 16).

Two entry points, one per phase of serving:

* :func:`mamba_prefill` — a chunk of T positions.  On a TPU a Pallas
  program per (row, block of channels) walks the positions with the
  state in VMEM as ``[N, channels]`` (channels on the 128 lanes, the 16
  state indices on two sublane tiles); elsewhere the same recurrence runs
  as a ``lax.scan`` over positions.  Which of the two runs is the
  CALLER's choice, made once (the engine resolves it at boot): nothing
  here looks at the backend, and an unknown choice raises.
* :func:`mamba_step` — the recurrence for one decoded token, plain XLA.

A position with ``delta = 0`` leaves the state as it was: that is how
pad positions of a left-padded batch, and the positions the wrapper
appends to fill the kernel's last group, are exact no-ops.  All
arithmetic is float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"   # the kernel in interpret mode (tests)
XLA = "xla"

# Positions a kernel step loads B and C for (one lane tile of the
# [N, T] operands), and positions written back at once (a sublane tile).
_GROUP = 128
_ROWS = 8


def _channel_block(C: int) -> int:
    """Channels a grid step takes: the state ``[N, block]`` and the
    per-position temporaries stay in vector registers at 512 (N 16: eight
    registers each)."""
    for block in (512, 256, 128):
        if C % block == 0:
            return block
    return C


def _scan_kernel(u_ref, dt_ref, bt_ref, ct_ref, a_ref, d_ref, s0_ref, y_ref, s_ref):
    """One grid step (row, channel block): every position of the chunk in
    order, the state carried in registers across a group of
    :data:`_ROWS` positions and in the loop carry across groups."""
    N, block = a_ref.shape
    T = u_ref.shape[1]
    A = a_ref[...]                                   # [N, block]
    Dv = d_ref[...]                                  # [1, block]
    lane = jax.lax.broadcasted_iota(jnp.int32, (N, _GROUP), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, block), 0)

    def group(g, S):
        base = pl.multiple_of(g * _GROUP, _GROUP)
        bt = bt_ref[0, :, pl.ds(base, _GROUP)]       # [N, GROUP]
        ct = ct_ref[0, :, pl.ds(base, _GROUP)]

        def rows(r, S):
            t0 = pl.multiple_of(base + r * _ROWS, _ROWS)
            u8 = u_ref[0, pl.ds(t0, _ROWS), :]       # [ROWS, block]
            d8 = dt_ref[0, pl.ds(t0, _ROWS), :]
            y8 = jnp.zeros((_ROWS, block), jnp.float32)
            for j in range(_ROWS):
                at = r * _ROWS + j
                # column ``at`` of B^T and C^T as [N, 1], by a masked sum
                b = jnp.sum(jnp.where(lane == at, bt, 0.0), axis=1, keepdims=True)
                c = jnp.sum(jnp.where(lane == at, ct, 0.0), axis=1, keepdims=True)
                u = jnp.sum(jnp.where(sub == j, u8, 0.0), axis=0, keepdims=True)
                d = jnp.sum(jnp.where(sub == j, d8, 0.0), axis=0, keepdims=True)
                S = jnp.exp(d * A) * S + b * (d * u)
                y = jnp.sum(c * S, axis=0, keepdims=True) + Dv * u
                y8 = jnp.where(sub == j, y, y8)
            y_ref[0, pl.ds(t0, _ROWS), :] = y8
            return S

        return jax.lax.fori_loop(0, _GROUP // _ROWS, rows, S)

    s_ref[0] = jax.lax.fori_loop(0, T // _GROUP, group, s0_ref[0])


def _pallas_scan(u, delta, Bt, Ct, At, D, S0, interpret: bool):
    """u, delta [B, T, C]; Bt, Ct [B, N, T]; At [N, C]; D [1, C]; S0 [B,
    N, C]; T a multiple of :data:`_GROUP`.  All float32."""
    Bn, T, C = u.shape
    N = At.shape[0]
    block = _channel_block(C)
    seq = pl.BlockSpec((1, T, block), lambda b, c: (b, 0, c))
    bc = pl.BlockSpec((1, N, T), lambda b, c: (b, 0, 0))
    state = pl.BlockSpec((1, N, block), lambda b, c: (b, 0, c))
    return pl.pallas_call(
        _scan_kernel,
        grid=(Bn, C // block),
        in_specs=[seq, seq, bc, bc,
                  pl.BlockSpec((N, block), lambda b, c: (0, c)),
                  pl.BlockSpec((1, block), lambda b, c: (0, c)),
                  state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((Bn, T, C), jnp.float32),
                   jax.ShapeDtypeStruct((Bn, N, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        # findable in the device trace beside the attention kernels
        name="mamba_prefill",
    )(u, delta, Bt, Ct, At, D, S0)


def _xla_scan(u, delta, Bm, Cm, A, D, S0):
    """The same recurrence with no kernel: a scan over positions."""
    def step(S, xs):
        u, d, b, c = xs                               # [B, C], [B, C], [B, N], [B, N]
        y, S = mamba_step(u, d, b, c, A, D, S)
        return S, y

    S, y = jax.lax.scan(step, S0, tuple(x.swapaxes(0, 1) for x in (u, delta, Bm, Cm)))
    return y.swapaxes(0, 1), S


def mamba_prefill(u, delta, Bm, Cm, A, D, S0, impl: str = XLA):
    """The selective scan over T positions from the state ``S0``.

    u, delta [B, T, C] (delta already discretised: softplus, 0 on pads);
    Bm, Cm [B, T, N]; A [C, N] (negative); D [C]; S0 [B, N, C] float32.
    Returns y [B, T, C] float32 and the state after position T - 1.
    ``impl`` is the caller's resolved choice: :data:`PALLAS`,
    :data:`PALLAS_INTERPRET` or :data:`XLA`.  The kernel is named
    ``mamba_prefill`` in the device trace."""
    f32 = jnp.float32
    u, delta, Bm, Cm, A, D = (x.astype(f32) for x in (u, delta, Bm, Cm, A, D))
    if impl == XLA:
        return _xla_scan(u, delta, Bm, Cm, A, D, S0)
    if impl not in (PALLAS, PALLAS_INTERPRET):
        raise ValueError(f"mamba_prefill: unknown impl {impl!r}")
    T = u.shape[1]
    pad = (-T) % _GROUP
    if pad:   # delta = 0: the appended positions leave S as it is
        u, delta, Bm, Cm = (jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
                            for x in (u, delta, Bm, Cm))
    y, S = _pallas_scan(u, delta, Bm.swapaxes(1, 2), Cm.swapaxes(1, 2), A.T, D[None],
                        S0, impl == PALLAS_INTERPRET)
    return y[:, :T], S


def mamba_step(u, delta, Bm, Cm, A, D, S):
    """The recurrence for one token.  u, delta [B, C]; Bm, Cm [B, N]; A
    [C, N]; D [C]; S [B, N, C] float32.  Returns y [B, C] float32 and the
    new state."""
    f32 = jnp.float32
    u, delta, Bm, Cm = (x.astype(f32) for x in (u, delta, Bm, Cm))
    S = (jnp.exp(delta[:, None, :] * A.astype(f32).T[None])) * S \
        + Bm[:, :, None] * (delta * u)[:, None, :]
    y = jnp.sum(Cm[:, :, None] * S, axis=1) + D.astype(f32) * u
    return y, S
