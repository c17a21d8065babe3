"""TPU attention kernels.

The reference delegates attention to vLLM's CUDA backends
(FlashAttention-2 / xFORMERS, picked by compute capability at
``vllm_agent.py:34-55``).  Here the same role is filled by:

* :func:`flash_attention` — a Pallas TPU kernel: blockwise online-softmax
  attention (never materializes the [T, S] score matrix), GQA-aware,
  arbitrary boolean mask.  This is the prefill hot path; the stock XLA
  einsum attention allocates B*H*T*S f32 scores, which at 10 agents x
  2K context OOMs a single v5e chip.
* :func:`blockwise_attention` — the same online-softmax algorithm as a
  pure-JAX ``lax.scan`` over key blocks: memory-bounded on any backend.

Which one a program runs is the CALLER's decision, made once (the
engine resolves it at boot from the backend, the head dim and the mesh):
:func:`flash_attention` always runs the kernel and raises on a geometry
the kernel cannot take — it never stands in an XLA path for itself.

Both compute softmax(scale * q @ k^T + mask) @ v in f32 and return the
query dtype.  Layouts match the model code: q [B, T, H, Dh],
k/v [B, S, Hkv, Dh], mask [B, T, S] (True = attend).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P


_NEG_INF = -1e30


def shard_heads(kernel, mesh: Mesh, batch: int, head_ranks):
    """``kernel(*head_operands, mask)`` over per-device head shards.
    Head operands are ``[B, heads, ...]`` of the given ranks and shard
    their head axis over ``tp``; ``(rank, lead)`` is one with ``lead``
    replicated axes in front (a stacked cache's ``[Lyr, B, heads,
    ...]``), ``None`` one replicated whole (a prefetched scalar),
    ``"rows"`` a prefetched ``[n, B]`` table with a column a batch row
    and no head axis (split with the batch alone).  The trailing mask
    is ``[B, rows, S]`` with no head axis; the output is
    ``[B, heads, rows, Dh]``.  Batch shards over ``dp`` when it divides;
    other mesh axes replicate.  Callers check head divisibility (the
    engine's boot rule)."""
    dp = mesh.shape.get("dp", 1)
    dp_ax = "dp" if dp > 1 and batch % dp == 0 else None

    def heads(rank, lead=0):
        return P(*([None] * lead), dp_ax, "tp", *([None] * (rank - lead - 2)))

    def operand(r):
        if r is None:
            return P()
        if r == "rows":
            return P(None, dp_ax)
        return heads(*r) if isinstance(r, tuple) else heads(r)

    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(operand(r) for r in head_ranks)
        + (P(dp_ax, None, None),),
        out_specs=heads(4),
        # pallas_call results carry no varying-axes type.
        check_vma=False,
    )


# ------------------------------------------------------------------ pallas

def _flash_kernel(
    q_ref, k_ref, v_ref, mask_ref, blk_any_ref, o_ref,
    m_scr, l_scr, acc_scr, *, scale, num_s_blocks,
):
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block skipping: a fully-masked (q-block, kv-block) pair contributes
    # nothing to the online softmax (p == 0, m/l/acc unchanged), so skip
    # its two MXU dots entirely.  In a left-padded suffix prefill over a
    # cached prefix, the causal upper triangle plus the pad region is
    # ~25-40% of all blocks — prefill attention is compute-bound at game
    # shapes, so skipped blocks are wall-clock (the DMA still pipelines,
    # but it overlaps the remaining compute).  The liveness table lives
    # whole in SMEM ((1,1,1) VMEM blocks are not lowerable on TPU);
    # int32 because SMEM scalar reads of bool are not supported either.
    b, t = pl.program_id(0), pl.program_id(2)

    @pl.when(blk_any_ref[b, t, s] != 0)
    def _compute():
        q = q_ref[0, 0]                      # [Tblk, Dh]
        k = k_ref[0, 0]                      # [Sblk, Dh]
        v = v_ref[0, 0]                      # [Sblk, Dh]
        mask = mask_ref[0]                   # [Tblk, Sblk] bool

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                            # [Tblk, Sblk]
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_scr[...]                  # [Tblk, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # Multiply by the mask: with the finite -1e30 sentinel, a fully-
        # masked row has m_new == -1e30 and exp(scores - m_new) == 1, so
        # the mask — not the exponential — must zero forbidden entries.
        p = jnp.exp(scores - m_new) * mask.astype(jnp.float32)

        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s == num_s_blocks - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _pallas_flash(q, k, v, mask, scale, block_q: int, block_kv: int,
                  interpret: bool = False):
    """q [B,H,T,Dh], k/v [B,Hkv,S,Dh], mask [B,T,S] — pre-padded so that
    T % block_q == 0, S % block_kv == 0, Dh % 128 == 0."""
    B, H, T, Dh = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = H // Hkv
    nT, nS = T // block_q, S // block_kv

    # Per-(q-block, kv-block) liveness for the kernel's skip guard.
    blk_any = (
        mask.reshape(B, nT, block_q, nS, block_kv)
        .any(axis=(2, 4))
        .astype(jnp.int32)
    )

    kernel = functools.partial(_flash_kernel, scale=scale, num_s_blocks=nS)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nT, nS),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, t, s: (b, h, t, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, Dh), lambda b, h, t, s, g=group: (b, h // g, s, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, Dh), lambda b, h, t, s, g=group: (b, h // g, s, 0)
            ),
            pl.BlockSpec((1, block_q, block_kv), lambda b, h, t, s: (b, t, s)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, t, s: (b, h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, Dh), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, mask, blk_any)


def _pad_to(x, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def flash_attention(q, k, v, mask, scale, block_q: int = 128,
                    block_kv: int = 256, mesh: Optional[Mesh] = None,
                    interpret: bool = False):
    """Pallas flash attention.  ``mesh``: run per ``tp`` head shard
    (:func:`shard_heads`).  Raises on a head dim the kernel cannot tile —
    the caller picks :func:`blockwise_attention` for those, by name."""
    B, T, H, Dh = q.shape
    if Dh % 128 != 0:
        raise ValueError(
            f"flash_attention needs head_dim % 128 == 0, got {Dh}; use "
            "blockwise_attention (impl='blockwise') for this geometry"
        )
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, block_q)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, block_kv)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, block_kv)
    mp = _pad_to(_pad_to(mask, 1, block_q), 2, block_kv)
    kernel = functools.partial(
        _pallas_flash, scale=scale, block_q=block_q, block_kv=block_kv,
        interpret=interpret,
    )
    if mesh is not None:
        kernel = shard_heads(kernel, mesh, B, (4, 4, 4))
    out = kernel(qt, kt, vt, mp)
    return out[:, :, :T].transpose(0, 2, 1, 3)


# ------------------------------------------------------------- pure-JAX scan

def blockwise_attention(q, k, v, mask, scale, block_kv: int = 512):
    """Online-softmax attention as a ``lax.scan`` over key blocks.

    Identical math to the Pallas kernel; peak memory is O(B*H*T*block_kv)
    instead of O(B*H*T*S).  Runs on any backend.
    """
    B, T, H, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv

    kp = _pad_to(k, 1, block_kv)
    vp = _pad_to(v, 1, block_kv)
    mp = _pad_to(mask, 2, block_kv)
    nS = kp.shape[1] // block_kv

    qg = q.reshape(B, T, Hkv, group, Dh)
    kb = kp.reshape(B, nS, block_kv, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nS, block_kv, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    mb = mp.reshape(B, T, nS, block_kv).transpose(2, 0, 1, 3)

    m0 = jnp.full((B, T, Hkv, group, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, group, 1), jnp.float32)
    acc0 = jnp.zeros((B, T, Hkv, group, Dh), jnp.float32)

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kc, vc, mc = blk                               # [B,s,Hkv,Dh], [B,T,s]
        scores = jnp.einsum(
            "bthgd,bshd->bthgs", qg, kc, preferred_element_type=jnp.float32
        ) * scale
        mcb = mc[:, :, None, None, :]                  # [B,T,1,1,s]
        scores = jnp.where(mcb, scores, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new) * mcb
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "bthgs,bshd->bthgd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, mb))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(B, T, H, Dh).astype(q.dtype)
