"""Pallas decode-step attention (T = 1) with optional int8 KV cache.

Decode reads every LIVE block of the KV cache every step — it is bound
by the cache it streams (the reference's vLLM leans on
FlashAttention/xFORMERS CUDA paged kernels for the same reason,
``vllm_agent.py:34-55``).  This kernel:

* streams K/V blocks once from HBM, online-softmax accumulation in VMEM
  (the stock einsum path materializes f32 scores and re-reads V);
* (int8) stops at the live slots: the grid spans the whole ALLOCATION,
  but each row's first and last block that hold an attendable slot ride
  the scalar prefetch (:func:`live_block_range`, read off the mask), a
  grid step outside them re-addresses the block already resident (the
  pipeline issues no copy) and runs no body.  Left pad and the unwritten
  tail of the allocation cost a step of nothing each; slots dead INSIDE
  the range (a fast-forward gap, the pad in the first live block) are
  masked in the body.  A wholly masked block leaves the online softmax
  as it was, so the output is that of the unbounded grid, bit for bit;
* optionally reads **int8** K/V with per-(position, kv-head) scales —
  halving the dominant HBM traffic with no full-precision cache copy
  ever materialized.  The int8 blocks go to the matmul dtype unscaled
  (exact: |x| <= 127) and a slot's scales are applied where they cost a
  ``[rows, Sblk]`` multiply: the K scale to its score
  (``q . s k = s (q . k)``), the V scale to its softmax weight before
  the PV matmul (``sum p s v = sum (p s) v``), never to the
  denominator.  Each scale is read as the ``[1, Sblk]`` lane row its
  block already is.  Dequantising every element instead (int8 ->
  float32, times the scale relaid from lanes to a ``[Sblk, 1]`` column,
  -> bfloat16) cost the body several VPU passes over each K and V
  block, some four times the block's DMA;
* is GQA-native: grid over (batch, kv-head), each program computing all
  ``group`` query heads of that kv head at once (an [group, Dh] MXU tile
  instead of ``group`` separate vector products).

Layouts: q [B, H, Dh]; bf16 k/v [B, S, Hkv, Dh] (cache layout); int8
k/v [B, Hkv, S, Dh] — int8 arrays tile as (32, 128) over the last two
dims, so the kernel's (block_s, Dh) block is Mosaic-native, where the
bf16 axis order would hand it (1, 128)-row int8 blocks (measured ~70x
slower); scales [B, Hkv, S] (S minor-most, lane-aligned, exactly what
the cache stores — no per-step transpose); mask [B, S] bool (attendable
slots).  Returns [B, H, Dh] in q's dtype.

The int8 forms also take a STACKED cache (the scan-over-layers carry:
k/v [Lyr, B, Hkv, S, Dh], scales [Lyr, B, Hkv, S]) with a ``layer``
index: the index is scalar-prefetched and the K/V/scale index maps
read that layer's blocks out of the stack, so the layer's entry is
never sliced out into a buffer of its own (a whole-layer copy a layer
and step).  A per-entry cache runs the same call as a stack of one.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30

# The S-axis block the kernels stream by, and what callers that ALLOCATE
# the cache round its length up to: `_pad_s` on a misaligned cache is a
# jnp.pad — a full copy of every k/v/scale array PER LAYER PER DECODE
# STEP, which is how the int8 cache measured ~4x slower than bf16 in
# round 1-2 (the bf16 einsum path never pads).
# The block is also the granularity at which the int8 kernel skips dead
# slots (module docstring): the first and last live block of a row are
# computed whole.  Measured on a v5e on the all-heads grid (B, nS) with
# the bound, in both benchmark cells (10 rows, S 5120, 2.2-2.5k live
# slots behind 1.5k of left pad; `round_s`, PERF.md section 6, PR 32):
# 8 KV heads 15.61 s at block 1024, 15.07 at 512, 14.73 at 256; 30 KV
# heads 14.66 at 512, 14.15 at 256.  A grid step outside the range
# costs about half a microsecond, so 128 would pay more in empty steps
# than its finer edges save (arithmetic, not measured).  The 1024 that
# stood here was chosen on the PER-HEAD grid (B, Hkv, nS), 640 programs
# of a ~2 us fixed cost each, unbounded (B=10, Hkv=8, S=4096: 1.18
# ms/step at 512 vs 0.70 at 1024): that grid is gone.
BLOCK_S = 256
ALIGN_S = 1024


# The all-heads int8 kernel holds one K and one V block of EVERY kv head
# at once, double-buffered: 4 * Hkv * block_s * Dh bytes.  Mosaic's
# scoped VMEM is 16 MiB on a v5e; half of it for these leaves room for
# scales, q, the mask and the accumulators.
_KV_BLOCKS_BUDGET = 8 << 20


def _pick_block(requested, kv_row_bytes: int = 0) -> int:
    """``kv_row_bytes``: bytes of one cache position over all the kv
    heads a program holds (Hkv * Dh for the int8 layout; 0 where a
    program holds one head).  ``BLOCK_S`` unless that many heads' K and
    V blocks would not fit VMEM (more than 64 heads of 128)."""
    if requested is not None:
        return requested
    block = BLOCK_S
    while block > 128 and 4 * block * kv_row_bytes > _KV_BLOCKS_BUDGET:
        block //= 2
    return block


def kernel_block(num_kv_heads: int, head_dim: int) -> int:
    """The block the int8 kernels stream a cache by when the caller
    names none: what the engine's block counter counts in."""
    return _pick_block(None, num_kv_heads * head_dim)


class LiveMask(NamedTuple):
    """A decode mask with :func:`live_slots` of it beside it.  The int8
    forms take either this or the bare mask (and then reduce it
    themselves); a caller that attends many layers under one mask makes
    it once a step, outside the layer scan, so the reduction over S is
    not repeated a layer."""

    mask: jax.Array      # [B, S] or [B, K, S] bool
    slots: jax.Array     # [2, B] int32


def live_slots(mask, xp=jnp):
    """``[2, B]`` int32: each row's first and last attendable slot, over
    every query row of a chunk mask ([B, K, S]: the union).  A row with
    none reads ``(S, -1)``.  ``xp=numpy`` for a mask the host holds."""
    rows = mask if mask.ndim == 2 else mask.any(axis=1)
    S = rows.shape[-1]
    some = rows.any(axis=-1)
    first = xp.where(some, xp.argmax(rows, axis=-1), S)
    last = xp.where(some, S - 1 - xp.argmax(rows[:, ::-1], axis=-1), -1)
    return xp.stack([first, last]).astype(xp.int32)


def live_block_range(first_slot, last_slot, block_s: int, xp=jnp):
    """``(first, last)`` block of ``block_s`` slots that holds an
    attendable slot, from the first and last attendable slot (arrays
    that broadcast).  No slot (``last_slot < first_slot``) is the empty
    range ``(1, 0)``: no block is live and a clip to it addresses block
    0.  The kernel's bound and the engine's block counter
    (``engine.decode.kv_blocks_live``) share this one definition."""
    empty = last_slot < first_slot
    return (xp.where(empty, 1, first_slot // block_s),
            xp.where(empty, 0, last_slot // block_s))


def live_block_count(first_slot, last_slot, block_s: int) -> int:
    """Blocks inside :func:`live_block_range`, summed over everything
    the two (NumPy) arrays broadcast to: rows by steps, on the host."""
    first, last = live_block_range(first_slot, last_slot, block_s, xp=np)
    return int((last - first + 1).sum())


def _decode_kernel(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref, o_ref,
    m_scr, l_scr, acc_scr, *, scale, num_s_blocks, quantized,
):
    """Per-(batch, kv-head) program over the bf16 cache layout.

    Only the bf16 path still uses this grid (its (1, block_s, 1, Dh)
    block does not lower on real TPUs for Hkv > 1 — it exists for
    interpret-mode reference checks); the int8 serving path runs
    :func:`_decode_kernel_allheads`.
    """
    del quantized  # signature kept stable for the shared in_specs
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                          # [rows, Dh]
    mask = mask_ref[0]                       # [M, Sblk] bool
    del ks_ref, vs_ref                       # dummies on the bf16 path

    k = k_ref[0, :, 0, :]                    # [Sblk, Dh]
    v = v_ref[0, :, 0, :]
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)

    # Single-step decode passes one mask row shared by every query row
    # (broadcast [1, Sblk]); the chunk variant pre-repeats per query row
    # HOST-SIDE ([rows, Sblk]) so the kernel never relies on Mosaic
    # lowering of an in-kernel repeat.
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                # [rows, Sblk]
    scores = jnp.where(mask, scores, _NEG_INF)

    m_prev = m_scr[...]                      # [group, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
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


def _decode_kernel_allheads(
    live_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref, o_ref,
    m_scr, l_scr, acc_scr, *, scale, num_s_blocks, hkv,
):
    """int8 variant processing ALL kv heads per program: grid (B, nS).
    ``live_ref`` ([2, B] in SMEM): the row's first and last live block;
    a step outside them computes nothing (its blocks are the neighbour
    step's, re-addressed by the index maps, and hold no attendable slot).

    The per-head grid (B, Hkv, nS) paid a ~2 us fixed cost per program
    invocation (v5e, measured in-loop round 3) — at decode block counts
    that overhead, not HBM streaming, dominated the kernel.  Folding the
    Hkv loop inside cuts program count 8x; K/V blocks stay (Sblk, Dh)
    Mosaic-native int8 tiles, scratch is per-head-indexed on its leading
    dim (static index — no sublane-offset slicing).
    """
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((s >= live_ref[0, b]) & (s <= live_ref[1, b]))
    def _compute():
        mask = mask_ref[0]                   # [M, Sblk]; M = 1 or rows
        maskf = mask.astype(jnp.float32)
        for h in range(hkv):
            q = q_ref[0, h]                  # [rows, Dh]
            # Unscaled: |x| <= 127 is exact in bfloat16.  The scales ride
            # the scores and the weights (module docstring).
            k = k_ref[0, h].astype(q.dtype)  # [Sblk, Dh]
            v = v_ref[0, h].astype(q.dtype)
            ks = ks_ref[0, pl.ds(h, 1), :]   # [1, Sblk]
            vs = vs_ref[0, pl.ds(h, 1), :]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * (scale * ks)                 # [rows, Sblk]
            scores = jnp.where(mask, scores, _NEG_INF)
            m_prev = m_scr[h]                # [rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new) * maskf
            m_scr[h] = m_new
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
                (p * vs).astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(s == num_s_blocks - 1)
    def _finish():
        for h in range(hkv):
            l = l_scr[h]
            o_ref[0, h] = (
                acc_scr[h] / jnp.where(l == 0.0, 1.0, l)
            ).astype(o_ref.dtype)


def _quantized_attention(qg, live, layer, kp, vp, ksp, vsp, mp, scale,
                         block_s, interpret, mesh=None):
    """Shared pallas_call for the int8 single-step and chunk paths.

    qg [B, Hkv, rows, Dh]; live [2, B] int32; layer [1] int32; kp/vp
    [Lyr, B, Hkv, Sp, Dh] int8; scales [Lyr, B, Hkv, Sp]; mp [B, M, Sp]
    with M == 1 (broadcast) or rows.  Returns [B, Hkv, rows, Dh].
    ``live`` and ``layer`` are scalar-prefetched.  The K/V/scale index
    maps read that layer's blocks, the stack's leading axis squeezed
    away, so the kernel body sees one entry's blocks whatever the
    stack's depth.  ``live`` is each row's first and last block with an
    attendable slot (:func:`live_block_range` of ``mp``): every S-axis
    index map addresses ``clip(s, first, last)``, so a grid step outside
    the range names the block the step beside it holds, which the
    pipeline does not copy again, and the body skips it.  ``mesh``: each
    ``tp`` device runs the kernel on its own Hkv/tp heads
    (ops/attention.shard_heads) — every operand but the mask and the
    range is laid out kv-head-major for exactly this.
    """
    if mesh is not None:
        from bcg_tpu.ops.attention import shard_heads

        return shard_heads(
            functools.partial(
                _quantized_attention, scale=scale, block_s=block_s,
                interpret=interpret,
            ),
            mesh, qg.shape[0],
            (4, "rows", None, (5, 1), (5, 1), (4, 1), (4, 1)),
        )(qg, live, layer, kp, vp, ksp, vsp, mp)
    B, Hkv, rows, Dh = qg.shape
    Sp = kp.shape[3]
    M = mp.shape[1]
    nS = Sp // block_s

    def blk(b, s, lv):
        return jnp.minimum(jnp.maximum(s, lv[0, b]), lv[1, b])

    kv_spec = pl.BlockSpec(
        (None, 1, Hkv, block_s, Dh),
        lambda b, s, lv, li: (li[0], b, 0, blk(b, s, lv), 0))
    scale_spec = pl.BlockSpec(
        (None, 1, Hkv, block_s),
        lambda b, s, lv, li: (li[0], b, 0, blk(b, s, lv)))
    q_spec = pl.BlockSpec((1, Hkv, rows, Dh), lambda b, s, lv, li: (b, 0, 0, 0))

    def kernel(live_ref, layer_ref, *refs):
        del layer_ref   # the index maps read it
        _decode_kernel_allheads(
            live_ref, *refs, scale=scale, num_s_blocks=nS, hkv=Hkv)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nS),
            in_specs=[
                q_spec,
                kv_spec,
                kv_spec,
                scale_spec,
                scale_spec,
                pl.BlockSpec(
                    (1, M, block_s),
                    lambda b, s, lv, li: (b, 0, blk(b, s, lv))),
            ],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((Hkv, rows, 1), jnp.float32),
                pltpu.VMEM((Hkv, rows, 1), jnp.float32),
                pltpu.VMEM((Hkv, rows, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, Dh), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(live, layer, qg, kp, vp, ksp, vsp, mp)


def pow2_rows(group: int) -> int:
    """Query-row count the int8 kernels dispatch for a GQA group: the
    group itself when it is a power of two, else the next power of two
    (the wrappers zero-pad the extra rows and slice them away).  The
    engine's kernel-dispatch guard and both wrapper pad sites share this
    ONE definition so the validated-set rule cannot drift."""
    return group if group & (group - 1) == 0 else 1 << group.bit_length()


def _pad_s(x, block_s, axis=1, value=0):
    pad = (-x.shape[axis]) % block_s
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _int8_operands(k, v, k_scale, v_scale, layer, block_s):
    """``(layer [1] int32, k, v, k_scale, v_scale)`` as
    :func:`_quantized_attention` takes them.  ``layer`` None: one
    entry's leaves ([B, Hkv, S, ...]), padded to the block and run as a
    stack of one.  Else the leaves are a stacked cache's
    ([Lyr, B, Hkv, S, ...]) and go in as they are: a pad there would
    copy the WHOLE cache a layer and step, so a misaligned one is an
    error (allocate at ``ALIGN_S``)."""
    leaves = (k, v, k_scale, v_scale)
    if layer is None:
        leaves = tuple(_pad_s(a, block_s, axis=2)[None] for a in leaves)
        layer = 0
    elif k.shape[-2] % block_s:
        raise ValueError(
            f"stacked int8 cache of {k.shape[-2]} slots is not a multiple "
            f"of the kernel's block {block_s}: allocate it at ALIGN_S "
            f"({ALIGN_S}); padding it here would copy the whole cache"
        )
    return (jnp.asarray(layer, jnp.int32).reshape(1),) + leaves


def _bounded(mask, block_s):
    """``(mask, live)`` of an int8 form's ``mask`` argument: the bare
    mask and the [2, B] block range :func:`_quantized_attention`
    prefetches, in units of THIS call's block."""
    if not isinstance(mask, LiveMask):
        mask = LiveMask(mask, live_slots(mask))
    return mask.mask, jnp.stack(live_block_range(*mask.slots, block_s))


def decode_attention(
    q, k, v, mask, scale,
    k_scale=None, v_scale=None,
    block_s=None,
    interpret: bool = False,
    mesh=None,
    layer=None,
):
    """q [B, H, Dh], mask [B, S] -> [B, H, Dh].  The int8 form also
    takes the mask as a :class:`LiveMask`.

    k/v: [B, S, Hkv, Dh] bf16, or — when ``k_scale`` is given — the int8
    cache layout [B, Hkv, S, Dh] (int8 tiles natively as (32, 128) over
    the last two dims; the bf16 axis order would hand Mosaic (1, 128)-row
    int8 blocks, measured ~70x slower).  Scales [B, Hkv, S].  With
    ``layer`` (int8 only) k/v and the scales are a stacked cache's
    leaves, one leading [Lyr] axis more, and that layer is attended
    (see the module docstring).
    """
    B, H, Dh = q.shape
    quantized = k_scale is not None
    block_s = (
        _pick_block(block_s, k.shape[-3] * k.shape[-1]) if quantized
        else _pick_block(block_s)
    )
    if quantized:
        Hkv = k.shape[-3]
        group = H // Hkv
        # Non-power-of-two GQA groups (14B: H=40/Hkv=8 -> 5) pad their
        # query rows up to the next power of two — the kernel then only
        # ever sees the row counts the hardware probe validates (2/4/8),
        # and the padded rows' outputs are sliced away.  Decode streams
        # the CACHE, so extra q rows cost MXU work only, not HBM.
        g2 = pow2_rows(group)
        qg = q.reshape(B, Hkv, group, Dh)
        if g2 != group:
            qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g2 - group), (0, 0)))
        mask, live = _bounded(mask, block_s)
        out = _quantized_attention(
            qg, live, *_int8_operands(k, v, k_scale, v_scale, layer, block_s),
            _pad_s(mask, block_s, axis=1)[:, None, :],
            scale, block_s, interpret, mesh,
        )
        if g2 != group:
            out = out[:, :, :group]
        return out.reshape(B, H, Dh)
    if mesh is not None or layer is not None:
        raise ValueError(
            "decode_attention: only the int8 cache layout shards over a "
            "mesh (the bf16 kernel's head axis is not block-major) or is "
            "read out of a stack by layer index"
        )
    S, Hkv = k.shape[1], k.shape[2]
    kp = _pad_s(k, block_s)
    vp = _pad_s(v, block_s)
    kv_spec = pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, s: (b, s, h, 0))
    Sp = kp.shape[1]
    # dummy operands so the kernel signature is stable
    ksp = jnp.ones((B, Hkv, Sp), jnp.float32)
    vsp = ksp
    group = H // Hkv
    mp = _pad_s(mask, block_s, axis=1)[:, None, :]  # [B, 1, S]
    nS = Sp // block_s

    qg = q.reshape(B, Hkv, group, Dh)

    kernel = functools.partial(
        _decode_kernel, scale=scale, num_s_blocks=nS, quantized=False,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nS),
        in_specs=[
            pl.BlockSpec((1, 1, group, Dh), lambda b, h, s: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, Hkv, block_s), lambda b, h, s: (b, 0, s)),
            pl.BlockSpec((1, Hkv, block_s), lambda b, h, s: (b, 0, s)),
            pl.BlockSpec((1, 1, block_s), lambda b, h, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, Dh), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, kp, vp, ksp, vsp, mp)
    return out.reshape(B, H, Dh)


def chunk_decode_attention(
    q, k, v, mask, scale,
    k_scale=None, v_scale=None,
    block_s=None,
    interpret: bool = False,
    mesh=None,
    layer=None,
):
    """Fast-forward chunk decode over the (possibly int8) cache.

    q [B, K, H, Dh] (K chunk positions), mask [B, K, S] (int8: or a
    :class:`LiveMask` of it) -> [B, K, H, Dh]; k/v [B, S, Hkv, Dh] bf16
    or the int8 cache layout [B, Hkv, S, Dh],
    stacked with ``layer`` (see :func:`decode_attention`).  Same
    streaming/online-softmax/scale-folding design, with an
    [K*group, Dh] query tile per (batch, kv-head) program — K=4, group=2
    is an 8-row MXU tile, where the prefill flash kernel would pad the
    4 chunk rows to a 128-row query block (32x wasted work).
    """
    B, K, H, Dh = q.shape
    quantized = k_scale is not None
    block_s = (
        _pick_block(block_s, k.shape[-3] * k.shape[-1]) if quantized
        else _pick_block(block_s)
    )
    if quantized:
        Hkv = k.shape[-3]
        group = H // Hkv
        # Pre-repeat the mask per query row (position-major: row
        # k*group+g = mask[k]) and lay q out [B, Hkv, K*group, Dh] to
        # match — no in-kernel repeat (Mosaic lowering of repeats is not
        # relied upon anywhere).  Non-power-of-two groups pad to the
        # next power of two (see decode_attention); padded rows reuse
        # their chunk's mask and are sliced away below.
        g2 = pow2_rows(group)
        mask, live = _bounded(mask, block_s)
        mp = jnp.repeat(_pad_s(mask, block_s, axis=2), g2, axis=1)
        qg = q.reshape(B, K, Hkv, group, Dh)
        if g2 != group:
            qg = jnp.pad(
                qg, ((0, 0), (0, 0), (0, 0), (0, g2 - group), (0, 0))
            )
        qg = qg.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, K * g2, Dh)
        out = _quantized_attention(
            qg, live, *_int8_operands(k, v, k_scale, v_scale, layer, block_s),
            mp, scale, block_s, interpret, mesh,
        )
        out = out.reshape(B, Hkv, K, g2, Dh)
        if g2 != group:
            out = out[:, :, :, :group]
        return (
            out
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, K, H, Dh)
        )
    if mesh is not None or layer is not None:
        raise ValueError(
            "chunk_decode_attention: only the int8 cache layout shards "
            "over a mesh or is read out of a stack by layer index"
        )
    Hkv = k.shape[2]
    kp = _pad_s(k, block_s)
    vp = _pad_s(v, block_s)
    kv_spec = pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, s: (b, s, h, 0))
    Sp = kp.shape[1]
    ksp = jnp.ones((B, Hkv, Sp), jnp.float32)
    vsp = ksp
    group = H // Hkv
    mp = _pad_s(mask, block_s, axis=2)              # [B, K, Sp]
    mp = jnp.repeat(mp, group, axis=1)              # [B, K*group, Sp]
    nS = Sp // block_s

    qg = (
        q.reshape(B, K, Hkv, group, Dh)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, K * group, Dh)
    )

    kernel = functools.partial(
        _decode_kernel, scale=scale, num_s_blocks=nS, quantized=False,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nS),
        in_specs=[
            pl.BlockSpec((1, 1, K * group, Dh), lambda b, h, s: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, Hkv, block_s), lambda b, h, s: (b, 0, s)),
            pl.BlockSpec((1, Hkv, block_s), lambda b, h, s: (b, 0, s)),
            pl.BlockSpec((1, K * group, block_s), lambda b, h, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, K * group, Dh), lambda b, h, s: (b, h, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, K * group, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((K * group, 1), jnp.float32),
            pltpu.VMEM((K * group, 1), jnp.float32),
            pltpu.VMEM((K * group, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, kp, vp, ksp, vsp, mp)
    return (
        out.reshape(B, Hkv, K, group, Dh)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, K, H, Dh)
    )


# ----------------------------------------------------------- kv quantization

def quantize_kv(x, axis=-1):
    """bf16/f32 [..., Dh] -> (int8 values, f32 per-row scale).

    Symmetric absmax over the head dim: scale[..., 1] = absmax / 127.
    """
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.squeeze(axis)


def dequantize_kv(q, scale, axis=-1):
    return q.astype(jnp.float32) * jnp.expand_dims(scale, axis)
