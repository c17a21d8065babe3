"""Block-paged KV-cache primitives: pool init, block-indexed
gather/scatter, gather-to-dense views, and the paged decode-attention
variant of :mod:`bcg_tpu.ops.decode_attention`.

The dense engine provisions one ``[B, S]`` KV slab per batch row, sized
at the worst-case decode window — N agents sharing a system prompt and
round history hold N copies of identical prefix KV.  The paged layout
replaces the per-row slab with ONE preallocated pool of fixed-size
blocks per layer plus a per-row **block table**: logical cache slot
``s`` of row ``b`` lives at physical slot ``tbl[b, s // bs] * bs +
s % bs`` of the pool.  Rows that share a token prefix reference the
same physical blocks (refcounted by the host-side radix index,
:mod:`bcg_tpu.engine.paged_kv`), so shared prefixes are stored and
prefilled once.

Layouts mirror the dense cache exactly, with the batch/sequence pair
``[B, S]`` replaced by ``[N_blocks, bs]``:

* bf16: ``k``/``v`` ``[N, bs, Hkv, Dh]`` (dense: ``[B, S, Hkv, Dh]``)
* int8: ``k``/``v`` ``[N, Hkv, bs, Dh]`` with f32 scales
  ``[N, Hkv, bs]`` (dense: ``[B, Hkv, S, Dh]`` / ``[B, Hkv, S]``)
* int4: ``k``/``v`` ``[N, Hkv, bs, Dh/2]`` packed two-per-byte with
  BF16 scales ``[N, Hkv, bs]`` — the scale dtype is the layout marker
  (``transformer.kv_is_int4``); the fused kernel unpacks nibbles in
  VMEM (capacity knob: half the int8 pool's bytes per block)

A paged cache ENTRY is the pool plus the traced block table:
``{"k", "v"[, "k_scale", "v_scale"], "tbl": [B, nblk] int32}`` — the
table is a regular pytree leaf, so varying its CONTENTS between calls
never re-traces a decode loop (only ``nblk``/pool shapes key compiles).
Block 0 is reserved as the null block: table padding points at it, it
is never written, and every slot it backs is masked out of attention.

Two attention implementations share these layouts:

* **XLA reference** (``impl="xla"``): gather the row's blocks into the
  dense layout (exact — a gather moves bits) and delegate to the stock
  masked attention, so paged output is bit-identical to the dense path
  given identical block contents.  The gathered view is a per-step
  transient — but it IS a per-step dense materialization, so on real
  TPUs the HBM-bandwidth win of paging is unrealized on this path.
* **Fused Pallas kernel** (``impl="paged_pallas"`` /
  ``"paged_pallas_it"`` for interpret mode): the
  ``jax.experimental.pallas.ops.tpu.paged_attention`` shape — grid over
  (rows, page groups), the row's block table rides as a SCALAR-PREFETCH
  operand so each page's BlockSpec index map reads its physical pool
  slot from the table (``tbl[b, i]``), and the Pallas pipeline
  double-buffers the page DMA from the HBM pool into VMEM.  Online-
  softmax accumulation in VMEM scratch; int8 pools dequantize per page
  in VMEM (no full-precision view ever materializes).  One program
  covers all kv heads (the ``_decode_kernel_allheads`` lesson: per-head
  programs paid ~2 us fixed cost each) and
  ``BCG_TPU_PAGED_PAGES_PER_PROGRAM`` pages (amortizing program
  overhead over small blocks; 128-token blocks = lane count need less
  of it).  Steady-state decode reads each block exactly once.

The engine resolves the impl (``EngineConfig.paged_kv_impl`` /
``BCG_TPU_PAGED_KV_IMPL``): ``pallas`` is the default on TPU, the XLA
gather stays the conformance oracle, and off-TPU the kernel runs in
interpret mode (tests) — the gather path remains the CPU default.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30

# Engine-resolved impl markers for the paged attention dispatch
# (models/transformer.py passes them through the decode loops' ``impl``
# parameter; anything else selects the XLA gather reference).
PALLAS = "paged_pallas"
PALLAS_INTERPRET = "paged_pallas_it"


def is_paged(entry: Dict) -> bool:
    """True for a paged cache entry (carries a block table)."""
    return "tbl" in entry


def block_size(entry: Dict) -> int:
    """Tokens per block, read off the pool's physical layout."""
    return entry["k"].shape[2 if "k_scale" in entry else 1]


def init_block_pool(
    spec, num_blocks: int, block_size: int, quantized=False,
    stacked: bool = False,
):
    """Preallocated per-layer block pool (no tables yet): the paged
    counterpart of ``transformer.init_kv_cache``.  Returns a per-layer
    list of entry dicts, or — ``stacked`` — one dict whose leaves carry
    a leading ``[num_layers]`` dim (scan-over-layers form).  Block 0 is
    the null block by convention (reserved by the allocator).

    ``quantized`` is False, True/``"int8"``, or ``"int4"`` — int4 packs
    the head dim two nibbles per byte on the int8 axes
    (``[N, Hkv, bs, Dh/2]``) with BF16 scales, the scale-dtype marker
    ``transformer.kv_is_int4`` keys every downstream dispatch on."""
    if quantized == "int4":
        from bcg_tpu.models.quantize import kv_int4_layout

        dh_store, scale_dtype = kv_int4_layout(spec.head_dim)
    else:
        dh_store, scale_dtype = spec.head_dim, jnp.float32
    shape = (num_blocks, block_size, spec.num_kv_heads, spec.head_dim)
    qshape = (num_blocks, spec.num_kv_heads, block_size, dh_store)
    scale_shape = (num_blocks, spec.num_kv_heads, block_size)

    def entry(lead=()):
        if quantized:
            return {
                "k": jnp.zeros(lead + qshape, jnp.int8),
                "v": jnp.zeros(lead + qshape, jnp.int8),
                "k_scale": jnp.ones(lead + scale_shape, scale_dtype),
                "v_scale": jnp.ones(lead + scale_shape, scale_dtype),
            }
        return {
            "k": jnp.zeros(lead + shape, jnp.bfloat16),
            "v": jnp.zeros(lead + shape, jnp.bfloat16),
        }

    if stacked:
        return entry(lead=(spec.num_layers,))
    return [entry() for _ in range(spec.num_layers)]


def paged_write(entry: Dict, k, v, pos) -> Dict:
    """Write fresh ``[B, T]`` KV through the block table (quantizing for
    int8 pools) — the block-indexed generalization of
    ``transformer._write_cache``: ``pos`` is a scalar logical slot
    shared by the batch (prefill chunks, the standard/fast-forward
    loops) or a ``[B]`` vector of per-row slots (the speculative loop's
    compacted writes); either way row ``b``'s token ``t`` lands at
    physical slot ``(tbl[b, p // bs], p % bs)`` with ``p = pos(+b) + t``.

    Callers guarantee the written logical range is backed by PRIVATE
    (unshared) blocks — decode/suffix regions are freshly allocated per
    row, so the scatter can never touch a radix-shared block."""
    B, T = k.shape[0], k.shape[1]
    tbl = entry["tbl"]
    bs = block_size(entry)
    if getattr(pos, "ndim", 0) == 1:
        p = pos[:, None] + jnp.arange(T)[None, :]          # [B, T]
    else:
        p = jnp.broadcast_to((pos + jnp.arange(T))[None, :], (B, T))
    bidx = jnp.arange(B)[:, None]                          # [B, 1]
    blk = tbl[bidx, p // bs]                               # [B, T]
    off = p % bs                                           # [B, T]
    new = dict(entry)
    if "k_scale" in entry:
        from bcg_tpu.models.transformer import _kv_quantizer

        quantize_kv = _kv_quantizer(entry)
        kq, ksc = quantize_kv(k)   # kq: [B, T, Hkv, Dh(/2)]; ksc: [B, T, Hkv]
        vq, vsc = quantize_kv(v)
        # Pool [N, Hkv, bs, Dh] / scales [N, Hkv, bs]: advanced indices
        # on axes (0, 2) move to the front, so the target region is
        # [B, T, Hkv, Dh] / [B, T, Hkv] — already the fresh-KV layout
        # (the same trick _write_cache_rows uses on the dense slab).
        new["k"] = entry["k"].at[blk, :, off].set(kq)
        new["v"] = entry["v"].at[blk, :, off].set(vq)
        new["k_scale"] = entry["k_scale"].at[blk, :, off].set(ksc)
        new["v_scale"] = entry["v_scale"].at[blk, :, off].set(vsc)
    else:
        new["k"] = entry["k"].at[blk, off].set(k.astype(entry["k"].dtype))
        new["v"] = entry["v"].at[blk, off].set(v.astype(entry["v"].dtype))
    return new


def paged_gather_entry(entry: Dict, upto_blocks: int = 0) -> Dict:
    """Dense-layout VIEW of a paged entry: gather each row's blocks and
    reshape to the dense cache layout (bf16 ``[B, S, Hkv, Dh]``; int8
    ``[B, Hkv, S, Dh]`` + ``[B, Hkv, S]`` scales), ``S = nblk * bs``.
    ``upto_blocks`` limits the gather to the table's first columns
    (suffix prefill reads only the prefix region).  The result carries
    no ``tbl`` — downstream attention/dequant code treats it exactly
    like a dense entry, which is what makes paged decode bit-identical
    to dense decode."""
    tbl = entry["tbl"]
    if upto_blocks:
        tbl = tbl[:, :upto_blocks]
    B, nblk = tbl.shape
    bs = block_size(entry)
    S = nblk * bs
    if "k_scale" in entry:
        def kv(name):
            g = entry[name][tbl]                  # [B, nblk, Hkv, bs, Dh]
            g = g.transpose(0, 2, 1, 3, 4)        # [B, Hkv, nblk, bs, Dh]
            return g.reshape(B, g.shape[1], S, g.shape[-1])

        def sc(name):
            g = entry[name][tbl]                  # [B, nblk, Hkv, bs]
            g = g.transpose(0, 2, 1, 3)           # [B, Hkv, nblk, bs]
            return g.reshape(B, g.shape[1], S)

        return {
            "k": kv("k"), "v": kv("v"),
            "k_scale": sc("k_scale"), "v_scale": sc("v_scale"),
        }
    def kv(name):
        g = entry[name][tbl]                      # [B, nblk, bs, Hkv, Dh]
        return g.reshape(B, S, g.shape[-2], g.shape[-1])

    return {"k": kv("k"), "v": kv("v")}


def num_kv_heads(entry: Dict) -> int:
    """Kv-head count, read off the pool's physical layout."""
    return entry["k"].shape[1 if "k_scale" in entry else 2]


def paged_decode_attention(q, entry: Dict, mask, scale, impl: str = "xla"):
    """Single-token decode attention over a paged cache — the paged
    variant of ``ops/decode_attention.decode_attention``.  q:
    ``[B, 1, H, Dh]``; mask: ``[B, S]`` attendable logical slots.

    ``impl`` :data:`PALLAS` / :data:`PALLAS_INTERPRET` runs the fused
    page-gather kernel; anything else gathers the row's blocks to the
    dense layout and runs the stock masked einsum attention
    (``transformer._xla_attention``) — bit-identical to the dense path
    by construction, and the kernel's conformance oracle."""
    if impl in (PALLAS, PALLAS_INTERPRET):
        from bcg_tpu.ops.decode_attention import pow2_rows

        B, _, H, Dh = q.shape
        Hkv = num_kv_heads(entry)
        group = H // Hkv
        g2 = pow2_rows(group)
        qg = q[:, 0].reshape(B, Hkv, group, Dh)
        if g2 != group:
            # Same padded-GQA dispatch as the dense int8 kernel: the
            # cache is what decode streams, so extra q rows cost MXU
            # work only (ops/decode_attention.decode_attention).
            qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g2 - group), (0, 0)))
        out = _paged_pallas_attention(
            qg, entry, mask[:, None, :], scale,
            interpret=(impl == PALLAS_INTERPRET),
        )
        if g2 != group:
            out = out[:, :, :group]
        return out.reshape(B, H, Dh)[:, None]
    from bcg_tpu.models.transformer import _kv_dequantizer, _xla_attention

    dense = paged_gather_entry(entry)
    k, v = dense["k"], dense["v"]
    if "k_scale" in dense:
        dequantize_kv = _kv_dequantizer(dense)
        k = dequantize_kv(k, dense["k_scale"]).transpose(0, 2, 1, 3).astype(q.dtype)
        v = dequantize_kv(v, dense["v_scale"]).transpose(0, 2, 1, 3).astype(q.dtype)
    return _xla_attention(q, k, v, mask[:, None, :], scale)


def paged_chunk_attention(q, entry: Dict, mask, scale, impl: str = "xla"):
    """Chunk decode attention over a paged cache — the fast-forward and
    speculative-verify loops' ``[B, K]`` token windows (paged chunked
    PREFILL never reaches here: its history attention runs through the
    transformer's cached-prefix path, ``_block`` with ``hist_len``).
    q: ``[B, K, H, Dh]``; mask: ``[B, K, S]``.

    ``impl`` :data:`PALLAS` / :data:`PALLAS_INTERPRET` runs the fused
    kernel with a ``[K*group, Dh]`` query tile per program (the
    ``chunk_decode_attention`` shape — the prefill flash kernel would
    pad K chunk rows to a 128-row block); the only other marker the
    decode loops resolve is ``"xla"``, the gather reference."""
    B, K, H, Dh = q.shape
    if impl in (PALLAS, PALLAS_INTERPRET):
        from bcg_tpu.ops.decode_attention import pow2_rows

        Hkv = num_kv_heads(entry)
        group = H // Hkv
        g2 = pow2_rows(group)
        # Pre-repeat the mask per query row (position-major: row
        # k*g2+g covers chunk position k) and lay q out
        # [B, Hkv, K*g2, Dh] to match — the chunk_decode_attention
        # idiom: no in-kernel repeat, padded rows reuse their chunk's
        # mask and are sliced away below.
        mp = jnp.repeat(mask, g2, axis=1)                    # [B, K*g2, S]
        qg = q.reshape(B, K, Hkv, group, Dh)
        if g2 != group:
            qg = jnp.pad(
                qg, ((0, 0), (0, 0), (0, 0), (0, g2 - group), (0, 0))
            )
        qg = qg.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, K * g2, Dh)
        out = _paged_pallas_attention(
            qg, entry, mp, scale, interpret=(impl == PALLAS_INTERPRET),
        )
        out = out.reshape(B, Hkv, K, g2, Dh)
        if g2 != group:
            out = out[:, :, :, :group]
        return out.transpose(0, 2, 1, 3, 4).reshape(B, K, H, Dh)
    from bcg_tpu.models.transformer import _kv_dequantizer, attention

    dense = paged_gather_entry(entry)
    ck, cv = dense["k"], dense["v"]
    if "k_scale" in dense:
        dequantize_kv = _kv_dequantizer(dense)
        ck = dequantize_kv(
            ck, dense["k_scale"]).transpose(0, 2, 1, 3).astype(q.dtype)
        cv = dequantize_kv(
            cv, dense["v_scale"]).transpose(0, 2, 1, 3).astype(q.dtype)
    # Stock masked attention over the gathered dense view: the K-row
    # decode windows reaching this branch are never flash-kernel
    # material, and a quantized gather already dequantized to bf16.
    return attention(q, ck, cv, mask, scale, "xla")


# ------------------------------------------------------------ fused kernel

def configured_pages_per_program(interpret: bool) -> int:
    """The CONFIGURED page-group size: ``BCG_TPU_PAGED_PAGES_PER_
    PROGRAM`` when set, else 1 under interpret mode (emulation has no
    per-program dispatch cost to amortize) and 8 on hardware (measured
    lesson from the dense kernels: ~2 us fixed cost per program
    dominates small blocks — 8 x 16-token pages ≈ one 128-token lane
    window per step).  This is what stats/bench surface; each kernel
    call additionally clamps it to its table width
    (:func:`pages_per_program`), and the value is read at TRACE time —
    already-compiled programs keep the grouping they compiled with."""
    from bcg_tpu.runtime.envflags import get_int

    ppp = get_int("BCG_TPU_PAGED_PAGES_PER_PROGRAM")
    return ppp if ppp > 0 else (1 if interpret else 8)


def pages_per_program(nblk: int, interpret: bool) -> int:
    """Pages each kernel program covers for an ``nblk``-wide table: the
    configured group size clamped to the table width (the wrapper pads
    the table with null blocks up to a multiple)."""
    return max(1, min(configured_pages_per_program(interpret), nblk))


def _paged_kernel(
    tbl_ref, q_ref, *refs, scale, num_pg, hkv, ppp, bs, quantized, int4,
):
    """One program of the fused paged-attention kernel: grid
    ``(B, nblk/ppp)``, all kv heads per program.  ``refs`` carries, in
    order, ``ppp`` K page refs, ``ppp`` V page refs, (quantized only)
    ``ppp`` + ``ppp`` scale page refs, the mask ref, the output ref and
    the three online-softmax scratch buffers.  Each page ref's block
    was DMA'd from the pool slot the row's block table names
    (``tbl[b, i*ppp + j]`` — the scalar-prefetch index maps in
    :func:`_paged_pallas_attention`); ``tbl_ref`` itself is only the
    prefetch operand and is not read here."""
    del tbl_ref
    k_refs = refs[:ppp]
    v_refs = refs[ppp:2 * ppp]
    if quantized:
        ks_refs = refs[2 * ppp:3 * ppp]
        vs_refs = refs[3 * ppp:4 * ppp]
        mask_ref, o_ref, m_scr, l_scr, acc_scr = refs[4 * ppp:]
    else:
        ks_refs = vs_refs = None
        mask_ref, o_ref, m_scr, l_scr, acc_scr = refs[2 * ppp:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    mask = mask_ref[0]                       # [M, ppp*bs]; M = 1 or rows
    for j in range(ppp):
        mj = mask[:, j * bs:(j + 1) * bs]    # [M, bs]
        mjf = mj.astype(jnp.float32)
        for h in range(hkv):
            q = q_ref[0, h]                  # [rows, Dh]
            if int4:
                # Packed-int4 page [Hkv, bs, Dh/2]: unpack both nibbles
                # in VMEM (int32 shifts — int8 shift lowering is spotty
                # across Mosaic versions, the ops/w4_matmul.py lesson)
                # and rebuild the head dim low-half-first, exactly the
                # quantize_kv_int4 packing contract.  bf16 scales.
                kp = k_refs[j][0, h].astype(jnp.int32)      # [bs, Dh/2]
                vp = v_refs[j][0, h].astype(jnp.int32)
                k_lo = jnp.right_shift(jnp.left_shift(kp, 28), 28)
                v_lo = jnp.right_shift(jnp.left_shift(vp, 28), 28)
                k_un = jnp.concatenate(
                    [k_lo, jnp.right_shift(kp, 4)], axis=-1
                ).astype(jnp.float32)                       # [bs, Dh]
                v_un = jnp.concatenate(
                    [v_lo, jnp.right_shift(vp, 4)], axis=-1
                ).astype(jnp.float32)
                k = k_un * ks_refs[j][0, h].astype(jnp.float32)[:, None]
                v = v_un * vs_refs[j][0, h].astype(jnp.float32)[:, None]
            elif quantized:
                # int8 page [Hkv, bs, Dh]: leading-dim head slice is a
                # Mosaic-native (bs, Dh) int8 tile; dequant in VMEM.
                k = k_refs[j][0, h].astype(jnp.float32) * ks_refs[j][0, h][:, None]
                v = v_refs[j][0, h].astype(jnp.float32) * vs_refs[j][0, h][:, None]
            else:
                k = k_refs[j][0, :, h, :]    # bf16 page [bs, Hkv, Dh]
                v = v_refs[j][0, :, h, :]
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                        # [rows, bs]
            scores = jnp.where(mj, scores, _NEG_INF)
            m_prev = m_scr[h]                # [rows, 1]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new) * mjf
            m_scr[h] = m_new
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(i == num_pg - 1)
    def _finish():
        for h in range(hkv):
            l = l_scr[h]
            o_ref[0, h] = (
                acc_scr[h] / jnp.where(l == 0.0, 1.0, l)
            ).astype(o_ref.dtype)


def _paged_pallas_attention(qg, entry: Dict, mp, scale, interpret: bool):
    """Shared pallas_call for the single-step and chunk paged paths.

    qg ``[B, Hkv, rows, Dh]``; mp ``[B, M, S]`` with M == 1 (broadcast)
    or rows, ``S = nblk * bs``.  Returns ``[B, Hkv, rows, Dh]``.

    The block table is the scalar-prefetch operand: page ``j`` of grid
    step ``(b, i)`` DMAs pool block ``tbl[b, i*ppp + j]`` — the Pallas
    pipeline emitter prefetches the NEXT program's pages while this one
    computes, which is the double-buffered page streaming the XLA
    gather path cannot express.  Table CONTENTS are traced values, so
    varying them between calls never re-traces (only pool/table shapes
    key compiles — the same contract as the gather path)."""
    tbl = entry["tbl"]
    quantized = "k_scale" in entry
    from bcg_tpu.models.transformer import kv_is_int4

    int4 = kv_is_int4(entry)
    dh_store = entry["k"].shape[-1]         # Dh, or Dh/2 packed int4
    bs = block_size(entry)
    B, nblk = tbl.shape
    _, Hkv, rows, Dh = qg.shape
    M = mp.shape[1]
    ppp = pages_per_program(nblk, interpret)
    pad = (-nblk) % ppp
    if pad:
        # Null-block padding: block 0 is all zeros and the padded mask
        # columns are False, so padded pages contribute nothing.
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
        mp = jnp.pad(mp, ((0, 0), (0, 0), (0, pad * bs)))
    num_pg = (nblk + pad) // ppp

    def kv_im(j):
        return lambda b, i, t: (t[b, i * ppp + j], 0, 0, 0)

    def sc_im(j):
        return lambda b, i, t: (t[b, i * ppp + j], 0, 0)

    if quantized:
        kv_shape = (1, Hkv, bs, dh_store)            # int8/int4 [N, Hkv, bs, *]
        sc_shape = (1, Hkv, bs)                      # f32/bf16 [N, Hkv, bs]
        page_specs = (
            [pl.BlockSpec(kv_shape, kv_im(j)) for j in range(ppp)] * 2
            + [pl.BlockSpec(sc_shape, sc_im(j)) for j in range(ppp)] * 2
        )
        page_args = (
            [entry["k"]] * ppp + [entry["v"]] * ppp
            + [entry["k_scale"]] * ppp + [entry["v_scale"]] * ppp
        )
    else:
        kv_shape = (1, bs, Hkv, Dh)                  # bf16 [N, bs, Hkv, Dh]
        page_specs = [
            pl.BlockSpec(kv_shape, kv_im(j)) for j in range(ppp)
        ] * 2
        page_args = [entry["k"]] * ppp + [entry["v"]] * ppp

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, num_pg),
        in_specs=[
            pl.BlockSpec((1, Hkv, rows, Dh), lambda b, i, t: (b, 0, 0, 0)),
            *page_specs,
            pl.BlockSpec((1, M, ppp * bs), lambda b, i, t: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rows, Dh), lambda b, i, t: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, num_pg=num_pg, hkv=Hkv, ppp=ppp, bs=bs,
        quantized=quantized, int4=int4,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, Dh), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tbl.astype(jnp.int32), qg, *page_args, mp)
