"""Ring attention: sequence-parallel exact attention over the ``sp`` axis.

Long-context path (SURVEY.md §5.7): the KV sequence is sharded across the
``sp`` mesh axis; K/V blocks rotate around the ring via ``ppermute`` while
each device's queries accumulate flash-style (running max / running sum in
f32), so attention over an L-token context costs L/sp memory per chip and
the collective rides ICI neighbour links.  Exact — not an approximation:
results match full attention to numerical tolerance.

The reference has no long-context machinery at all (it *compresses*
context instead, SURVEY.md §5.7); this makes 100K+-token histories
feasible where the reference caps at 8K.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _block_attend(q, k, v, q_pos, k_pos, scale, causal, kv_valid=None):
    """One q-block x kv-block partial attention.

    q: [B, Tq, H, Dh], k/v: [B, Tk, Hkv, Dh], kv_valid: [B, Tk] bool
    (False = padded kv position, masked for every query).
    Returns (scores_max [B,H',G,Tq], exp_sum, acc [B,Tq,H,Dh-as-grouped]).
    """
    B, Tq, H, Dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Tq, Hkv, group, Dh)
    logits = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    if kv_valid is not None:
        # [B, Tk] -> [B, 1, 1, 1, Tk] over (Hkv, G, Tq)
        logits = jnp.where(
            kv_valid[:, None, None, None, :], logits, -jnp.inf
        )
    m = jnp.max(logits, axis=-1)  # [B,Hkv,G,Tq]
    # Fully-masked rows (no valid kv yet) keep m = -inf so the caller's
    # running-max merge ignores them; a 0.0 sentinel there would inflate
    # the merged max and underflow exp() whenever every valid logit is
    # strongly negative.  The local exp still needs a finite reference.
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - safe_m[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,Hkv,G,Tq]
    acc = jnp.einsum(
        "bhgts,bshd->bthgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m, l, acc


def _ring_body(axis_name: str, sp: int, causal: bool, scale: float,
               q, k0, v0, q_offset, block_len, kv_valid0=None,
               vary_axes=None):
    """Runs on each device inside shard_map.

    The carry tuple (and the per-step ppermute set) includes the kv
    validity block only when one was given — the unmasked path must not
    rotate a dummy all-ones block around the ring every step.
    """
    B, Tq, H, Dh = q.shape
    Hkv = k0.shape[2]
    group = H // Hkv
    my_idx = jax.lax.axis_index(axis_name)
    q_pos = q_offset + jnp.arange(Tq)
    masked = kv_valid0 is not None
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(s, carry):
        if masked:
            m, l, acc, k, v, kvv = carry
        else:
            m, l, acc, k, v = carry
            kvv = None
        # After s rotations device i holds block (i - s) mod sp.
        block_owner = (my_idx - s) % sp
        k_pos = block_owner * block_len + jnp.arange(k.shape[1])
        bm, bl, bacc = _block_attend(
            q, k, v, q_pos, k_pos, scale, causal, kv_valid=kvv,
        )
        # m / bm are -inf for rows with no valid kv so far; reference
        # the exps against a finite max and zero the -inf sides (their
        # l/acc are already 0) instead of evaluating exp(-inf - -inf).
        new_m = jnp.maximum(m, bm)
        safe_new = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_new), 0.0)
        beta = jnp.where(jnp.isfinite(bm), jnp.exp(bm - safe_new), 0.0)
        l = l * alpha + bl * beta
        acc = acc * alpha.transpose(0, 3, 1, 2)[..., None] + \
            bacc * beta.transpose(0, 3, 1, 2)[..., None]
        # Rotate kv (and its validity block) to the next device.
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        if not masked:
            return new_m, l, acc, k, v
        kvv = jax.lax.ppermute(kvv, axis_name, perm)
        return new_m, l, acc, k, v, kvv

    # Initial accumulators must carry the same varying-over-mesh-axes
    # type as the loop outputs (which derive from the sharded inputs and
    # axis_index) — hence pcast-to-varying over every axis the inputs are sharded
    # on (sp always; plus dp/tp on a composed mesh).
    vary = vary_axes if vary_axes is not None else (axis_name,)

    def varying(x):
        return jax.lax.pcast(x, vary, to="varying")

    m0 = varying(jnp.full((B, Hkv, group, Tq), -jnp.inf, jnp.float32))
    l0 = varying(jnp.zeros((B, Hkv, group, Tq), jnp.float32))
    acc0 = varying(jnp.zeros((B, Tq, Hkv, group, Dh), jnp.float32))
    carry0 = (m0, l0, acc0, k0, v0) + ((kv_valid0,) if masked else ())
    out_carry = jax.lax.fori_loop(0, sp, step, carry0)
    m, l, acc = out_carry[0], out_carry[1], out_carry[2]
    out = acc / jnp.maximum(l.transpose(0, 3, 1, 2)[..., None], 1e-30)
    return out.reshape(B, Tq, H, Dh).astype(q.dtype)


def sp_chunk_decode_attention(
    q: jax.Array,        # [B, K, H, Dh] chunk of decode queries
    k: jax.Array,        # [B, S, Hkv, Dh] cache, S divisible by sp
                         # (int8 layout [B, Hkv, S, Dh] with k_scale/v_scale)
    v: jax.Array,        # [B, S, Hkv, Dh]
    mask: jax.Array,     # [B, K, S] bool attendable slots per query
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # [B, Hkv, S] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunk-decode attention over a sequence-sharded KV cache.

    Flash-decoding shape: each device attends its local S/sp cache slice
    (partial max / exp-sum / accumulator in f32), then the partials merge
    across the ``sp`` axis with one ``pmax`` + two ``psum``s of
    O(B*K*H)-sized stats — the cache itself never moves.  With sp chips
    the decode-bandwidth roof scales ~sp× for long contexts: decode is
    KV-bound (BENCH_NOTES: 88% of single-chip HBM roof at bench shapes),
    so slicing the cache across chips is the scaling lever single-chip
    kernels cannot reach.  Exact, not approximate.  Serves both the
    plain single-token loop (K=1 via :func:`sp_decode_attention`) and
    the forced-chain fast-forward loop's [B, K] chunks.

    With ``k_scale``/``v_scale`` the cache is int8 in its storage layout
    [B, Hkv, S, Dh] (scales [B, Hkv, S]); each device dequantizes only
    its LOCAL S/sp slice inside the shard_map — sp× less dequant work
    and traffic than the replicated full-cache fallback.

    Composed meshes shard batch over ``dp`` and whole GQA groups over
    ``tp`` when the dims divide (same policy as :func:`ring_attention`).
    """
    quantized = k_scale is not None
    B, K, H, Dh = q.shape
    S = k.shape[2] if quantized else k.shape[1]
    Hkv = k.shape[1] if quantized else k.shape[2]
    sp = mesh.shape[axis_name]
    if S % sp:
        raise ValueError(f"cache length {S} not divisible by sp={sp}")
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    group = H // Hkv

    dp_ax = (
        "dp"
        if mesh.shape.get("dp", 1) > 1 and B % mesh.shape["dp"] == 0
        else None
    )
    tp_ax = (
        "tp"
        if (mesh.shape.get("tp", 1) > 1
            and H % mesh.shape["tp"] == 0 and Hkv % mesh.shape["tp"] == 0)
        else None
    )

    def body(q_blk, k_blk, v_blk, mask_blk, *scales):
        b = q_blk.shape[0]
        if quantized:
            # Dequantize the LOCAL slice, KEEPING the int8 storage
            # layout [b, hkv, s, Dh] — layout-native einsum subscripts
            # below let XLA fuse the dequant into the dots instead of
            # materializing a transposed bf16 copy of the slice every
            # decode step (the transpose is the materialization point,
            # see _dequant_slice).
            from bcg_tpu.ops.decode_attention import dequantize_kv

            ks_blk, vs_blk = scales
            k_loc = dequantize_kv(k_blk, ks_blk).astype(q_blk.dtype)
            v_loc = dequantize_kv(v_blk, vs_blk).astype(q_blk.dtype)
            kv_sub = "bhsd"
        else:
            k_loc, v_loc = k_blk, v_blk
            kv_sub = "bshd"
        qg = q_blk.reshape(b, K, -1, group, Dh)       # [b, K, hkv, g, Dh]
        # Stats layout [b, K, hkv, g(, ...)] throughout — K stays in
        # position 1 on every side, so no transposes in the merge.
        logits = jnp.einsum(
            f"bkhgd,{kv_sub}->bkhgs", qg, k_loc,
            preferred_element_type=jnp.float32,
        ) * scale
        logits = jnp.where(
            mask_blk[:, :, None, None, :], logits, -jnp.inf
        )
        m_loc = jnp.max(logits, axis=-1)              # [b, K, hkv, g]
        safe_m = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        l_loc = jnp.sum(p, axis=-1)                   # [b, K, hkv, g]
        acc_loc = jnp.einsum(
            f"bkhgs,{kv_sub}->bkhgd", p.astype(v_loc.dtype), v_loc,
            preferred_element_type=jnp.float32,
        )
        # Merge partials across the cache slices: global running max,
        # then rescale each slice's exp-sum/accumulator into it.  pmax
        # the RAW per-slice max — a fully-masked slice contributes -inf,
        # not a 0.0 sentinel that would inflate the global max and
        # underflow exp() when every valid logit is strongly negative
        # (short left-padded rows on large sp leave most slices empty).
        m_glob_raw = jax.lax.pmax(m_loc, axis_name)
        m_glob = jnp.where(jnp.isfinite(m_glob_raw), m_glob_raw, 0.0)
        corr = jnp.where(                              # [b, K, hkv, g]
            jnp.isfinite(m_loc), jnp.exp(m_loc - m_glob), 0.0
        )
        l = jax.lax.psum(l_loc * corr, axis_name)
        acc = jax.lax.psum(acc_loc * corr[..., None], axis_name)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(b, K, -1, Dh).astype(q_blk.dtype)

    if quantized:
        kv_spec = P(dp_ax, tp_ax, axis_name, None)   # [B, Hkv, S, Dh]
        extra_in = (P(dp_ax, tp_ax, axis_name),) * 2  # scales [B, Hkv, S]
        extra_args = (k_scale, v_scale)
    else:
        kv_spec = P(dp_ax, axis_name, tp_ax, None)   # [B, S, Hkv, Dh]
        extra_in = ()
        extra_args = ()
    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp_ax, None, tp_ax, None),       # q [B, K, H, Dh]
            kv_spec, kv_spec,
            P(dp_ax, None, axis_name),         # mask [B, K, S]
        ) + extra_in,
        out_specs=P(dp_ax, None, tp_ax, None),
    )
    return f(q, k, v, mask, *extra_args)


def sp_decode_attention(
    q: jax.Array,        # [B, H, Dh] one decode-step query
    k: jax.Array,        # [B, S, Hkv, Dh] cache, S divisible by sp
    v: jax.Array,        # [B, S, Hkv, Dh]
    mask: jax.Array,     # [B, S] bool attendable slots
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-token decode attention over a sequence-sharded KV cache
    (the K=1 case of :func:`sp_chunk_decode_attention`)."""
    return sp_chunk_decode_attention(
        q[:, None], k, v, mask[:, None, :], mesh,
        axis_name=axis_name, scale=scale, k_scale=k_scale, v_scale=v_scale,
    )[:, 0]


def ring_attention(
    q: jax.Array,   # [B, T, H, Dh], T divisible by sp
    k: jax.Array,   # [B, T, Hkv, Dh]
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    kv_valid: Optional[jax.Array] = None,  # [B, T] bool; False = pad
) -> jax.Array:
    """Exact attention with the sequence sharded over ``axis_name``.

    ``kv_valid`` masks padded kv positions for every query (the engine's
    left-padded batches need it); the validity block rotates around the
    ring with its k/v block.  Fully-masked query rows output 0, matching
    the engine's flash path.
    """
    sp = mesh.shape[axis_name]
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    if T % sp:
        raise ValueError(f"sequence length {T} not divisible by sp={sp}")
    block_len = T // sp
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    # Composed meshes: attention is independent per batch row and per
    # GQA group, so shard batch over `dp` and heads over `tp` whenever
    # the dims divide (a spec that omits a mesh axis REPLICATES over it —
    # on a dp x tp x sp mesh that would all-gather the tp-sharded heads
    # into every device and defeat the O(L/sp) memory point).  Sharding
    # heads requires BOTH H and Hkv to divide so each shard keeps whole
    # GQA groups.
    dp_ax = (
        "dp"
        if mesh.shape.get("dp", 1) > 1 and B % mesh.shape["dp"] == 0
        else None
    )
    tp_ax = (
        "tp"
        if (mesh.shape.get("tp", 1) > 1
            and H % mesh.shape["tp"] == 0 and Hkv % mesh.shape["tp"] == 0)
        else None
    )
    qkv_spec = P(dp_ax, axis_name, tp_ax, None)
    valid_spec = P(dp_ax, axis_name)
    in_specs = (qkv_spec, qkv_spec, qkv_spec) + (
        (valid_spec,) if kv_valid is not None else ()
    )

    vary_axes = tuple(a for a in (dp_ax, axis_name, tp_ax) if a is not None)

    def body(q_blk, k_blk, v_blk, *rest):
        my_idx = jax.lax.axis_index(axis_name)
        q_offset = my_idx * block_len
        return _ring_body(axis_name, sp, causal, scale,
                          q_blk, k_blk, v_blk, q_offset, block_len,
                          kv_valid0=rest[0] if rest else None,
                          vary_axes=vary_axes)

    f = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec,
    )
    args = (q, k, v) + ((kv_valid,) if kv_valid is not None else ())
    return f(*args)
