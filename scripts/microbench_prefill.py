#!/usr/bin/env python
"""Microbenchmark prefill components at game shapes, IN-LOOP.

Round-3 measured prefill at 15.8% MFU while decode sits at 88% of the
HBM roof — prefill is now the larger half of round time, and the bench
cannot say WHERE the other 84% goes (the per-call dispatch floor
hides per-op costs).  Like
``microbench_decode_attention.py``, every op here runs N times inside
ONE jitted ``fori_loop`` with a serializing data dependency, so the
per-iteration number is the in-loop cost.

Measured components at bench-1b layer dims (B=10, L=2048, D=2048,
H=16/Hkv=8/Dh=128, F=6144):

- each projection matmul in bf16 vs int8 W8A8 (``quantize.dense``:
  act-quant + int8 dot + rescale) vs int4 W4A16 (XLA dequant fallback —
  the prefill path of ``dense``),
- flash-attention prefill (Pallas) vs the blockwise-scan fallback,
- rope rotation,
- rmsnorm,
- a FULL transformer layer via the same primitives chained.

Prints per-op ms/iter, achieved TFLOP/s, and % of the v5e peak for the
op's dtype (bf16 197 / int8 394 TFLOP/s) so the MFU gap decomposes.

Usage (on the TPU):  python scripts/microbench_prefill.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bcg_tpu.models.configs import spec_for_model
from bcg_tpu.models.quantize import dense, quantize_weight, quantize_weight_int4
from bcg_tpu.models.transformer import apply_rope, rms_norm, rope_table
from bcg_tpu.ops.attention import blockwise_attention, flash_attention
from bcg_tpu.runtime.envflags import get_bool, get_int

ITERS = get_int("MB_ITERS")
PEAK_BF16 = 197e12
PEAK_INT8 = 394e12


def loop_time(body, carry0, iters=ITERS):
    @jax.jit
    def run(carry):
        return jax.lax.fori_loop(0, iters, body, carry)

    out = run(carry0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = run(carry0)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def feedback(x, out):
    """Fold a scalar of ``out`` back into ``x`` to serialize iterations."""
    s = out.astype(jnp.float32).mean() * 1e-20
    return x + s.astype(x.dtype)


def bench_matmul(name, x, w, flops, peak):
    def body(i, carry):
        xx, acc = carry
        out = dense(xx, w)
        return (feedback(xx, out), acc + out.astype(jnp.float32).mean())

    dt = loop_time(body, (x, jnp.float32(0)))
    print(f"  {name:<28s} {dt*1e3:7.2f} ms  {flops/dt/1e12:6.1f} TF/s"
          f"  {100*flops/dt/peak:5.1f}% peak")
    return dt


def main():
    B = get_int("MB_B")
    L = get_int("MB_L")
    spec = spec_for_model("bcg-tpu/bench-1b")
    D, H, Hkv, Dh, F = 2048, 16, 8, 128, 6144
    if get_bool("MB_TINY"):  # CPU smoke: shrink every dim
        B, L, D, H, Hkv, Dh, F = 2, 64, 64, 2, 1, 32, 128
    S = L  # self-attention over the fresh prompt
    rng = np.random.default_rng(0)
    print(f"prefill shapes: B={B} L={L} D={D} H={H} Hkv={Hkv} Dh={Dh} F={F}"
          f"  ({ITERS} in-loop iterations; backend={jax.default_backend()})")

    x = jnp.asarray(rng.standard_normal((B, L, D)) * 0.02, jnp.bfloat16)
    BL = B * L

    shapes = {
        "qkv": (D, (H + 2 * Hkv) * Dh),
        "o": (H * Dh, D),
        "gate_up": (D, 2 * F),
        "down": (F, D),
    }
    ws = {k: jnp.asarray(rng.standard_normal(s) * 0.02, jnp.bfloat16)
          for k, s in shapes.items()}
    mode_weights = {
        "bf16": ws,
        "int8": {k: quantize_weight(v) for k, v in ws.items()},
        "int4": {k: quantize_weight_int4(v) for k, v in ws.items()},
    }

    total = {"bf16": 0.0, "int8": 0.0, "int4": 0.0}
    mm_flops = 0
    for k, (din, dout) in shapes.items():
        xin = x if din == D else jnp.asarray(
            rng.standard_normal((B, L, din)) * 0.02, jnp.bfloat16)
        fl = 2 * BL * din * dout
        mm_flops += fl
        total["bf16"] += bench_matmul(
            f"{k} bf16", xin, mode_weights["bf16"][k], fl, PEAK_BF16)
        total["int8"] += bench_matmul(
            f"{k} int8 W8A8", xin, mode_weights["int8"][k], fl, PEAK_INT8)
        total["int4"] += bench_matmul(
            f"{k} int4 W4A16", xin, mode_weights["int4"][k], fl, PEAK_BF16)

    # Attention at prefill shapes, causal mask.
    q = jnp.asarray(rng.standard_normal((B, L, H, Dh)) * 0.1, jnp.bfloat16)
    k_ = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)) * 0.1, jnp.bfloat16)
    v_ = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)) * 0.1, jnp.bfloat16)
    causal = jnp.asarray(
        np.tril(np.ones((L, S), bool))[None].repeat(B, 0))
    scale = Dh ** -0.5
    # ~half the score/AV work survives the causal mask.
    attn_flops = 2 * 2 * B * H * L * S * Dh // 2

    flash_dt = 0.0
    for name, fn in (("flash_attention (Pallas)", flash_attention),
                     ("blockwise_attention (XLA)", blockwise_attention)):
        def body(i, carry, fn=fn):
            qq, acc = carry
            out = fn(qq, k_, v_, causal, scale)
            return (feedback(qq, out), acc + out.astype(jnp.float32).mean())

        dt = loop_time(body, (q, jnp.float32(0)))
        if fn is flash_attention:
            flash_dt = dt  # subtracted from the full-layer gap below
        print(f"  {name:<28s} {dt*1e3:7.2f} ms  {attn_flops/dt/1e12:6.1f} TF/s"
              f"  {100*attn_flops/dt/PEAK_BF16:5.1f}% peak")

    # A/B against the OFFICIAL jax pallas TPU flash kernel (no GQA: KV
    # repeated to H heads, so it carries group x the KV bytes — prefill
    # at these shapes is compute-dominated, so the comparison is still
    # apples-to-apples on the score/AV pipeline).
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )

        group = H // Hkv
        kr = jnp.repeat(k_, group, axis=2).transpose(0, 2, 1, 3)  # [B,H,S,Dh]
        vr = jnp.repeat(v_, group, axis=2).transpose(0, 2, 1, 3)

        def jf_body(i, carry):
            qq, acc = carry
            out = jax_flash(
                qq.transpose(0, 2, 1, 3), kr, vr,
                causal=True, sm_scale=scale,
            )
            out = out.transpose(0, 2, 1, 3)
            return (feedback(qq, out), acc + out.astype(jnp.float32).mean())

        dt = loop_time(jf_body, (q, jnp.float32(0)))
        print(f"  {'official jax tpu flash':<28s} {dt*1e3:7.2f} ms  "
              f"{attn_flops/dt/1e12:6.1f} TF/s"
              f"  {100*attn_flops/dt/PEAK_BF16:5.1f}% peak")
    except Exception as exc:  # noqa: BLE001 — comparison point, not critical
        print(f"  official jax tpu flash: unavailable ({type(exc).__name__}: "
              f"{str(exc)[:120]})")

    # A/B against the official SPLASH kernel, GQA-NATIVE via the MQA
    # variant (per kv-head: `group` query heads share one KV stream —
    # no KV repeat, unlike the flash row above).  q is pre-scaled
    # (splash applies no sm_scale itself).
    try:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
            splash_attention_mask as sm,
        )

        group = H // Hkv
        smask = sm.MultiHeadMask([sm.CausalMask((L, S)) for _ in range(group)])
        mqa = sk.make_splash_mqa(smask, head_shards=1, q_seq_shards=1,
                                 block_sizes=sk.BlockSizes.get_default())
        splash_fn = jax.vmap(jax.vmap(mqa))  # over batch, then kv-head

        kg = k_.transpose(0, 2, 1, 3)                      # [B,Hkv,S,Dh]
        vg = v_.transpose(0, 2, 1, 3)

        def sp_body(i, carry):
            qq, acc = carry
            qg2 = (qq * scale).transpose(0, 2, 1, 3).reshape(
                B, Hkv, group, L, Dh)
            out = splash_fn(qg2, kg, vg)                   # [B,Hkv,g,L,Dh]
            out = out.reshape(B, H, L, Dh).transpose(0, 2, 1, 3)
            return (feedback(qq, out), acc + out.astype(jnp.float32).mean())

        dt = loop_time(sp_body, (q, jnp.float32(0)))
        print(f"  {'official splash (GQA-mqa)':<28s} {dt*1e3:7.2f} ms  "
              f"{attn_flops/dt/1e12:6.1f} TF/s"
              f"  {100*attn_flops/dt/PEAK_BF16:5.1f}% peak")
    except Exception as exc:  # noqa: BLE001 — comparison point, not critical
        print(f"  official splash: unavailable ({type(exc).__name__}: "
              f"{str(exc)[:120]})")

    # Rope + rmsnorm via the PRODUCTION ops (transformer.py) at the
    # spec's constants, so the microbench measures the real code path
    # (bandwidth-bound elementwise; report ms + GB/s).
    positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    cos, sin = rope_table(positions, Dh, spec.rope_theta)

    def rope_body(i, carry):
        qq, acc = carry
        rot = apply_rope(qq, cos, sin)
        return (feedback(qq, rot), acc + rot.astype(jnp.float32).mean())

    dt = loop_time(rope_body, (q, jnp.float32(0)))
    gb = 2 * q.size * 2 / 1e9
    print(f"  {'rope (q-side)':<28s} {dt*1e3:7.2f} ms  {gb/dt:6.1f} GB/s")

    g = jnp.ones((D,), jnp.bfloat16)

    def norm_body(i, carry):
        xx, acc = carry
        out = rms_norm(xx, g, spec.rms_eps)
        return (feedback(xx, out), acc + out.astype(jnp.float32).mean())

    dt = loop_time(norm_body, (x, jnp.float32(0)))
    gb = 2 * x.size * 2 / 1e9
    print(f"  {'rmsnorm':<28s} {dt*1e3:7.2f} ms  {gb/dt:6.1f} GB/s")

    # FULL layer chained from the same primitives: norm -> qkv ->
    # qk-norm -> rope -> flash attn -> o -> norm -> gate/up ->
    # (silu*mul) -> down, with residual adds.  The chained number
    # exposes fusion/dispatch gaps the per-op numbers hide.
    g_qk = jnp.ones((Dh,), jnp.bfloat16)
    def full_layer(xx, wmode):
        w = mode_weights[wmode]
        h = xx
        hn = rms_norm(h, g, spec.rms_eps)
        qkv = dense(hn, w["qkv"])
        qh = qkv[..., :H * Dh].reshape(B, L, H, Dh)
        kh = qkv[..., H * Dh:(H + Hkv) * Dh].reshape(B, L, Hkv, Dh)
        vh = qkv[..., (H + Hkv) * Dh:].reshape(B, L, Hkv, Dh)
        if spec.qk_norm:  # bench-1b has per-head q/k norms (Qwen3-style)
            qh = rms_norm(qh, g_qk, spec.rms_eps)
            kh = rms_norm(kh, g_qk, spec.rms_eps)
        qh = apply_rope(qh, cos, sin)
        kh = apply_rope(kh, cos, sin)
        attn = flash_attention(qh, kh, vh, causal, scale)
        h = h + dense(attn.reshape(B, L, H * Dh), w["o"])
        hn = rms_norm(h, g, spec.rms_eps)
        gu = dense(hn, w["gate_up"])
        gate, up = jnp.split(gu, 2, axis=-1)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
        return h + dense(act, w["down"])

    layer_flops = mm_flops + attn_flops
    for mode in ("bf16", "int8", "int4"):
        def body(i, carry, mode=mode):
            xx, acc = carry
            out = full_layer(xx, mode)
            return (feedback(xx, out), acc + out.astype(jnp.float32).mean())

        dt = loop_time(body, (x, jnp.float32(0)))
        gap = dt - total[mode] - flash_dt
        print(f"  full layer {mode:<17s} {dt*1e3:7.2f} ms "
              f" {layer_flops/dt/1e12:6.1f} TF/s "
              f" (vs matmuls {total[mode]*1e3:.2f} + attn {flash_dt*1e3:.2f} ms;"
              f" elementwise+fusion gap {gap*1e3:.2f} ms)")
    print(f"  layer matmul-only roofline: {mm_flops/PEAK_BF16*1e3:.2f} ms bf16"
          f" / {mm_flops/PEAK_INT8*1e3:.2f} ms int8;"
          f" attn roofline {attn_flops/PEAK_BF16*1e3:.2f} ms bf16")


if __name__ == "__main__":
    main()
