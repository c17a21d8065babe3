#!/usr/bin/env python
"""Microbenchmark decode-step components at game shapes, IN-LOOP.

Every device call pays a host dispatch latency, so per-call timing is
latency-floored and meaningless for ops that run inside the decode
``lax.while_loop``.  Every measurement here runs the
op N times inside ONE jitted ``fori_loop`` with a serializing data
dependency, so the reported per-iteration cost is the in-loop cost.

Motivated by round-3: the int8-KV decode loop measured 9.0 ms/step vs
bf16's 5.1 while carrying ~2/3 the traffic.  Suspects: the Pallas
kernel's achieved bandwidth, and the quantize+scatter cache writes.

Usage (on the TPU):  python scripts/microbench_decode_attention.py
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bcg_tpu.ops.decode_attention import (
    chunk_decode_attention,
    decode_attention,
    quantize_kv,
)

ITERS = 100


def loop_time(make_body, carry0, iters=ITERS):
    """Time ``iters`` sequential in-loop applications of ``make_body``
    inside one jit; returns seconds per iteration."""

    @jax.jit
    def run(carry):
        return jax.lax.fori_loop(0, iters, make_body, carry)

    out = run(carry0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = run(carry0)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    B, H, Hkv, Dh, S = 10, 16, 8, 128, 4096
    K = 8
    scale = Dh ** -0.5
    rng = np.random.default_rng(0)
    q0 = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.bfloat16)
    qk0 = jnp.asarray(rng.standard_normal((B, K, H, Dh)), jnp.bfloat16)
    k_bf = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), jnp.bfloat16)
    v_bf = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), jnp.bfloat16)
    k_i8 = jnp.asarray(rng.integers(-127, 127, (B, Hkv, S, Dh)), jnp.int8)
    v_i8 = jnp.asarray(rng.integers(-127, 127, (B, Hkv, S, Dh)), jnp.int8)
    ks = jnp.asarray(rng.random((B, Hkv, S)) * 0.01 + 0.001, jnp.float32)
    vs = jnp.asarray(rng.random((B, Hkv, S)) * 0.01 + 0.001, jnp.float32)
    mask = jnp.asarray(np.ones((B, S), bool))
    maskk = jnp.asarray(np.ones((B, K, S), bool))

    i8_bytes = 2 * B * Hkv * S * Dh + 2 * B * Hkv * S * 4
    bf_bytes = 2 * B * S * Hkv * Dh * 2
    print(f"shapes: B={B} H={H} Hkv={Hkv} Dh={Dh} S={S}; per-step KV "
          f"traffic int8 {i8_bytes/1e6:.0f} MB, bf16 {bf_bytes/1e6:.0f} MB; "
          f"{ITERS} in-loop iterations")

    def attn_body(attn_fn):
        # carry = (acc, q); feed acc back into q so iterations serialize.
        def body(i, carry):
            acc, q = carry
            out = attn_fn(q)
            acc = acc + out.astype(jnp.float32).mean()
            q = q + (acc * 1e-20).astype(q.dtype)
            return (acc, q)
        return body

    # int8 Pallas kernel across block sizes.
    for bs in (512, 1024, 2048, 4096):
        t = loop_time(
            attn_body(partial(
                decode_attention, k=k_i8, v=v_i8, mask=mask, scale=scale,
                k_scale=ks, v_scale=vs, block_s=bs,
            )),
            (jnp.float32(0), q0),
        )
        print(f"int8 pallas  block={bs:<4d}: {t*1e3:7.3f} ms/it  "
              f"{i8_bytes/t/1e9:6.1f} GB/s")

    # bf16 einsum reference (the stock decode path).
    def einsum_path(q):
        qg = q.reshape(B, Hkv, H // Hkv, Dh)
        logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_bf).astype(jnp.float32) * scale
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(v_bf.dtype)
        return jnp.einsum("bhgs,bshd->bhgd", p, v_bf).reshape(B, H, Dh)

    t = loop_time(attn_body(einsum_path), (jnp.float32(0), q0))
    print(f"bf16 einsum           : {t*1e3:7.3f} ms/it  {bf_bytes/t/1e9:6.1f} GB/s")

    # int8 einsum-with-dequant (the non-Pallas int8 fallback shape).
    def dequant_einsum(q):
        kd = (k_i8.astype(jnp.float32) * ks[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
        vd = (v_i8.astype(jnp.float32) * vs[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
        qg = q.reshape(B, Hkv, H // Hkv, Dh)
        logits = jnp.einsum("bhgd,bshd->bhgs", qg, kd).astype(jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None, None, :], logits, -1e30), axis=-1)
        return jnp.einsum("bhgs,bshd->bhgd", p.astype(vd.dtype), vd).reshape(B, H, Dh)

    t = loop_time(attn_body(dequant_einsum), (jnp.float32(0), q0))
    print(f"int8 dequant einsum   : {t*1e3:7.3f} ms/it  {i8_bytes/t/1e9:6.1f} GB/s")

    # int8 chunk kernel (the fast-forward path).
    def chunk_body(bs):
        def body(i, carry):
            acc, qk = carry
            out = chunk_decode_attention(
                qk, k_i8, v_i8, maskk, scale, k_scale=ks, v_scale=vs,
                block_s=bs,
            )
            acc = acc + out.astype(jnp.float32).mean()
            qk = qk + (acc * 1e-20).astype(qk.dtype)
            return (acc, qk)
        return body

    for bs in (512, 2048, 4096):
        t = loop_time(chunk_body(bs), (jnp.float32(0), qk0))
        print(f"int8 chunk{K} block={bs:<4d}: {t*1e3:7.3f} ms/it  "
              f"{i8_bytes/t/1e9:6.1f} GB/s")

    # Cache-write paths (per decode step): bf16 = 2 dynamic updates;
    # int8 = quantize + transpose + 4 updates (k/v/scales).
    kn = jnp.asarray(rng.standard_normal((B, K, Hkv, Dh)), jnp.bfloat16)

    def bf16_write(i, carry):
        acc, k_cache, v_cache = carry
        fresh = kn + (acc * 1e-20).astype(kn.dtype)
        k_cache = jax.lax.dynamic_update_slice(k_cache, fresh, (0, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, fresh, (0, 0, 0, 0))
        return (acc + k_cache[0, 0, 0, 0].astype(jnp.float32), k_cache, v_cache)

    t = loop_time(bf16_write, (jnp.float32(0), k_bf, v_bf))
    print(f"bf16 cache write (K={K}) : {t*1e3:7.3f} ms/it")

    def int8_write(i, carry):
        acc, kc, vc, ksc, vsc = carry
        fresh = kn + (acc * 1e-20).astype(kn.dtype)
        kq, s = quantize_kv(fresh)
        kc = jax.lax.dynamic_update_slice(kc, kq.transpose(0, 2, 1, 3), (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, kq.transpose(0, 2, 1, 3), (0, 0, 0, 0))
        ksc = jax.lax.dynamic_update_slice(ksc, s.transpose(0, 2, 1), (0, 0, 0))
        vsc = jax.lax.dynamic_update_slice(vsc, s.transpose(0, 2, 1), (0, 0, 0))
        return (acc + kc[0, 0, 0, 0].astype(jnp.float32), kc, vc, ksc, vsc)

    t = loop_time(int8_write, (jnp.float32(0), k_i8, v_i8, ks, vs))
    print(f"int8 cache write (K={K}) : {t*1e3:7.3f} ms/it")


if __name__ == "__main__":
    main()
