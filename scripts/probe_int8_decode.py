#!/usr/bin/env python
"""Lower and validate the int8 decode-attention kernels on the TPU.

The all-heads int8 kernels (ops/decode_attention.py, round-3 rework:
grid (B, nS) with an in-kernel Hkv loop) are interpret-mode tested on
CPU but have never lowered on real hardware.  This probe runs both the
single-step and fast-forward chunk kernels at bench-1b and 8B game
shapes against a pure-XLA dequant-attention reference, so a Mosaic
lowering or miscompile problem surfaces as a named failure instead of
a crash (or silent corruption) inside the queued int8-KV / 8B benches.

Fails off-TPU (nothing would be validated).  Prints
"int8-decode-probe OK" when all cases pass.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bcg_tpu.ops.decode_attention import (
    chunk_decode_attention,
    decode_attention,
    dequantize_kv,
    quantize_kv,
)

# (name, B, H, Hkv, Dh, S).  2048/4096 are lengths the engine serves
# (it aligns the int8 cache to ALIGN_S=1024); 3584 is off that alignment
# and on the kernels' own block (BLOCK_S).
CASES = [
    ("1b-shapes", 10, 16, 8, 128, 2048),
    ("8b-shapes", 10, 32, 8, 128, 4096),
    ("off-align-path", 10, 32, 8, 128, 3584),
]

# INFORMATIONAL cases: validated-if-they-pass, but failures do NOT gate
# the probe's verdict — an experimental geometry that regresses must
# not read as a failure of the VALIDATED group-2/4 configs.  14B (H=40, Hkv=8 -> GQA group 5):
# the wrapper now pads query rows to the next power of two
# (ops/decode_attention.py), so the kernel sees rows=8 — a validated
# count — but the padded dispatch itself has not run on hardware yet;
# the engine's GQA group guard keeps 14B on the XLA dequant fallback
# until this case records an OK.
INFO_CASES = [
    ("14b-group5-padded", 10, 40, 8, 128, 4096),
]


def _reference(q, kd, vd, mask, scale):
    """Stock masked softmax attention on the dequantized cache.

    q [B, H, Dh]; kd/vd [B, Hkv, S, Dh] f32; mask [B, S].
    """
    B, H, Dh = q.shape
    Hkv = kd.shape[1]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, Dh).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg, kd) * scale
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, vd)
    return out.reshape(B, H, Dh)


def main() -> None:
    backend = jax.default_backend()
    print("backend:", backend)
    if backend != "tpu":
        print("int8-decode-probe FAILED: accelerator unavailable "
              "(backend is not tpu; nothing validated)")
        raise SystemExit(1)
    rng = np.random.default_rng(0)
    ok = True
    for name, B, H, Hkv, Dh, S in CASES + INFO_CASES:
        gating = (name, B, H, Hkv, Dh, S) in CASES
        q = jnp.asarray(rng.standard_normal((B, H, Dh)) * 0.3, jnp.bfloat16)
        k_bf = jnp.asarray(rng.standard_normal((B, Hkv, S, Dh)) * 0.3, jnp.float32)
        v_bf = jnp.asarray(rng.standard_normal((B, Hkv, S, Dh)) * 0.3, jnp.float32)
        k_i8, k_s = quantize_kv(k_bf)
        v_i8, v_s = quantize_kv(v_bf)
        valid = rng.random((B, S)) > 0.2
        valid[:, -1] = True
        mask = jnp.asarray(valid)
        scale = Dh ** -0.5

        kd = dequantize_kv(k_i8, k_s)
        vd = dequantize_kv(v_i8, v_s)
        want = np.asarray(_reference(q, kd, vd, mask, scale), dtype=np.float32)

        for kind in ("step", "chunk"):
            try:
                if kind == "step":
                    got = decode_attention(
                        q, k_i8, v_i8, mask, scale, k_scale=k_s, v_scale=v_s
                    )
                    got = np.asarray(got, dtype=np.float32)
                    ref = want
                else:
                    K = 4
                    qk = jnp.asarray(
                        rng.standard_normal((B, K, H, Dh)) * 0.3, jnp.bfloat16
                    )
                    maskk = jnp.broadcast_to(mask[:, None, :], (B, K, S))
                    got = chunk_decode_attention(
                        qk, k_i8, v_i8, maskk, scale, k_scale=k_s, v_scale=v_s
                    )
                    got = np.asarray(got, dtype=np.float32)
                    ref = np.stack(
                        [np.asarray(_reference(qk[:, i], kd, vd, mask, scale))
                         for i in range(K)], axis=1,
                    )
                err = float(np.max(np.abs(got - ref)))
                denom = float(np.max(np.abs(ref))) + 1e-9
                rel = err / denom
                good = rel < 5e-2  # bf16 q + f32-accum reorder tolerance
                if not good and gating:
                    ok = False
                tag = "OK" if good else "MISMATCH"
                if not gating:
                    tag = "info-" + tag
                print(f"  {name}/{kind:<6s} max|d|={err:.4f} rel={rel:.3e} "
                      f"{tag}")
            except Exception as exc:  # noqa: BLE001 — a probe reports, not crashes
                if gating:
                    ok = False
                print(f"  {name}/{kind:<6s} "
                      f"{'FAILED' if gating else 'info-FAILED'}: "
                      f"{type(exc).__name__}: {str(exc)[:200]}")
    print("int8-decode-probe OK" if ok else "int8-decode-probe FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
