#!/usr/bin/env python
"""Lower and validate the Pallas flash-prefill kernel on the TPU.

bench_14b's first attempt crashed in its FIRST prefill compile (under a
compiler since replaced) with the W4 kernel already disabled, leaving
two suspects: the int8 decode kernels at GQA group 5 (now excluded by
the engine's group guard) and this flash kernel at 14B dims (H=40 —
untested on hardware; 1B/8B ran H=16/32).  This probe lowers the kernel
at the chunked-prefill shapes each preset actually serves and checks it
against the pure-JAX blockwise reference, so the crasher is identified
by name instead of inferred from a failed 90-minute bench.

Fails off-TPU (nothing would be validated).  Prints
"flash-prefill-probe OK" when all cases pass.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from bcg_tpu.ops.attention import blockwise_attention, flash_attention

# (name, B, T, S, H, Hkv, Dh): T = chunk length (prefill_chunk for the
# large class), S = T + cached history the chunk attends.
CASES = [
    ("1b-full-prefill", 4, 1024, 1024, 16, 8, 128),
    ("8b-chunk", 10, 512, 2048, 32, 8, 128),
    ("14b-chunk", 10, 512, 2048, 40, 8, 128),
    ("14b-first-chunk", 10, 512, 512, 40, 8, 128),
]


def main() -> None:
    backend = jax.default_backend()
    print("backend:", backend)
    if backend != "tpu":
        print("flash-prefill-probe FAILED: accelerator unavailable "
              "(backend is not tpu; nothing validated)")
        raise SystemExit(1)
    rng = np.random.default_rng(0)
    ok = True
    for name, B, T, S, H, Hkv, Dh in CASES:
        q = jnp.asarray(rng.standard_normal((B, T, H, Dh)) * 0.3, jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)) * 0.3, jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)) * 0.3, jnp.bfloat16)
        # Causal-with-history mask plus some padding holes, like the
        # chunk path builds (transformer.prefill_chunk_at).
        hist = S - T
        causal = np.tril(np.ones((T, T), bool))
        mask_np = np.concatenate(
            [np.ones((T, hist), bool), causal], axis=1
        )[None].repeat(B, axis=0)
        mask_np[:, :, : max(hist // 8, 0)] = False  # left-pad holes
        mask = jnp.asarray(mask_np)
        scale = Dh ** -0.5
        try:
            got = np.asarray(
                flash_attention(q, k, v, mask, scale), dtype=np.float32
            )
            want = np.asarray(
                blockwise_attention(q, k, v, mask, scale), dtype=np.float32
            )
            err = float(np.max(np.abs(got - want)))
            denom = float(np.max(np.abs(want))) + 1e-9
            rel = err / denom
            good = rel < 5e-2
            if not good:
                ok = False
            print(f"  {name:<18s} max|d|={err:.4f} rel={rel:.3e} "
                  f"{'OK' if good else 'MISMATCH'}")
        except Exception as exc:  # noqa: BLE001 — a probe reports, not crashes
            ok = False
            print(f"  {name:<18s} FAILED: "
                  f"{type(exc).__name__}: {str(exc)[:200]}")
    print("flash-prefill-probe OK" if ok else "flash-prefill-probe FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
