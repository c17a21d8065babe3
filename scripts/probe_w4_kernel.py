#!/usr/bin/env python
"""Lower and validate the W4A16 Pallas kernel on the attached TPU.

The int4 decode kernel (ops/w4_matmul.py) is interpret-mode tested on
CPU, but Mosaic lowering rules differ on real hardware (round-2/3
lessons: scale blockspecs, (1,1,1) VMEM blocks, bool SMEM).  This probe
runs the kernel at decode shapes — tiny, bench-1b, and 14B w_down
dims — and checks each against the XLA dequant fallback, so a lowering
problem surfaces as a named failure here instead of a crash deep inside
the queued 14B bench.

Prints one line per case and "w4-kernel-probe OK" when all pass.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bcg_tpu.models.quantize import dequantize_int4, quantize_weight_int4
from bcg_tpu.ops.w4_matmul import w4a16_matmul, w4a16_supported


CASES = [
    # (rows, in_dim, out_dim) at decode row counts
    ("tiny", 8, 256, 512),
    ("1b-ffn", 10, 2048, 6144),
    ("14b-qkv", 10, 5120, 8192),
    ("14b-wdown", 10, 17408, 5120),
    ("14b-wdown-retry-rows", 160, 17408, 5120),
]


def main() -> None:
    backend = jax.default_backend()
    print("backend:", backend)
    if backend != "tpu":
        # Off-TPU the kernel falls back to the very XLA path used as the
        # reference below — "OK" would be vacuous.
        print("w4-kernel-probe FAILED: accelerator unavailable "
              "(backend is not tpu; nothing validated)")
        raise SystemExit(1)
    rng = np.random.default_rng(0)
    ok = True
    for name, m, din, dout in CASES:
        w = jnp.asarray(rng.standard_normal((din, dout)) * 0.02, jnp.bfloat16)
        qw = quantize_weight_int4(w)
        x = jnp.asarray(rng.standard_normal((m, din)) * 0.5, jnp.bfloat16)
        # The kernel silently falls back to the XLA dequant path (the
        # very reference below) for unsupported shapes — "OK" would
        # then be vacuous, so unsupported cases are hard failures here.
        if not w4a16_supported(
            (m, din), qw["q4"].shape, qw["gscale"].shape
        ):
            ok = False
            print(f"  {name:<22s} UNSUPPORTED shape (kernel would fall "
                  f"back; probe would compare XLA to XLA)")
            continue
        try:
            got = np.asarray(w4a16_matmul(x, qw["q4"], qw["gscale"]))
            want = np.asarray(
                (x.astype(jnp.bfloat16) @ dequantize_int4(qw)).astype(jnp.float32)
            )
            err = float(np.max(np.abs(got - want)))
            rel = err / (float(np.max(np.abs(want))) + 1e-9)
            # `not (rel < tol)` so NaN (from a miscompile) fails too.
            good = rel < 2e-2
            status = "OK" if good else f"MISMATCH rel={rel:.3e}"
            if not good:
                ok = False
            print(f"  {name:<22s} [{m}x{din}]@[{din}x{dout}]  max|d|={err:.4f}  {status}")
        except Exception as exc:  # noqa: BLE001 — a probe reports, not crashes
            ok = False
            print(f"  {name:<22s} FAILED: {type(exc).__name__}: {str(exc)[:200]}")
    print("w4-kernel-probe OK" if ok else "w4-kernel-probe FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
