#!/usr/bin/env python
"""Time the int8 decode-attention kernel alone, IN-LOOP, at the
benchmark cells' geometries.

Every device call pays a host dispatch latency, so per-call timing is
latency-floored and meaningless for an op that runs inside the decode
``lax.while_loop``.  Here ``decode_attention`` runs N times inside ONE
jitted ``fori_loop`` with a serializing data dependency, so the
reported per-iteration cost is the in-loop cost.

Geometries: 10 rows, Dh 128, a stacked int8 cache of two layers at
5,120 slots with the second attended (the layer scan's carry and its
prefetched index); each row 1,536 slots of left pad, then 2,300 live
(a decide call's rows on the 4096 prompt rung), handed over as a
:class:`LiveMask` made outside the loop as the engine makes it once a
step.

* ``dense``   Hkv 8, group 4  (Qwen3-8B)
* ``hybrid``  Hkv 30, group 1 (Olmo-Hybrid-7B's full-attention layers)
* ``sparse``  Hkv 8, group 8  (Solar-Open2's GQA layers)

For each: us a call with the rows live, us a call with no slot live (a
grid of empty steps: what a step outside a row's range costs), the live
blocks a call computes (``live_block_count``), us a live block with the
empty steps taken out, and the live blocks' K/V and scale bytes over
the call's time against the v5e's 819 GB/s.

Usage (on the TPU):  python scripts/microbench_decode_attention.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bcg_tpu.ops.decode_attention import (
    LiveMask,
    decode_attention,
    kernel_block,
    live_block_count,
    live_slots,
)

GEOMETRIES = {"dense": (8, 4), "hybrid": (30, 1), "sparse": (8, 8)}
B, DH, S, LAYERS = 10, 128, 5120, 2
PAD, LIVE = 1536, 2300
HBM_BYTES_PER_S = 819e9           # TPU v5e, Google Cloud's published peak


def loop_time(body, carry0, iters, repeats):
    """Best of ``repeats`` timings of ``iters`` sequential in-loop
    applications of ``body`` inside one jit; seconds per iteration."""

    @jax.jit
    def run(carry):
        return jax.lax.fori_loop(0, iters, body, carry)

    jax.block_until_ready(run(carry0))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run(carry0))
        best = min(best, time.perf_counter() - t0)
    return best / iters


def measure(name, hkv, group, iters, repeats, seed=0):
    rng = np.random.default_rng(seed)
    H = hkv * group
    cache = (LAYERS, B, hkv, S)
    k = jnp.asarray(rng.integers(-127, 128, cache + (DH,)), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, cache + (DH,)), jnp.int8)
    ks = jnp.asarray(10.0 ** rng.uniform(-3, -1, cache), jnp.float32)
    vs = jnp.asarray(10.0 ** rng.uniform(-3, -1, cache), jnp.float32)
    q0 = jnp.asarray(rng.standard_normal((B, H, DH)), jnp.bfloat16)
    slots = np.arange(S)
    live = np.broadcast_to((slots >= PAD) & (slots < PAD + LIVE), (B, S))
    layer = jnp.int32(LAYERS - 1)

    def timed(mask):
        lm = LiveMask(jnp.asarray(mask), live_slots(jnp.asarray(mask)))

        def body(i, carry):
            acc, q = carry
            out = decode_attention(q, k, v, lm, DH ** -0.5, k_scale=ks,
                                   v_scale=vs, layer=layer)
            acc = acc + out.astype(jnp.float32).mean()
            return acc, q + (acc * 1e-20).astype(q.dtype)

        return loop_time(body, (jnp.float32(0), q0), iters, repeats)

    t_live = timed(live)
    t_dead = timed(np.zeros((B, S), bool))
    block = kernel_block(hkv, DH)
    grid = B * (S // block)
    first, last = live_slots(live, xp=np)
    blocks = live_block_count(first, last, block)
    per_block = (t_live - t_dead * (grid - blocks) / grid) / blocks
    nbytes = blocks * hkv * block * (2 * DH + 2 * 4)
    return {
        "geometry": name, "hkv": hkv, "group": group, "block": block,
        "grid_steps": grid, "live_blocks": blocks,
        "us_per_call": t_live * 1e6,
        "us_per_call_no_slot_live": t_dead * 1e6,
        "us_per_empty_step": t_dead / grid * 1e6,
        "us_per_live_block": per_block * 1e6,
        "us_per_live_block_and_head": per_block / hkv * 1e6,
        "hbm_roof_pct": 100.0 * nbytes / HBM_BYTES_PER_S / t_live,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--geometry", choices=sorted(GEOMETRIES), action="append")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; B={B} Dh={DH} S={S} "
          f"pad={PAD} live={LIVE}; {args.iters} in-loop iterations, best "
          f"of {args.repeats}")
    for name in args.geometry or list(GEOMETRIES):
        row = measure(name, *GEOMETRIES[name], args.iters, args.repeats)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
