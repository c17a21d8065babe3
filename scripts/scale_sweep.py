#!/usr/bin/env python
"""One-agent-per-chip scale sweep through the REAL serving stack.

BASELINE config 4's shape ("Scale sweep: 16/32/64 agents, one-agent-per-
chip on v5e-64"): N agents play a full Byzantine Consensus Game through
``BCGSimulation`` -> ``JaxEngine(dp=N)`` — every decision/vote batch is
one [N, ...] device batch SHARDED one-row-per-chip over the mesh's `dp`
axis (engine._put_batch), and the broadcast/receive phase is one
``all_gather`` over the same mesh (--spmd-exchange path).  The reference
runs its scale sweep by queueing agents through one vLLM server
(vllm_agent.py batching); here agent parallelism IS the mesh layout.

Since the sweep tier landed this script is a THIN WRAPPER over a
one-job :mod:`bcg_tpu.sweep` run (the game goes through the shared
serving scheduler as a tenant, and the sweep manifest — fleet-identity-
stamped like every JSONL sink — lands in --sweep-dir); the emitted JSON
line is byte-compatible with the pre-wrapper schema, pinned by
``tests/test_scale_sweep.py``.

Hermetic run on a virtual device mesh (no TPU pod needed):

    XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        python scripts/scale_sweep.py --agents 16 --rounds 4

Emits ONE JSON line: {agents, devices, dp, rounds, rounds_per_sec,
decisions_per_sec, dp_batches, dp_bypasses, sp_bypasses, consensus}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=16,
                    help="total agents; byzantine count is agents//4")
    ap.add_argument("--rounds", type=int, default=4, help="max game rounds")
    ap.add_argument("--model", default="bcg-tpu/tiny-test")
    ap.add_argument("--max-model-len", type=int, default=512)
    ap.add_argument("--decide-tokens", type=int, default=48)
    ap.add_argument("--vote-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sweep-dir", default=None,
                    help="sweep dir for the manifest/events (default: a "
                    "fresh temp dir — this script is a metrics probe)")
    args = ap.parse_args()

    # A virtual-device request (XLA_FLAGS) means the CPU backend.
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    n_dev = len(jax.devices())
    dp = next(d for d in range(min(args.agents, n_dev), 0, -1)
              if args.agents % d == 0)

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bcg_tpu.sweep import run_sweep

    n_byz = args.agents // 4
    spec = {
        "name": f"scale-{args.agents}",
        "base": {
            "agents": args.agents,
            "byzantine": n_byz,
            "max_rounds": args.rounds,
            "seed": args.seed,
            "backend": "jax",
            "model": args.model,
            "max_model_len": args.max_model_len,
            "data_parallel_size": dp,
            "spmd_exchange": True,
            "decide_tokens": args.decide_tokens,
            "vote_tokens": args.vote_tokens,
        },
        "axes": {},
    }
    out_dir = args.sweep_dir or tempfile.mkdtemp(prefix="bcg-scale-sweep-")
    summary = run_sweep(spec, out_dir, max_concurrent=1, linger_ms=0)
    if summary["failed"]:
        print(json.dumps(summary, default=str), file=sys.stderr)
        return 1
    if summary["results"]:
        job = summary["results"][0]
    else:
        # Resume path: the job already completed in this --sweep-dir on
        # a previous invocation — rebuild the row from its persisted
        # manifest record instead of failing an all-skipped rerun.
        from bcg_tpu.sweep import completed_job_ids, expand

        jid = expand(spec)[0].job_id
        job = completed_job_ids(out_dir).get(jid)
        if job is None:
            print(json.dumps(summary, default=str), file=sys.stderr)
            return 1
        print(
            f"scale_sweep: job {jid} already completed in {out_dir}; "
            "reporting the recorded result (use a fresh --sweep-dir to "
            "re-measure)",
            file=sys.stderr,
        )
    eng = job.get("engine") or {}
    # Legacy schema — byte-compatible with the pre-sweep-tier script
    # (tests/test_scale_sweep.py pins every key).
    row = {
        "agents": args.agents,
        "devices": n_dev,
        "dp": dp,
        "model": args.model,
        "rounds": job.get("rounds", 0),
        "rounds_per_sec": job.get("rounds_per_sec", 0.0),
        "decisions_per_sec": job.get("decisions_per_sec", 0.0),
        "dp_batches": eng.get("dp_batches"),
        "dp_bypasses": eng.get("dp_bypasses"),
        "sp_bypasses": eng.get("sp_bypasses"),
        "spmd_mesh_dp": job.get("spmd_mesh_dp"),
        "consensus": bool(job.get("converged")),
    }
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
