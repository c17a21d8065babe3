#!/usr/bin/env python
"""Hermetic perf-regression gate: counter-derived metrics vs banded
baselines, CPU-only.

BENCH_r02-r05 lost an entire benchmark trajectory to accelerator-attach
outages — wall-clock on flaky hardware cannot gate anything.  This gate
re-derives the perf story from COUNTERS, which are exact on any
backend:

* ``engine`` scenario — a tiny real ``JaxEngine`` (``bcg-tpu/
  tiny-test``) runs the guided-JSON decision benchmark twice (plain and
  speculative): device decode iterations per decision, the speculative
  step-reduction ratio, the draft acceptance rate, and ZERO
  steady-state retraces (counter deltas over a warm repeat call).
* ``serve`` scenario — a scripted FakeEngine serving run (16 concurrent
  requests against one scheduler bucket, spec mirror on): completion
  fraction, engine errors, batch-merge rows per dispatch, and the
  mirrored draft acceptance rate.
* ``hlo`` scenario — delegates to ``scripts/hlo_census.py``'s drift
  check (kernel counts per jit entry vs ``hlo_baseline.json``) and
  gates on zero findings.

Every measured metric must have a justified entry in
``perf_baseline.json`` (same load-bearing idiom as
``lint_baseline.json``: an unbaselined metric is itself a failure, so
deleting an entry RESURFACES its check rather than silencing it; a
baseline entry the scenarios no longer produce is a stale-entry
failure).  Bounds are tolerance-banded (``op``: ``min``/``max``/
``range`` with ``tol_rel``/``tol_abs``); a regression failure names the
metric, the measured value, the violated bound, and the entry's reason.

Exit status: 0 = green; 2 = regression/drift (composes with
``set -o pipefail`` harnesses); 1 = usage error.  Tier-1 runs the same
comparisons in-process (``tests/test_perf_gate.py``).

Usage:
    python scripts/perf_gate.py                    # all scenarios
    python scripts/perf_gate.py --scenarios serve,engine
    python scripts/perf_gate.py --update-baseline  # regenerate (keeps reasons)
    python scripts/perf_gate.py --inject-regression spec-off   # self-test
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# "alerts" stays LAST: its oracle arm resets the counter registry to
# kill absolute-gauge leftovers (headroom, heartbeats, stragglers)
# that earlier scenarios legitimately leave behind.
SCENARIOS = ("serve", "engine", "paged", "sampler", "int4", "consensus",
             "fleet", "hostsync", "compile", "sweep", "chaos",
             "scenarios", "hlo", "alerts")
REGRESSIONS = ("none", "spec-off", "fail-rows", "events-off",
               "straggler-off", "hostsync-off", "compile-off",
               "fairness-off", "chaos-off", "scenarios-off",
               "alerts-off")

DECISION = {
    "type": "object",
    "properties": {
        "internal_strategy": {"type": "string", "minLength": 1, "maxLength": 25},
        "value": {"type": "integer", "minimum": 0, "maximum": 50},
        "public_reasoning": {"type": "string", "minLength": 1, "maxLength": 25},
    },
    "required": ["internal_strategy", "value", "public_reasoning"],
    "additionalProperties": False,
}
VOTE = {
    "type": "object",
    "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
    "required": ["decision"],
    "additionalProperties": False,
}


def baseline_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, "perf_baseline.json")


def _force_cpu() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")


# ------------------------------------------------------------- scenarios
def run_serve_scenario(inject: str = "none") -> Dict[str, float]:
    """Scripted FakeEngine serving run: 2 waves x 8 threads x 2-row
    guided requests against a 16-row bucket with a generous linger, so
    full-bucket merges dominate regardless of host load.  The spec
    mirror (BCG_TPU_SPEC=1) makes the hermetic run carry a realistic
    draft-acceptance profile."""
    from bcg_tpu.engine.fake import FakeEngine
    from bcg_tpu.obs import counters as obs_counters
    from bcg_tpu.serve.scheduler import Scheduler

    # Save/restore needs the RAW value (None vs ""), not the parsed
    # bool — the registry accessors cannot round-trip "was unset".
    prior_spec = os.environ.get("BCG_TPU_SPEC")  # lint: ignore[BCG-ENV-RAW]
    os.environ["BCG_TPU_SPEC"] = "0" if inject == "spec-off" else "1"
    try:
        engine = FakeEngine(
            seed=0, policy="consensus",
            fail_first_n_calls=(10**6 if inject == "fail-rows" else 0),
        )
        sched = Scheduler(
            engine, linger_ms=400, bucket_rows=16,
            max_queue_rows=4096, deadline_ms=0, strict_admission=False,
        )
        before = obs_counters.snapshot()
        payload = [
            ("agent system prompt",
             "Round 2. agent_1 value: 17. agent_2 value: 17. "
             "Your current value: 17. Decide.",
             DECISION),
        ] * 2
        errors: List[BaseException] = []
        row_counts = {"rows": 0, "error_rows": 0}
        count_lock = threading.Lock()

        def one_request():
            try:
                out = sched.submit_and_wait(
                    ("json",), list(payload), [0.0] * 2, [64] * 2
                )
                bad = sum(
                    1 for r in out if not isinstance(r, dict) or "error" in r
                )
                with count_lock:
                    row_counts["rows"] += len(out)
                    row_counts["error_rows"] += bad
            except BaseException as e:  # collected, raised below
                errors.append(e)

        for _wave in range(2):
            threads = [
                threading.Thread(target=one_request) for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        snap = sched.snapshot()
        sched.close()
        moved = obs_counters.delta(before)
    finally:
        if prior_spec is None:
            os.environ.pop("BCG_TPU_SPEC", None)
        else:
            os.environ["BCG_TPU_SPEC"] = prior_spec
    if errors:
        raise errors[0]
    drafted = moved.get("engine.spec.drafted", 0)
    accepted = moved.get("engine.spec.accepted", 0)
    dispatches = max(1, snap["dispatches"])
    return {
        "serve.completed_fraction": snap["completed"] / max(1, snap["submitted"]),
        "serve.engine_errors": snap["engine_errors"],
        "serve.error_row_fraction": (
            row_counts["error_rows"] / max(1, row_counts["rows"])
        ),
        "serve.rows_per_dispatch": snap["dispatched_rows"] / dispatches,
        "serve.spec_acceptance_rate": accepted / drafted if drafted else 0.0,
    }


def run_engine_scenario(inject: str = "none") -> Dict[str, float]:
    """Tiny real-engine decision benchmark, plain vs speculative, at
    temperature 0 (fully deterministic: fixed weights, fixed prompts) —
    the counter-derived core of what BENCH measures on hardware."""
    _force_cpu()
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine
    from bcg_tpu.obs import counters as obs_counters

    prompts = [
        ("honest agent system prompt", "Round 3: propose a value", DECISION),
        ("byzantine agent system prompt", "Round 3: vote now", VOTE),
        ("honest agent system prompt", "Round 4: propose a value", DECISION),
    ]

    def cfg(**kw):
        return EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048, **kw,
        )

    std = JaxEngine(cfg())
    spec = JaxEngine(cfg(spec_decode=(inject != "spec-off")))
    try:
        r_std = std.batch_generate_json(prompts, temperature=0.0, max_tokens=80)
        steps_std = std.total_decode_steps
        before = obs_counters.snapshot()
        r_spec = spec.batch_generate_json(prompts, temperature=0.0, max_tokens=80)
        steps_spec = spec.total_decode_steps
        moved = obs_counters.delta(before)
        # Steady state: an identical-shape repeat call may compile
        # NOTHING new — the retrace counters must not move.
        before_warm = obs_counters.snapshot()
        spec.batch_generate_json(prompts, temperature=0.0, max_tokens=80)
        warm_moved = obs_counters.delta(before_warm)
    finally:
        std.shutdown()
        spec.shutdown()
    bad = sum(1 for r in r_std + r_spec if not isinstance(r, dict) or "error" in r)
    drafted = moved.get("engine.spec.drafted", 0)
    accepted = moved.get("engine.spec.accepted", 0)
    retraces = sum(
        v for k, v in warm_moved.items() if k.startswith("engine.retrace.")
    ) + sum(
        v for k, v in warm_moved.items() if k.startswith("engine.compile.")
    )
    decisions = len(prompts)
    return {
        "engine.decode_steps_per_decision": steps_spec / decisions,
        "engine.spec_step_reduction": 1.0 - steps_spec / max(1, steps_std),
        "engine.spec_acceptance_rate": accepted / drafted if drafted else 0.0,
        "engine.steady_state_retraces": retraces,
        "engine.error_rows": bad,
    }


def run_paged_scenario(inject: str = "none") -> Dict[str, float]:
    """Block-paged KV cache (engine/paged_kv.py) gates, all hermetic:

    * ``positions_real_per_agent_slope`` — per-game real prefill
      positions per agent at N=8 over N=2 (fresh engine per N, shared
      system prompt + per-agent tail).  Radix sharing prefills the
      shared prefix ONCE per game, so the ratio must stay well under 1
      (the superlinear-sharing acceptance assertion);
      ``positions_real_monotone`` is 1.0 iff strictly decreasing over
      N in {2, 4, 8}.
    * ``prefix_hit_rate`` — radix hit rate after a second round on a
      persistent engine (grown history extends round 1's chain).
    * ``greedy_parity_mismatches`` — paged vs dense greedy outputs on
      the same prompts (must be 0: token-identical by construction).
    * ``row_cap_gain`` — serve admission cap (derive_row_cap) of a
      paged engine over the dense worst-case provisioner at the SAME
      synthetic HBM budget; > 1 because the pool unifies the dense
      path's separate prefix reserve and needs no ALIGN_S padding.
    """
    _force_cpu()
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine
    from bcg_tpu.obs import counters as obs_counters
    from bcg_tpu.serve.scheduler import derive_row_cap

    def cfg(**kw):
        return EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048, **kw,
        )

    shared_sys = (
        "You are an agent in a Byzantine consensus game. The rules are "
        "long and shared by every participant: propose integer values, "
        "exchange them with peers, and vote to stop once values converge "
        "within the consensus threshold. " * 3
    )
    per_agent: Dict[int, float] = {}
    for n_agents in (2, 4, 8):
        eng = JaxEngine(cfg(paged_kv=True))
        before = obs_counters.value("engine.prefill.positions_real")
        eng.batch_generate_json(
            [(shared_sys, f"You are agent_{i}. Round 1. Peers said 17. "
              "Decide.", VOTE) for i in range(n_agents)],
            temperature=0.0, max_tokens=24,
        )
        moved = obs_counters.value("engine.prefill.positions_real") - before
        per_agent[n_agents] = moved / n_agents
        eng.shutdown()
    monotone = float(per_agent[2] > per_agent[4] > per_agent[8])

    # Parity + hit rate: two rounds on ONE paged engine vs a dense twin.
    prompts = [
        (shared_sys + f" You are agent_{i}.", "Round 1. Decide.", DECISION)
        for i in range(3)
    ]
    dense = JaxEngine(cfg())
    paged = JaxEngine(cfg(paged_kv=True))
    try:
        mismatches = 0
        round1_batch = round1_dense = None
        for round_no in (1, 2):
            batch = [
                (s, f"Round {round_no}. Peers said 17. Decide.", sch)
                for s, _, sch in prompts
            ]
            r_d = dense.batch_generate_json(batch, temperature=0.0,
                                            max_tokens=48)
            r_p = paged.batch_generate_json(batch, temperature=0.0,
                                            max_tokens=48)
            mismatches += sum(1 for a, b in zip(r_d, r_p) if a != b)
            if round_no == 1:
                round1_batch, round1_dense = batch, r_d
        pool = paged.kv_pool_stats() or {}
        hit_rate = pool.get("prefix_hit_rate") or 0.0
    finally:
        dense.shutdown()
        paged.shutdown()

    # Impl parity: the fused Pallas kernel (interpret mode on this CPU
    # host) must reproduce the dense greedy output on the same batch —
    # the hermetic stand-in for the hardware kernel's token-identity
    # claim, gated 0 exact like the gather path's parity above.
    pallas = JaxEngine(cfg(paged_kv=True, paged_kv_impl="pallas"))
    try:
        r_k = pallas.batch_generate_json(round1_batch, temperature=0.0,
                                         max_tokens=48)
    finally:
        pallas.shutdown()
    pallas_mismatches = sum(
        1 for a, b in zip(round1_dense, r_k) if a != b
    )

    # Admission gain at one synthetic HBM budget.  The dense reserve
    # uses the boot formula's fraction WITHOUT its 256 MB large-model
    # floor (which would zero the dense budget at test-sized synthetic
    # limits and overstate the gain); the paged pool gets the same
    # budget with no separate reserve — the structural win under test.
    limit = 32 << 20
    dense = JaxEngine(cfg())
    dense._mem_limit = limit
    free = (dense.config.hbm_utilization * limit
            - dense._param_bytes_per_device)
    dense._prefix_budget = max(0, int(free * 0.25))
    dense_cap = derive_row_cap(dense) or 1
    # Size the equivalent pool at the block size the paged engine will
    # actually use (the config default) — a hardcoded 16 would silently
    # desync the comparison if the default ever moves (e.g. to the
    # Pallas kernel's 128).
    bs_blk = EngineConfig().kv_block_size
    block_bytes = bs_blk * dense._kv_slot_bytes * dense.spec.num_layers
    usable = max(64, int(free // block_bytes))
    dense.shutdown()
    paged = JaxEngine(cfg(paged_kv=True, kv_pool_blocks=usable + 1))
    paged_cap = derive_row_cap(paged) or 1
    paged.shutdown()

    if inject == "fail-rows":
        mismatches += 1  # self-test hook: provoke the parity gate
    return {
        "paged.positions_real_per_agent_slope": per_agent[8] / per_agent[2],
        "paged.positions_real_monotone": monotone,
        "paged.prefix_hit_rate": hit_rate,
        "paged.greedy_parity_mismatches": float(mismatches),
        "paged.pallas_parity_mismatches": float(pallas_mismatches),
        "paged.row_cap_gain": paged_cap / dense_cap,
    }


def run_sampler_scenario(inject: str = "none") -> Dict[str, float]:
    """Fused guided-sampling kernel (ops/guided_sampler.py, interpret
    mode on this CPU host — the same program hardware lowers) against
    the XLA masked-sampler reference, across ALL THREE decode-loop
    families on the greedy decision benchmark:

    * ``parity_mismatches`` — fused vs xla outputs per family (must be
      0 EXACT: greedy rows are token-identical by construction; the
      acceptance criterion's hermetic stand-in for the hardware
      kernel's claim).
    * ``fused_kernel_invocations`` — the fused engines' total kernel
      invocation count (one program per decode iteration); floored > 0
      so the parity gate can never pass vacuously with the kernel
      silently disengaged.
    """
    _force_cpu()
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    prompts = [
        ("honest agent system prompt", "Round 3: propose a value", DECISION),
        ("byzantine agent system prompt", "Round 3: vote now", VOTE),
    ]

    def cfg(**kw):
        return EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048, **kw,
        )

    mismatches = 0
    fused_calls = 0
    for family_kw in ({}, {"decode_fast_forward": True},
                      {"spec_decode": True}):
        ref = JaxEngine(cfg(**family_kw))
        fused = JaxEngine(cfg(fused_sampler="pallas", **family_kw))
        try:
            r_ref = ref.batch_generate_json(prompts, temperature=0.0,
                                            max_tokens=64)
            r_fus = fused.batch_generate_json(prompts, temperature=0.0,
                                              max_tokens=64)
            mismatches += sum(1 for a, b in zip(r_ref, r_fus) if a != b)
            fused_calls += fused.sampler_stats()["fused_calls"]
        finally:
            ref.shutdown()
            fused.shutdown()
    if inject == "fail-rows":
        mismatches += 1  # self-test hook: provoke the parity gate
    return {
        "sampler.parity_mismatches": float(mismatches),
        "sampler.fused_kernel_invocations": float(fused_calls),
    }


def run_int4_scenario(inject: str = "none") -> Dict[str, float]:
    """Packed-int4 KV cache gates, all hermetic:

    * ``row_cap_gain`` — ``cap_for``-derived dense admission cap of an
      int4 engine over its int8 twin at the SAME synthetic HBM budget
      (min-banded >= 1.8: the packed slot is exactly half the int8
      slot — 2(Dh+4) vs Dh+4 bytes per kv head — so the cap doubles up
      to integer flooring).
    * ``pool_blocks_gain`` — paged-pool auto-sizing at the same
      synthetic budget (the serve-admission form of the same claim:
      admission caps come out measurably higher).
    * ``paged_parity_mismatches`` — int4 paged (fused kernel, interpret
      mode) vs int4 dense greedy outputs (0 exact: identical
      quantization, block paging is bit-preserving).
    * ``error_rows`` — every int4 decision/vote row parses as valid
      guided JSON (the decision benchmark staying within the
      established quantization tolerance; token-level drift vs bf16 is
      tier-1's tolerance test, not a gate band).
    """
    _force_cpu()
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    def cfg(**kw):
        return EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048, **kw,
        )

    limit = 32 << 20
    caps = {}
    blocks = {}
    for dtype in ("int8", "int4"):
        eng = JaxEngine(cfg(kv_cache_dtype=dtype))
        eng._mem_limit = limit
        free = (eng.config.hbm_utilization * limit
                - eng._param_bytes_per_device)
        eng._prefix_budget = max(0, int(free * 0.25))
        caps[dtype] = eng.cap_for(256) or 1
        blocks[dtype] = eng._auto_pool_blocks(eng.config.kv_block_size)
        eng.shutdown()

    prompts = [
        ("honest agent system prompt", "Round 3: propose a value", DECISION),
        ("byzantine agent system prompt", "Round 3: vote now", VOTE),
    ]
    dense = JaxEngine(cfg(kv_cache_dtype="int4"))
    paged = JaxEngine(cfg(kv_cache_dtype="int4", paged_kv=True,
                          paged_kv_impl="pallas"))
    try:
        r_d = dense.batch_generate_json(prompts, temperature=0.0,
                                        max_tokens=64)
        r_p = paged.batch_generate_json(prompts, temperature=0.0,
                                        max_tokens=64)
    finally:
        dense.shutdown()
        paged.shutdown()
    mismatches = sum(1 for a, b in zip(r_d, r_p) if a != b)
    bad = sum(1 for r in r_d + r_p if not isinstance(r, dict) or "error" in r)
    if inject == "fail-rows":
        mismatches += 1  # self-test hook
    return {
        "int4.row_cap_gain": caps["int4"] / caps["int8"],
        "int4.pool_blocks_gain": blocks["int4"] / blocks["int8"],
        "int4.paged_parity_mismatches": float(mismatches),
        "int4.error_rows": float(bad),
    }


# Game-event types every completed game must carry (the manifest is
# per-file, checked separately).
_REQUIRED_GAME_EVENTS = (
    "game_start", "round_start", "decision", "deliveries", "vote",
    "round_end", "game_end",
)


def run_consensus_scenario(inject: str = "none") -> Dict[str, float]:
    """Hermetic FakeEngine consensus games with game-event telemetry on
    (BCG_TPU_GAME_EVENTS to a temp file): three seeded games — two
    fully-connected, one ring (topology-masked deliveries) — gating

    * ``convergence_rate`` / ``rounds_to_consensus_mean`` — the paper's
      outcome metrics, deterministic under the FakeEngine consensus
      policy's seeded dynamics;
    * ``event_schema_completeness`` — fraction of required event types
      present per game (manifest checked per file): a silently dropped
      emission site shows up as < 1 here, not as a mysteriously thin
      sweep report later;
    * ``events_dropped`` — the bounded sink must not shed records at
      this scale;
    * ``histogram_quantile_sanity`` — the game.round_ms registry
      histogram's bucket-derived quantiles are ordered (p50<=p95<=p99),
      non-negative, and within the declared bounds.

    ``events-off`` injection unsets the flag — the gate must then name
    the schema-completeness and convergence metrics rather than pass
    vacuously."""
    import dataclasses
    import tempfile

    from bcg_tpu.config import (
        BCGConfig, EngineConfig, GameConfig, MetricsConfig, NetworkConfig,
    )
    from bcg_tpu.obs import counters as obs_counters, game_events
    from bcg_tpu.runtime.orchestrator import BCGSimulation

    events_path = os.path.join(
        tempfile.mkdtemp(prefix="bcg-perf-gate-"), "game_events.jsonl"
    )
    # Save/restore the RAW value (None vs "") — registry accessors
    # cannot round-trip "was unset".
    prior = os.environ.get("BCG_TPU_GAME_EVENTS")  # lint: ignore[BCG-ENV-RAW]
    if inject == "events-off":
        os.environ.pop("BCG_TPU_GAME_EVENTS", None)
    else:
        os.environ["BCG_TPU_GAME_EVENTS"] = events_path
    game_events.reset_sink()
    drops_before = obs_counters.value("game.events_dropped")
    hist_before = obs_counters.value("game.round_ms.count")
    try:
        games = [
            dict(seed=7, topology="fully_connected"),
            dict(seed=8, topology="fully_connected"),
            dict(seed=3, topology="ring"),
        ]
        for spec in games:
            cfg = dataclasses.replace(
                BCGConfig(),
                game=GameConfig(num_honest=4, num_byzantine=1,
                                max_rounds=6, seed=spec["seed"]),
                network=NetworkConfig(topology_type=spec["topology"]),
                engine=EngineConfig(backend="fake"),
                metrics=MetricsConfig(save_results=False),
                verbose=False,
            )
            sim = BCGSimulation(config=cfg)
            try:
                sim.run()
            finally:
                sim.close()
        game_events.reset_sink()  # drain + close so the file is complete
    finally:
        if prior is None:
            os.environ.pop("BCG_TPU_GAME_EVENTS", None)
        else:
            os.environ["BCG_TPU_GAME_EVENTS"] = prior
        game_events.reset_sink()

    # Outcome + schema metrics come from the FILE (what a sweep would
    # actually consume), not in-process state.
    per_game: Dict[str, Dict] = {}
    have_manifest = False
    if os.path.exists(events_path):
        with open(events_path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "manifest":
                    have_manifest = rec.get("schema_version") is not None
                    continue
                gid = rec.get("game")
                if gid is None:
                    continue
                g = per_game.setdefault(
                    gid, {"events": set(), "converged": False, "rounds": 0}
                )
                g["events"].add(rec["event"])
                if rec["event"] == "game_end":
                    g["converged"] = bool(rec.get("converged"))
                    g["rounds"] = int(rec.get("rounds", 0))
    n_games = len(per_game)
    converged = [g for g in per_game.values() if g["converged"]]
    completeness = (
        sum(
            sum(1 for e in _REQUIRED_GAME_EVENTS if e in g["events"])
            / len(_REQUIRED_GAME_EVENTS)
            for g in per_game.values()
        ) / n_games
        if n_games else 0.0
    ) * (1.0 if have_manifest or not n_games else 0.0)
    rounds_mean = (
        sum(g["rounds"] for g in converged) / len(converged)
        if converged else 0.0
    )

    try:
        hist = obs_counters.histogram("game.round_ms")  # read access
    except KeyError:
        hist = None  # recorder never ran (events-off injection)
    if hist is not None and hist.count > hist_before:
        q = hist.quantiles()
        sane = float(
            0.0 <= q["p50"] <= q["p95"] <= q["p99"] <= hist.bounds[-1]
        )
    else:
        sane = 0.0
    return {
        "consensus.convergence_rate": (
            len(converged) / n_games if n_games else 0.0
        ),
        "consensus.rounds_to_consensus_mean": rounds_mean,
        "consensus.event_schema_completeness": completeness,
        "consensus.events_dropped": float(
            obs_counters.value("game.events_dropped") - drops_before
        ),
        "consensus.histogram_quantile_sanity": sane,
    }


def run_fleet_scenario(inject: str = "none") -> Dict[str, float]:
    """Distributed observability plane (bcg_tpu/obs/fleet.py +
    scripts/fleet_report.py) on a REAL 2-process CPU cluster — the
    tests/_multihost_worker.py coordinator-handshake idiom, but each
    rank plays a FakeEngine consensus game with metric shards + game
    events on, and the last rank runs with a FROZEN fleet watermark
    (fleet.freeze_watermark, the documented chaos hook).  Gated:

    * ``shard_completeness`` — every rank's shard file present for the
      shared run id;
    * ``merged_p50_rel_err`` / ``merged_p95_rel_err`` — fleet_report's
      bucket-wise merge of the ranks' deterministic ``fleet.probe_ms``
      histograms vs a single-stream oracle bucketing the union of the
      same values in-process;
    * ``counter_merge_error`` — the merged ``fleet.probe`` counter vs
      the exact cross-rank sum the workers incremented;
    * ``events_dropped`` — the bounded event sinks shed nothing at this
      scale (summed across ranks from the merged shards);
    * ``straggler_flagged`` — the HEALTHY rank's runtime straggler pass
      (fleet.stragglers gauge in its final shard flush) flagged the
      frozen rank.  ``--inject-regression straggler-off`` disables
      detection (BCG_TPU_FLEET_STRAGGLER_FACTOR=0): the flag stays 0
      and the gate must fail naming this metric — detection can never
      pass vacuously."""
    import importlib.util
    import socket
    import subprocess
    import tempfile
    import uuid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(root, "tests", "_fleet_worker.py")
    wspec = importlib.util.spec_from_file_location("_fleet_worker", worker)
    wmod = importlib.util.module_from_spec(wspec)
    wspec.loader.exec_module(wmod)  # formulas only; main() is guarded

    tmp = tempfile.mkdtemp(prefix="bcg-fleet-gate-")
    shard_dir = os.path.join(tmp, "shards")
    run = uuid.uuid4().hex[:12]
    nproc = 2
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    base_env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PYTHONPATH=root,
        BCG_TPU_RUN_ID=run,
        BCG_TPU_METRICS_SHARD_DIR=shard_dir,
        BCG_TPU_METRICS_SHARD_MS="100",
        BCG_TPU_FLEET_STRAGGLER_FACTOR=(
            "0" if inject == "straggler-off" else "3"
        ),
    )
    procs = []
    for pid in range(nproc):
        env = dict(base_env)
        env["BCG_TPU_GAME_EVENTS"] = os.path.join(
            tmp, f"events-{pid}.jsonl"
        )
        straggle = "1" if pid == nproc - 1 else "0"
        procs.append(subprocess.Popen(
            [sys.executable, worker, coord, str(nproc), str(pid), straggle],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=root,
        ))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"fleet worker rank {pid} failed:\n{out[-3000:]}"
            )

    fr_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fleet_report.py"
    )
    frspec = importlib.util.spec_from_file_location("fleet_report", fr_path)
    fr = importlib.util.module_from_spec(frspec)
    frspec.loader.exec_module(fr)
    problems: List[str] = []
    records = [
        r for r in fr.load_shards([shard_dir], problems)
        if (r.get("identity") or {}).get("run_id") == run
    ]
    for problem in problems:
        print(f"perf_gate[fleet]: {problem}", file=sys.stderr)
    merged_counters = fr.merge_counters(records)
    merged_hists = fr.merge_histograms(records, problems)
    ranks = {
        (r.get("identity") or {}).get("process_index") for r in records
    }
    completeness = len(ranks) / nproc

    # Single-stream oracle: bucket the UNION of every rank's probe
    # values through one in-process registry histogram, then compare
    # fleet_report's merged quantiles against it.
    from bcg_tpu.obs.counters import Histogram

    oracle = Histogram("fleet.probe_oracle", wmod.PROBE_BOUNDS)
    for pid in range(nproc):
        for value in wmod.probe_values(pid):
            oracle.observe(value)
    oq = oracle.quantiles()
    merged_probe = merged_hists.get("fleet.probe_ms")
    if merged_probe is not None and merged_probe["count"]:
        mq = fr.histogram_quantiles(merged_probe)
        p50_err = abs(mq["p50"] - oq["p50"]) / max(1e-9, oq["p50"])
        p95_err = abs(mq["p95"] - oq["p95"]) / max(1e-9, oq["p95"])
    else:
        p50_err = p95_err = 1.0

    probe_total = merged_counters.get("fleet.probe", {}).get("total", 0)
    expected_probe = sum(100 + pid for pid in range(nproc))
    drops = (
        merged_counters.get("game.events_dropped", {}).get("total", 0)
        + merged_counters.get("serve.events_dropped", {}).get("total", 0)
    )
    flagged = 0.0
    for rec in records:
        if (rec.get("identity") or {}).get("process_index") == 0:
            flagged = float(
                (rec.get("gauges") or {}).get("fleet.stragglers", 0) >= 1
            )
    return {
        "fleet.shard_completeness": completeness,
        "fleet.merged_p50_rel_err": p50_err,
        "fleet.merged_p95_rel_err": p95_err,
        "fleet.counter_merge_error": abs(probe_total - expected_probe),
        "fleet.events_dropped": float(drops),
        "fleet.straggler_flagged": flagged,
    }


def run_hostsync_scenario(inject: str = "none") -> Dict[str, float]:
    """Runtime host-sync auditor (bcg_tpu/obs/hostsync.py) gates,
    pinned the way the while-body kernel census pinned PRs 8/10's
    fusion claims:

    * ``syncs_per_round`` — mean of the ``game.host_syncs`` per-round
      histogram over one hermetic FakeEngine consensus game: 2 batched
      engine calls per round (decide + vote) x 3 mirrored decode-path
      syncs = 6.0, the number the benchmark's ledger reports under the
      same name.
    * ``syncs_per_decision`` — observed transfers per agent decision on
      the tiny REAL engine's guided-JSON benchmark (one batched call,
      3 decisions): the decode path's actual materialization count
      (prefill barrier + decode readback + step readback), exact on any
      backend.
    * ``attribution_coverage`` — attributed / total over the whole
      scenario (acceptance: >= 0.95; tracing is off here, so this is
      the jit-entry attribution path doing the work).
    * ``error_rows`` — every real-engine row parses as valid guided
      JSON (the decision benchmark can't degrade to cover a sync
      regression).

    ``hostsync-off`` injection unsets the flag — the auditor observes
    nothing and the gate must FAIL naming syncs_per_round /
    syncs_per_decision / attribution_coverage rather than pass
    vacuously (zero-surface means zero metrics, not green metrics)."""
    import dataclasses

    from bcg_tpu.config import (
        BCGConfig, EngineConfig, GameConfig, MetricsConfig,
    )
    from bcg_tpu.obs import counters as obs_counters, hostsync as obs_hostsync
    from bcg_tpu.runtime.orchestrator import BCGSimulation

    # Save/restore the RAW values (None vs "") — registry accessors
    # cannot round-trip "was unset".
    prior = os.environ.get("BCG_TPU_HOSTSYNC")  # lint: ignore[BCG-ENV-RAW]
    if inject == "hostsync-off":
        os.environ.pop("BCG_TPU_HOSTSYNC", None)
    else:
        os.environ["BCG_TPU_HOSTSYNC"] = "1"
    obs_hostsync.reset()
    total_before = obs_counters.value("engine.hostsync.total")
    attr_before = obs_counters.value("engine.hostsync.attributed")
    try:
        # Arm 1: hermetic FakeEngine game (same geometry as the
        # consensus scenario's converging seed).
        cfg = dataclasses.replace(
            BCGConfig(),
            game=GameConfig(num_honest=4, num_byzantine=1,
                            max_rounds=6, seed=7),
            engine=EngineConfig(backend="fake"),
            metrics=MetricsConfig(save_results=False),
            verbose=False,
        )
        rounds_before = obs_counters.value("game.host_syncs.count")
        round_syncs_before = obs_counters.value("game.host_syncs.sum")
        sim = BCGSimulation(config=cfg)
        try:
            sim.run()
        finally:
            sim.close()
        rounds = obs_counters.value("game.host_syncs.count") - rounds_before
        round_syncs = (
            obs_counters.value("game.host_syncs.sum") - round_syncs_before
        )

        # Arm 2: tiny real engine, guided-JSON decision benchmark
        # (deterministic at temperature 0 — the engine scenario's
        # prompt set).
        _force_cpu()
        from bcg_tpu.engine.jax_engine import JaxEngine

        prompts = [
            ("honest agent system prompt", "Round 3: propose a value",
             DECISION),
            ("byzantine agent system prompt", "Round 3: vote now", VOTE),
            ("honest agent system prompt", "Round 4: propose a value",
             DECISION),
        ]
        eng_before = obs_counters.value("engine.hostsync.total")
        eng = JaxEngine(EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048,
        ))
        try:
            results = eng.batch_generate_json(
                prompts, temperature=0.0, max_tokens=64
            )
        finally:
            eng.shutdown()
        decision_syncs = (
            obs_counters.value("engine.hostsync.total") - eng_before
        )
        bad = sum(
            1 for r in results if not isinstance(r, dict) or "error" in r
        )
        total = obs_counters.value("engine.hostsync.total") - total_before
        attributed = (
            obs_counters.value("engine.hostsync.attributed") - attr_before
        )
    finally:
        if prior is None:
            os.environ.pop("BCG_TPU_HOSTSYNC", None)
        else:
            os.environ["BCG_TPU_HOSTSYNC"] = prior
        obs_hostsync.reset()
    return {
        "hostsync.syncs_per_round": round_syncs / rounds if rounds else 0.0,
        "hostsync.syncs_per_decision": decision_syncs / len(prompts),
        "hostsync.attribution_coverage": (
            attributed / total if total else 0.0
        ),
        "hostsync.error_rows": float(bad),
    }


def run_compile_scenario(inject: str = "none") -> Dict[str, float]:
    """Compile-cost observability (bcg_tpu/obs/compile.py) gates — the
    drift baseline for the sweep tier's per-tenant signature
    multiplication, pinned the way hostsync pinned
    the transfer structure:

    * ``steady_state_retraces`` — compile + retrace counter movement
      over an identical-shape warm repeat call (must be 0 EXACT: the
      observer's seams are the SAME trace-cache-miss accounting the
      engine already keys on, so enabling observability can never
      provoke a compile).
    * ``retrace_cause_coverage`` — structured cause records emitted per
      counted retrace over a PROVOKED retrace (a new max_tokens on the
      warm engine ⇒ new max_new/cache_len signatures).  Acceptance:
      every counted retrace carries a cause (min 0.95).
    * ``compile_cache_entries`` — distinct (entry, signature) pairs the
      observer accounted over the whole scenario (banded: the tiny
      engine's prefill + decode_loop signatures, cold + provoked).
    * ``error_rows`` — every row parses as valid guided JSON (the
      decision benchmark can't degrade to cover a compile regression).

    ``compile-off`` injection unsets the flag — the observer accounts
    nothing and the gate must FAIL naming retrace_cause_coverage /
    compile_cache_entries rather than pass vacuously (zero-surface
    means zero metrics, not green metrics)."""
    _force_cpu()
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine
    from bcg_tpu.obs import compile as obs_compile
    from bcg_tpu.obs import counters as obs_counters

    # Save/restore the RAW value (None vs "") — registry accessors
    # cannot round-trip "was unset".
    prior = os.environ.get("BCG_TPU_COMPILE_OBS")  # lint: ignore[BCG-ENV-RAW]
    if inject == "compile-off":
        os.environ.pop("BCG_TPU_COMPILE_OBS", None)
    else:
        os.environ["BCG_TPU_COMPILE_OBS"] = "1"
    obs_compile.reset()
    prompts = [
        ("honest agent system prompt", "Round 3: propose a value", DECISION),
        ("byzantine agent system prompt", "Round 3: vote now", VOTE),
        ("honest agent system prompt", "Round 4: propose a value", DECISION),
    ]
    try:
        eng = JaxEngine(EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=2048,
        ))
        try:
            cold = eng.batch_generate_json(prompts, temperature=0.0,
                                           max_tokens=64)
            # Steady state: an identical-shape repeat compiles NOTHING.
            before_warm = obs_counters.snapshot()
            warm = eng.batch_generate_json(prompts, temperature=0.0,
                                           max_tokens=64)
            warm_moved = obs_counters.delta(before_warm)
            # Provoked retrace: a new token budget on the warm engine is
            # a new max_new (decode loop) and cache_len (prefill)
            # signature — each must carry exactly one cause record.
            before_provoke = obs_counters.snapshot()
            provoked = eng.batch_generate_json(prompts, temperature=0.0,
                                               max_tokens=96)
            provoke_moved = obs_counters.delta(before_provoke)
        finally:
            eng.shutdown()
        # Per-scenario population from THE OBSERVER OBJECT, not a gauge
        # delta: the gauge holds absolute values, and an observer an
        # earlier in-process scenario created (any note_signature under
        # BCG_TPU_COMPILE_OBS) may have left it higher than this fresh
        # observer's count — a delta would go negative and fail the
        # band spuriously.  compile-off: no observer, 0.
        obs_active = obs_compile.observer()
        entries = (
            obs_active.brief()["cache_entries"]
            if obs_active is not None else 0
        )
    finally:
        if prior is None:
            os.environ.pop("BCG_TPU_COMPILE_OBS", None)
        else:
            os.environ["BCG_TPU_COMPILE_OBS"] = prior
        obs_compile.reset()
    # Prefix note: the observer's own families spell their segment with
    # an underscore (engine.compile_ms / engine.compile_obs /
    # engine.retrace_cause), so the dotted engine.compile. /
    # engine.retrace. prefixes below match ONLY the per-entry
    # trace-cache counters.
    steady = sum(
        v for k, v in warm_moved.items()
        if k.startswith(("engine.retrace.", "engine.compile."))
    )
    retraces = sum(
        v for k, v in provoke_moved.items()
        if k.startswith("engine.retrace.")
    )
    causes = sum(
        v for k, v in provoke_moved.items()
        if k.startswith("engine.retrace_cause.")
    )
    bad = sum(
        1 for r in cold + warm + provoked
        if not isinstance(r, dict) or "error" in r
    )
    return {
        "compile.steady_state_retraces": float(steady),
        "compile.retrace_cause_coverage": (
            causes / retraces if retraces else 0.0
        ),
        "compile.compile_cache_entries": float(entries),
        "compile.error_rows": float(bad),
    }


def run_sweep_scenario(inject: str = "none") -> Dict[str, float]:
    """Multi-tenant scheduling gates (the sweep tier's games-as-tenants
    contract, bcg_tpu/sweep + serve/scheduler.py tenancy), all
    deterministic: the device is PLUGGED (run_exclusive holds the
    device lock) while requests queue, so batch formation order is a
    pure function of the queue content.

    * ``starvation_ratio`` — 2 tenants through a FakeEngine scheduler
      (bucket 8 rows, linger 0): "heavy" floods 16 x 4-row requests,
      "light" submits 2.  The metric is the mean normalized batch
      position of the light tenant's rows: weighted-fair selection
      rides them in the FIRST post-plug batch (~0.1); FIFO drowns them
      behind the heavy backlog (~1.0).  ``--inject-regression
      fairness-off`` (Scheduler(fair=False)) must fail naming this
      metric.
    * ``fairness_batches`` — dispatch-count floor so the ratio can
      never pass vacuously on a degenerate single-batch run.
    * ``quota_overrun_rows`` / ``quota_deferrals`` — a tenant with an
      8-row quota: its queued-row high-water can NEVER exceed the quota
      (exactness, 0 exact) and the over-quota submit defers (>= 1)
      with a positive retry-after (``retry_after_live_ms``).
    * ``retry_after_monotonicity`` — the retry-after derivation
      (derive_retry_after_ms) over a headroom grid at a fixed SLO:
      1.0 iff non-increasing in headroom AND the zero-headroom backoff
      is >= 2x the full-headroom base (the serve.slo.headroom_ms
      histogram actually steers admission, monotonically).
    * ``error_rows`` — every scheduled row parses as valid guided JSON.
    """
    from bcg_tpu.engine.fake import FakeEngine
    from bcg_tpu.serve.scheduler import (
        AdmissionDeferred, Scheduler, derive_retry_after_ms,
    )

    class RecordingEngine:
        """FakeEngine proxy: records each dispatched batch's row
        markers (the first character of every user prompt) and adds a
        small device latency so dispatches are distinct batches."""

        def __init__(self):
            self.inner = FakeEngine(seed=0, policy="consensus")
            self.batches: List[List[str]] = []

        def batch_generate_json(self, prompts, temperature=0.8,
                                max_tokens=512):
            self.batches.append([p[1][0] for p in prompts])
            import time as _time

            _time.sleep(0.002)
            return self.inner.batch_generate_json(
                prompts, temperature=temperature, max_tokens=max_tokens
            )

    def _plug(sched):
        """Hold the device lock until released — dispatches form but
        cannot run, so queued work accumulates deterministically."""
        release = threading.Event()
        plugged = threading.Event()

        def hold():
            plugged.set()
            release.wait()

        t = threading.Thread(target=lambda: sched.run_exclusive(hold))
        t.start()
        plugged.wait(10)
        return release, t

    def _row(marker: str):
        return ("agent system prompt",
                f"{marker} Round 2. agent_1 value: 17. Your current "
                "value: 17. Decide.", DECISION)

    def _drain_queue(sched, deadline_s: float = 10.0) -> None:
        import time as _time

        t0 = _time.monotonic()
        poll_s = 0.0005
        while sched.queue_depth_rows() > 0:
            if _time.monotonic() - t0 > deadline_s:
                raise RuntimeError("scheduler never picked up the seed batch")
            _time.sleep(poll_s)  # backoff, not fixed-cadence (BCG-RETRY-SLEEP)
            poll_s = min(poll_s * 2, 0.01)

    # --- fairness arm -------------------------------------------------
    eng = RecordingEngine()
    sched = Scheduler(
        eng, linger_ms=0, bucket_rows=8, max_queue_rows=4096,
        deadline_ms=0, strict_admission=False,
        fair=(inject != "fairness-off"),
    )
    sched.register_tenant("heavy", weight=1.0)
    sched.register_tenant("light", weight=1.0)
    release, plug_thread = _plug(sched)
    try:
        reqs = [sched.submit(("json",), [_row("H")] * 4, [0.0] * 4,
                             [64] * 4, tenant="heavy")]
        _drain_queue(sched)  # seed batch in flight, blocked on the plug
        for _ in range(15):
            reqs.append(sched.submit(("json",), [_row("H")] * 4,
                                     [0.0] * 4, [64] * 4, tenant="heavy"))
        for _ in range(2):
            reqs.append(sched.submit(("json",), [_row("L")] * 4,
                                     [0.0] * 4, [64] * 4, tenant="light"))
    finally:
        release.set()
        plug_thread.join(10)
    for r in reqs:
        r.done.wait(30)
    sched.close()
    bad = sum(
        1 for r in reqs for row in (r.results or [])
        if not isinstance(row, dict) or "error" in row
    )
    n_batches = len(eng.batches)
    light_idx = [i for i, b in enumerate(eng.batches) if "L" in b]
    starvation = (
        sum(light_idx) / len(light_idx) / max(1, n_batches - 1)
        if light_idx else 1.0
    )

    # --- quota arm ----------------------------------------------------
    eng2 = FakeEngine(seed=0, policy="consensus")
    sched2 = Scheduler(eng2, linger_ms=0, max_queue_rows=4096,
                       deadline_ms=0, strict_admission=False)
    q = sched2.register_tenant("quotatenant", quota_rows=8)
    release2, plug2 = _plug(sched2)
    retry_ms = 0.0
    try:
        first = sched2.submit(("json",), [_row("Q")] * 4, [0.0] * 4,
                              [64] * 4, tenant="quotatenant")
        _drain_queue(sched2)
        fills = [sched2.submit(("json",), [_row("Q")] * 4, [0.0] * 4,
                               [64] * 4, tenant="quotatenant")
                 for _ in range(2)]
        over = sched2.submit(("json",), [_row("Q")] * 4, [0.0] * 4,
                             [64] * 4, tenant="quotatenant")
        if isinstance(over.error, AdmissionDeferred):
            retry_ms = over.error.retry_after_s * 1e3
    finally:
        release2.set()
        plug2.join(10)
    for r in [first] + fills:
        r.done.wait(30)
    sched2.close()
    overrun = max(0, q.max_queued_rows - 8)

    # --- retry-after shape (pure) ------------------------------------
    slo = 50
    grid = [derive_retry_after_ms(20.0, 10.0, slo_ms=slo,
                                  headroom_p50_ms=float(h))
            for h in range(0, slo + 1, 5)]
    monotone = all(a >= b for a, b in zip(grid, grid[1:]))
    responsive = grid[0] >= 2.0 * grid[-1]
    return {
        "sweep.starvation_ratio": starvation,
        "sweep.fairness_batches": float(n_batches),
        "sweep.quota_overrun_rows": float(overrun),
        "sweep.quota_deferrals": float(q.deferrals),
        "sweep.retry_after_live_ms": retry_ms,
        "sweep.retry_after_monotonicity": float(monotone and responsive),
        "sweep.error_rows": float(bad),
    }


def run_chaos_scenario(inject: str = "none") -> Dict[str, float]:
    """Chaos seam injection + recovery tier gates (runtime/resilience.py
    + the serve dispatch retry/supervisor ladder + the sweep job-requeue
    policy), all hermetic and deterministic — the scheduler's single
    dispatch thread makes seam occurrences strictly sequential, so an
    occurrence-indexed chaos spec fires the same faults at the same
    passes on every run:

    * serve arm — a seeded FakeEngine serving run (2 waves x 8 threads
      x 2-row guided requests, 4-row bucket, retries=2, watchdog 1.5s +
      engine_factory) under an injected engine CRASH (dispatch pass 2),
      device-call HANG (pass 4, 4s > watchdog), and PoolExhausted
      (pass 6).  Every fault must recover: completed_fraction 1.0,
      lost_futures/failed_requests/error_rows 0, and the recovery
      counters (dispatch_retries, recoveries, engine_rebuilds,
      batch_splits) land EXACTLY where the spec puts them — plus the
      serve.recovery_ms histogram's quantile sanity.
    * sweep arm — a 3-job FakeEngine sweep with a transient job crash
      injected at job pass 2 and a retry budget: the job must requeue,
      complete, and report exactly once (sweep_jobs_retried >= 1,
      completed_fraction 1.0, duplicate-job problems EMPTY via the real
      consensus_report parser).

    ``chaos-off`` injection unsets BCG_TPU_CHAOS: nothing fires, nothing
    recovers, and the gate must FAIL naming the retry/recovery/rebuild
    metrics rather than pass vacuously (zero faults means zero recovery
    evidence, not green recovery)."""
    import importlib.util
    import tempfile

    from bcg_tpu.engine.fake import FakeEngine
    from bcg_tpu.obs import counters as obs_counters
    from bcg_tpu.runtime import resilience
    from bcg_tpu.serve.scheduler import Scheduler
    from bcg_tpu.sweep.controller import run_sweep

    chaos_on = inject != "chaos-off"
    # Save/restore the RAW value (None vs "") — registry accessors
    # cannot round-trip "was unset".
    prior = os.environ.get("BCG_TPU_CHAOS")  # lint: ignore[BCG-ENV-RAW]
    before = obs_counters.snapshot()

    # --- serve arm: crash + hang + exhaust, all recovered -------------
    if chaos_on:
        os.environ["BCG_TPU_CHAOS"] = (
            "seed=7;crash@serve.dispatch:2;hang@serve.dispatch:4:4.0;"
            "exhaust@serve.dispatch:6"
        )
    else:
        os.environ.pop("BCG_TPU_CHAOS", None)
    resilience.reset()
    try:
        sched = Scheduler(
            FakeEngine(seed=0, policy="consensus"),
            linger_ms=0, bucket_rows=4, max_queue_rows=4096, deadline_ms=0,
            strict_admission=False, max_dispatch_retries=2,
            watchdog_s=1.5,
            engine_factory=lambda: FakeEngine(seed=0, policy="consensus"),
        )
        payload = [
            ("agent system prompt",
             "Round 2. agent_1 value: 17. agent_2 value: 17. "
             "Your current value: 17. Decide.",
             DECISION),
        ] * 2
        errors: List[BaseException] = []
        row_counts = {"rows": 0, "error_rows": 0}
        count_lock = threading.Lock()

        def one_request():
            try:
                out = sched.submit_and_wait(
                    ("json",), list(payload), [0.0] * 2, [64] * 2
                )
                bad = sum(
                    1 for r in out if not isinstance(r, dict) or "error" in r
                )
                with count_lock:
                    row_counts["rows"] += len(out)
                    row_counts["error_rows"] += bad
            except BaseException as e:  # lost futures surface as metrics
                errors.append(e)

        for _wave in range(2):
            threads = [
                threading.Thread(target=one_request) for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        snap = sched.snapshot()
        sched.close()

        # --- sweep arm: transient job crash, requeued, reported once --
        if chaos_on:
            os.environ["BCG_TPU_CHAOS"] = "seed=7;crash@sweep.job:2"
        resilience.reset()
        sweep_dir = os.path.join(
            tempfile.mkdtemp(prefix="bcg-chaos-gate-"), "sweep"
        )
        spec = {
            "name": "chaos-sweep",
            "base": {"agents": 3, "byzantine": 0, "max_rounds": 3,
                     "backend": "fake"},
            "axes": {"seed": [1, 2, 3]},
        }
        summary = run_sweep(
            spec, sweep_dir, max_concurrent=1,
            engine=FakeEngine(seed=0, policy="consensus"),
            max_job_retries=2,
        )
    finally:
        if prior is None:
            os.environ.pop("BCG_TPU_CHAOS", None)
        else:
            os.environ["BCG_TPU_CHAOS"] = prior
        resilience.reset()
    moved = obs_counters.delta(before)

    # Duplicate-job detection over the sweep's event files, through the
    # REAL merge consumer (scripts/consensus_report.py) — a requeued job
    # must never double its game_end.
    cr_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "consensus_report.py"
    )
    cr_spec = importlib.util.spec_from_file_location("consensus_report", cr_path)
    cr = importlib.util.module_from_spec(cr_spec)
    cr_spec.loader.exec_module(cr)
    import glob as _glob

    games, problems = [], []
    for path in sorted(_glob.glob(os.path.join(sweep_dir, "events-*.jsonl"))):
        games.extend(cr.parse_file(path, problems))
    dup_problems = cr.duplicate_job_problems(games)

    # serve.recovery_ms quantile sanity (the structural histogram gate —
    # wall-clock quantile VALUES are not banded, ordering is).  The
    # count guard reads the SCENARIO's movement, not the process
    # absolute: an earlier in-process recovery (another test) must not
    # let the chaos-off arm pass this vacuously.
    try:
        hist = obs_counters.histogram("serve.recovery_ms")
        q = hist.quantiles()
        hist_sane = float(
            moved.get("serve.recovery_ms.count", 0) > 0
            and 0.0 <= q["p50"] <= q["p95"] <= q["p99"] <= hist.bounds[-1]
        )
    except KeyError:
        hist_sane = 0.0
    if errors:
        raise errors[0]
    return {
        "chaos.completed_fraction": (
            snap["completed"] / max(1, snap["submitted"])
        ),
        "chaos.lost_futures": float(snap["pending"]),
        "chaos.failed_requests": float(snap["failed"]),
        "chaos.error_rows": float(row_counts["error_rows"]),
        "chaos.dispatch_retries": moved.get("serve.dispatch_retries", 0),
        "chaos.batch_splits": moved.get("serve.batch_splits", 0),
        "chaos.recoveries": moved.get("serve.recoveries", 0),
        "chaos.engine_rebuilds": moved.get("serve.engine_rebuilds", 0),
        "chaos.faults_injected": moved.get("chaos.injected", 0),
        "chaos.recovery_hist_sanity": hist_sane,
        "chaos.sweep_completed_fraction": (
            summary["completed"] / max(1, len(summary["results"]))
        ),
        "chaos.sweep_jobs_retried": moved.get("sweep.jobs.retried", 0),
        "chaos.sweep_duplicate_job_problems": float(len(dup_problems)),
    }


def run_scenarios_scenario(inject: str = "none") -> Dict[str, float]:
    """Adversary library + scenario registry gates (bcg_tpu/scenarios):
    a 4-scenario FakeEngine sweep (adaptive-margin, baseline-disrupt,
    clique-collusion, equivocation-split at seed 0) through the REAL
    sweep controller — each job derives its role-aware scripted policy
    from the registry (no injected engine) — consumed by the REAL
    report parser (scripts/consensus_report.py):

    * ``influence_<strategy>`` — per-strategy byzantine_influence
      floors (non-vacuity: every scripted adversary must actually move
      honest values, not just exist in config);
    * ``equivocation_divergence_rows`` — (round, sender) pairs whose
      delivered values differ across receivers, floored >= 1 under the
      equivocating strategy; ``offstrategy_divergence_rows`` pinned 0
      EXACT (only the equivocator may split values per receiver);
    * ``clique_shared_target_agreement`` — fraction of byzantine
      decisions in the clique games equal to the seed-derived
      ``clique_target`` (1.0 exact: collusion is scripted arithmetic);
    * ``strategies_covered`` — distinct strategies stamped in
      game_start (4 exact); ``error_rows`` — invalid decisions (0).

    ``scenarios-off`` injection runs the same grid shape with the
    registry unplugged (plain default jobs, no scenario key): the
    influence floors, coverage, divergence, and clique agreement must
    all FAIL loudly rather than pass vacuously."""
    import glob as _glob
    import importlib.util
    import tempfile

    from bcg_tpu.scenarios.strategies import clique_target
    from bcg_tpu.sweep.controller import run_sweep

    scen = ["adaptive-margin", "baseline-disrupt", "clique-collusion",
            "equivocation-split"]
    if inject == "scenarios-off":
        spec = {"name": "scenarios-gate",
                "base": {"agents": 6, "byzantine": 2, "max_rounds": 6},
                "axes": {"seed": [0, 1, 2, 3]}}
    else:
        spec = {"name": "scenarios-gate", "axes": {"scenario": scen}}
    out_dir = os.path.join(
        tempfile.mkdtemp(prefix="bcg-scen-gate-"), "sweep"
    )
    run_sweep(spec, out_dir, max_concurrent=1, max_job_retries=2)

    cr_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "consensus_report.py"
    )
    cr_spec = importlib.util.spec_from_file_location(
        "consensus_report", cr_path
    )
    cr = importlib.util.module_from_spec(cr_spec)
    cr_spec.loader.exec_module(cr)
    games: List = []
    problems: List[str] = []
    event_files = sorted(
        _glob.glob(os.path.join(out_dir, "events-*.jsonl"))
    )
    for path in event_files:
        games.extend(cr.parse_file(path, problems))

    influence: Dict[str, int] = {}
    equiv_rows = off_rows = invalids = 0
    strategies = set()
    for g in games:
        if not g.ended:
            continue
        invalids += g.invalids
        if g.strategy:
            strategies.add(g.strategy)
            influence[g.strategy] = (
                influence.get(g.strategy, 0) + g.influence
            )
            if g.strategy == "equivocate":
                equiv_rows += g.equivocation_rows
            else:
                off_rows += g.equivocation_rows

    # Clique oracle: collusion is pure arithmetic, so EVERY byzantine
    # decision in the clique games must equal the seed-derived target —
    # read straight from the decision events, not the aggregates.
    clique_hits = clique_total = 0
    for path in event_files:
        meta: Dict[str, Dict] = {}
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "game_start":
                    meta[rec["game"]] = rec
                elif (rec.get("event") == "decision"
                      and rec.get("role") == "byzantine"
                      and rec.get("value") is not None):
                    start = meta.get(rec.get("game"))
                    if start and start.get("strategy") == "clique":
                        lo_, hi_ = start["value_range"]
                        clique_total += 1
                        clique_hits += int(
                            rec["value"]
                            == clique_target(start.get("seed"), lo_, hi_)
                        )
    return {
        "scenarios.influence_disrupt": float(influence.get("disrupt", 0)),
        "scenarios.influence_clique": float(influence.get("clique", 0)),
        "scenarios.influence_adaptive": float(
            influence.get("adaptive", 0)
        ),
        "scenarios.influence_equivocate": float(
            influence.get("equivocate", 0)
        ),
        "scenarios.equivocation_divergence_rows": float(equiv_rows),
        "scenarios.offstrategy_divergence_rows": float(off_rows),
        "scenarios.clique_shared_target_agreement": (
            clique_hits / clique_total if clique_total else 0.0
        ),
        "scenarios.strategies_covered": float(len(strategies)),
        "scenarios.error_rows": float(invalids),
    }


def run_hlo_scenario(inject: str = "none") -> Dict[str, float]:
    """Kernel-census drift findings (scripts/hlo_census.py) as a gated
    metric — 0 findings = the lowered programs still match
    hlo_baseline.json.

    Runs as a SUBPROCESS, not in-process: XLA's fusion decisions depend
    on the host-platform device count, which is frozen at first jax
    import — a gate process that already ran other scenarios could not
    adopt the 8-device virtual-mesh geometry the census script (and
    tests/conftest.py) pin, and would diff against the baseline with
    the wrong lowering."""
    import subprocess

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hlo_census.py")
    proc = subprocess.run(
        [sys.executable, path, "--check"],
        capture_output=True, text=True, timeout=580,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    findings = [line for line in proc.stderr.splitlines()
                if line.startswith("DRIFT: ")]
    if proc.returncode not in (0, 2):  # crash, not a drift verdict
        findings.append(
            f"census subprocess failed rc={proc.returncode}: "
            + proc.stderr.strip()[-300:]
        )
    for f in findings:
        print(f"perf_gate[hlo]: {f}", file=sys.stderr)
    return {"hlo.census_drift_findings": float(len(findings))}


def run_alerts_scenario(inject: str = "none") -> Dict[str, float]:
    """Health & alerting plane gates (bcg_tpu/obs/alerts.py) driven
    over the chaos scenario's serve recipe — the evaluator watches a
    run the gate KNOWS contains exactly 3 faults (crash at dispatch
    pass 2, 4s hang at pass 4, PoolExhausted at pass 6), with the
    periodic thread parked (BCG_TPU_ALERT_MS=1h) so every evaluation
    cycle is driven explicitly and firing windows are deterministic:

    * oracle arm — a fault-free FakeEngine serving run under manual
      evaluation cycles: ``false_positives`` 0 EXACT (a quiet healthy
      process may not alert; threshold rules read ABSOLUTE gauges, so
      this arm starts from a reset registry — see SCENARIOS comment).
    * chaos arm — the crash+hang+exhaust run with one evaluation cycle
      per wave: the expected recovery rules (engine_errors,
      engine_rebuilt, dispatch_retries) each fire exactly once
      (``chaos_alerts_fired`` floored at the injected-fault count,
      ``fault_coverage`` >= 1), every episode resolves on the
      post-close quiet cycles (``unresolved_at_end`` 0, ``flaps`` 0 —
      a condition spanning consecutive cycles is ONE episode), no
      unexpected rule fires, ``health()`` flips failing while the
      engine_errors page alert is up and back (``healthz_flip`` 1),
      readiness flips unready INSIDE the hang window and back — read
      from the pushed transition history, no polling race
      (``readyz_flip`` 1) — and the JSONL alert stream's record counts
      match the engine's fired/resolved totals (``event_stream_ok``).

    ``alerts-off`` injection unsets BCG_TPU_ALERTS: the same faulted
    run evaluates NOTHING and the gate must FAIL naming
    rules_evaluated / chaos_alerts_fired / fault_coverage /
    healthz_flip / event_stream_ok rather than pass vacuously (zero
    observed faults means zero alerting evidence, not green alerting).
    readyz_flip stays 1 by DESIGN: readiness is plain module state the
    scheduler pushes regardless of the alerting flag."""
    import tempfile

    from bcg_tpu.engine.fake import FakeEngine
    from bcg_tpu.obs import alerts as obs_alerts
    from bcg_tpu.obs import counters as obs_counters
    from bcg_tpu.runtime import resilience
    from bcg_tpu.serve.scheduler import Scheduler

    alerts_on = inject != "alerts-off"
    # Save/restore the RAW values (None vs "") — registry accessors
    # cannot round-trip "was unset".
    prior_alerts = os.environ.get("BCG_TPU_ALERTS")  # lint: ignore[BCG-ENV-RAW]
    prior_ms = os.environ.get("BCG_TPU_ALERT_MS")  # lint: ignore[BCG-ENV-RAW]
    prior_events = os.environ.get("BCG_TPU_ALERT_EVENTS")  # lint: ignore[BCG-ENV-RAW]
    prior_chaos = os.environ.get("BCG_TPU_CHAOS")  # lint: ignore[BCG-ENV-RAW]

    events_path = os.path.join(
        tempfile.mkdtemp(prefix="bcg-alert-gate-"), "alerts.jsonl"
    )
    if alerts_on:
        os.environ["BCG_TPU_ALERTS"] = "1"
    else:
        os.environ.pop("BCG_TPU_ALERTS", None)
    os.environ["BCG_TPU_ALERT_MS"] = "3600000"
    os.environ["BCG_TPU_ALERT_EVENTS"] = events_path
    # Threshold/staleness rules read absolute registry values; earlier
    # scenarios legitimately leave stale heartbeats / zero headroom /
    # straggler verdicts behind.  The 0-exact false-positive pin needs
    # a pristine registry ('alerts' runs last for this reason).
    obs_counters.reset()
    obs_alerts.reset()
    obs_alerts.reset_readiness()
    resilience.reset()

    payload = [
        ("agent system prompt",
         "Round 2. agent_1 value: 17. agent_2 value: 17. "
         "Your current value: 17. Decide.",
         DECISION),
    ] * 2
    expected = ("engine_errors", "engine_rebuilt", "dispatch_retries")
    saw_failing = False
    final_ok = False
    try:
        # --- oracle arm: healthy traffic may not alert ----------------
        os.environ.pop("BCG_TPU_CHAOS", None)
        sched = Scheduler(
            FakeEngine(seed=0, policy="consensus"),
            linger_ms=0, bucket_rows=4, max_queue_rows=4096,
            deadline_ms=0, strict_admission=False,
        )
        obs_alerts.evaluate_now()  # base snapshot: rate rules need two
        for _ in range(2):
            sched.submit_and_wait(
                ("json",), list(payload), [0.0] * 2, [64] * 2
            )
            obs_alerts.evaluate_now()
        sched.close()
        obs_alerts.evaluate_now()
        eng = obs_alerts.engine()
        false_pos = float(eng.fired) if eng is not None else 0.0

        # --- chaos arm: the PR-15 recipe, one cycle per wave ----------
        before = obs_counters.snapshot()
        os.environ["BCG_TPU_CHAOS"] = (
            "seed=7;crash@serve.dispatch:2;hang@serve.dispatch:4:4.0;"
            "exhaust@serve.dispatch:6"
        )
        resilience.reset()
        sched = Scheduler(
            FakeEngine(seed=0, policy="consensus"),
            linger_ms=0, bucket_rows=4, max_queue_rows=4096,
            deadline_ms=0, strict_admission=False, max_dispatch_retries=2,
            watchdog_s=1.5,
            engine_factory=lambda: FakeEngine(seed=0, policy="consensus"),
        )
        obs_alerts.evaluate_now()  # fresh base: wave deltas are wave-only
        errors: List[BaseException] = []

        def one_request():
            try:
                sched.submit_and_wait(
                    ("json",), list(payload), [0.0] * 2, [64] * 2
                )
            except BaseException as e:  # lost futures surface as metrics
                errors.append(e)

        for _wave in range(2):
            threads = [
                threading.Thread(target=one_request) for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            obs_alerts.evaluate_now()
            ok, _ = obs_alerts.health()
            saw_failing = saw_failing or not ok
        sched.close()
        for _ in range(2):  # quiet cycles: every episode must resolve
            obs_alerts.evaluate_now()
        final_ok, _ = obs_alerts.health()

        # --- verdicts (gathered before the engine is torn down) -------
        moved = obs_counters.delta(before)
        injected = moved.get("chaos.injected", 0)
        if eng is not None:
            by_rule = eng.fired_by_rule()
            evaluations = float(eng.evaluations)
            flaps = float(eng.flaps)
            unresolved = float(len(eng.firing()))
            total_fired, total_resolved = eng.fired, eng.resolved
        else:
            by_rule = {}
            evaluations = flaps = unresolved = 0.0
            total_fired = total_resolved = 0
        chaos_fired = float(sum(by_rule.get(r, 0) for r in expected))
        unexpected = float(total_fired) - chaos_fired - false_pos

        hist = obs_alerts.readiness_history()
        engine_flips = sum(
            1 for h in hist if not h["ready"] and "engine" in h["reasons"]
        )
        readyz_flip = float(
            engine_flips if hist and hist[-1]["ready"] else 0
        )

        # Stop the evaluator and CLOSE the sink (drains the queue) so
        # the JSONL stream can be compared against the engine totals.
        obs_alerts.reset()
        firing_recs = resolved_recs = 0
        manifest_first = False
        try:
            with open(events_path) as f:
                recs = [json.loads(line) for line in f if line.strip()]
            manifest_first = bool(recs) and recs[0].get("event") == "manifest"
            firing_recs = sum(1 for r in recs if r.get("event") == "alert"
                              and r.get("state") == "firing")
            resolved_recs = sum(1 for r in recs if r.get("event") == "alert"
                                and r.get("state") == "resolved")
        except OSError:
            pass  # alerts-off: no engine, no sink, no file
        stream_ok = float(
            manifest_first and total_fired > 0
            and firing_recs == total_fired
            and resolved_recs == total_resolved
        )
    finally:
        for name, prior in (("BCG_TPU_ALERTS", prior_alerts),
                            ("BCG_TPU_ALERT_MS", prior_ms),
                            ("BCG_TPU_ALERT_EVENTS", prior_events),
                            ("BCG_TPU_CHAOS", prior_chaos)):
            if prior is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = prior
        obs_alerts.reset()
        obs_alerts.reset_readiness()
        resilience.reset()
    if errors:
        raise errors[0]
    return {
        "alerts.rules_evaluated": evaluations,
        "alerts.chaos_alerts_fired": chaos_fired,
        "alerts.fault_coverage": chaos_fired / max(1.0, float(injected)),
        "alerts.false_positives": false_pos,
        "alerts.flaps": flaps,
        "alerts.unresolved_at_end": unresolved,
        "alerts.unexpected_alerts": unexpected,
        "alerts.readyz_flip": readyz_flip,
        "alerts.healthz_flip": float(saw_failing and final_ok),
        "alerts.event_stream_ok": stream_ok,
    }


_RUNNERS = {
    "serve": run_serve_scenario,
    "engine": run_engine_scenario,
    "paged": run_paged_scenario,
    "sampler": run_sampler_scenario,
    "int4": run_int4_scenario,
    "consensus": run_consensus_scenario,
    "fleet": run_fleet_scenario,
    "hostsync": run_hostsync_scenario,
    "compile": run_compile_scenario,
    "sweep": run_sweep_scenario,
    "chaos": run_chaos_scenario,
    "scenarios": run_scenarios_scenario,
    "hlo": run_hlo_scenario,
    "alerts": run_alerts_scenario,
}


# ---------------------------------------------------------------- gating
def load_baseline(path: Optional[str] = None) -> Optional[Dict]:
    path = path or baseline_path()
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _bounds(entry: Dict) -> str:
    op = entry.get("op", "range")
    value = float(entry["value"])
    tol_rel = float(entry.get("tol_rel", 0.0))
    tol_abs = float(entry.get("tol_abs", 0.0))
    slack = abs(value) * tol_rel + tol_abs
    if op == "min":
        return f">= {value - slack:.4g}"
    if op == "max":
        return f"<= {value + slack:.4g}"
    return f"within [{value - slack:.4g}, {value + slack:.4g}]"


def check_metrics(measured: Dict[str, float], baseline: Optional[Dict]) -> List[str]:
    """Findings (empty = green): banded comparison plus the
    load-bearing-baseline contract (unbaselined measured metric and
    stale baseline entry are both failures)."""
    if baseline is None:
        return [f"no baseline file at {baseline_path()} — run "
                "scripts/perf_gate.py --update-baseline"]
    entries = baseline.get("metrics", {})
    findings: List[str] = []
    for name, got in sorted(measured.items()):
        entry = entries.get(name)
        if entry is None:
            findings.append(
                f"{name}: measured {got:.4g} but metric has no entry in "
                "perf_baseline.json — every gated metric needs a "
                "justified baseline (run --update-baseline and add a reason)"
            )
            continue
        op = entry.get("op", "range")
        value = float(entry["value"])
        tol_rel = float(entry.get("tol_rel", 0.0))
        tol_abs = float(entry.get("tol_abs", 0.0))
        slack = abs(value) * tol_rel + tol_abs
        ok = (
            got >= value - slack if op == "min"
            else got <= value + slack if op == "max"
            else value - slack <= got <= value + slack
        )
        if not ok:
            findings.append(
                f"{name}: measured {got:.4g}, required {_bounds(entry)} "
                f"(baseline {value:.4g}, tol_rel={tol_rel}, "
                f"tol_abs={tol_abs}) — {entry.get('reason', 'no reason')}"
            )
    return findings


def check_stale(measured: Dict[str, float], baseline: Optional[Dict],
                scenarios) -> List[str]:
    """Baseline entries whose scenario ran but which nothing measured
    (renamed/dropped metric = stale entry; a SKIPPED scenario's entries
    are not stale)."""
    if baseline is None:
        return []
    prefixes = tuple(f"{s}." for s in scenarios)
    return [
        f"perf_baseline.json entry {name!r} was not produced by its "
        "scenario (stale — remove it, or restore the metric)"
        for name in sorted(baseline.get("metrics", {}))
        if name.startswith(prefixes) and name not in measured
    ]


def update_baseline(measured: Dict[str, float],
                    path: Optional[str] = None) -> str:
    path = path or baseline_path()
    prior = load_baseline(path) or {}
    prior_metrics = prior.get("metrics", {})
    metrics = {}
    for name, got in sorted(measured.items()):
        old = prior_metrics.get(name, {})
        metrics[name] = {
            "value": round(float(got), 6),
            "op": old.get("op", "range"),
            "tol_rel": old.get("tol_rel", 0.15),
            "tol_abs": old.get("tol_abs", 0.0),
            "reason": old.get(
                "reason",
                "pinned by scripts/perf_gate.py --update-baseline; "
                "justify intentional perf changes here",
            ),
        }
    # Entries for scenarios that did not run this time survive untouched.
    for name, entry in prior_metrics.items():
        metrics.setdefault(name, entry)
    data = {
        "_comment": (
            "Hermetic perf-gate baseline (scripts/perf_gate.py). Every "
            "gated metric needs a justified entry; bounds are op "
            "(min/max/range) with tol_rel/tol_abs slack. An unbaselined "
            "measured metric and a stale entry are both gate failures — "
            "the baseline is load-bearing, not a mute "
            "(tests/test_perf_gate.py)."
        ),
        "metrics": dict(sorted(metrics.items())),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    return path


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="CPU-hermetic counter-derived perf gate "
        "(FakeEngine serving + tiny real engine + HLO census drift)."
    )
    parser.add_argument("--scenarios", default=",".join(SCENARIOS),
                        help=f"comma list of {SCENARIOS}")
    parser.add_argument("--update-baseline", action="store_true",
                        help="regenerate perf_baseline.json (keeps reasons/bands)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="print measured metrics as JSON")
    parser.add_argument("--inject-regression", default="none",
                        choices=REGRESSIONS,
                        help="self-test: provoke a known regression and "
                        "confirm the gate names it")
    args = parser.parse_args(argv)

    scenarios = tuple(s for s in args.scenarios.split(",") if s)
    bad = [s for s in scenarios if s not in SCENARIOS]
    if bad:
        print(f"unknown scenarios {bad}; known: {SCENARIOS}", file=sys.stderr)
        return 1
    measured: Dict[str, float] = {}
    for s in scenarios:
        measured.update(_RUNNERS[s](args.inject_regression))
    if args.as_json:
        print(json.dumps(measured, indent=2, sort_keys=True))
    else:
        width = max(len(n) for n in measured)
        for name, got in sorted(measured.items()):
            print(f"{name:<{width}}  {got:.4f}")
    if args.update_baseline:
        path = update_baseline(measured)
        print(f"baseline written: {path}", file=sys.stderr)
        return 0
    findings = check_metrics(measured, load_baseline())
    findings += check_stale(measured, load_baseline(), scenarios)
    for f in findings:
        print(f"PERF REGRESSION: {f}", file=sys.stderr)
    if findings:
        return 2
    print("perf gate green", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
