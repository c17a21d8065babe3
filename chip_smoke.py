#!/usr/bin/env python
"""Does the system still start on the chip?  One process, normal entry
points, the 8B game round at published widths.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the tensor-parallel path only

One chip: boot ``bcg-tpu/bench-8b`` (Qwen3-8B widths, all 36 layers,
vocab 151,936; random weights from seed 0) with int8 weights and int8
KV through ``BCGConfig -> BCGSimulation -> create_engine -> JaxEngine``,
play two lockstep rounds of the 8 honest + 2 Byzantine game with
``sim.run_round()``, then one round of two games at once through
``run_serving_simulations`` on the same engine.  Four chips: the same
model on one device and on a ``tp=4`` mesh (prefill logits compared),
one tp=4 round, and a ``bcg-tpu/bench-14b`` tp=4 boot plus round.

Every phase's failure is the script's failure.  No accelerator means a
non-zero exit before anything boots: there is no CPU branch and no
subprocess.  The LAST line of stdout is the result the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

SEED = 0
MODEL_8B = "bcg-tpu/bench-8b"
MODEL_14B = "bcg-tpu/bench-14b"
# tp=4 prefill logits against one chip's, as a fraction of the largest
# one-chip logit.  tp moves no arithmetic: the row-parallel int8 partial
# sums are all-reduced in int32 and the weights are bit-identical (the
# script checks their checksum).  But W8A8 quantises activations to
# int8 on the fly, so wherever XLA fuses the sharded program differently
# and a bf16 rounding lands one ulp apart, a fraction of the activations
# re-round by one int8 step and the logits move by about the size of
# the quantisation noise itself.  That floor was measured in PR 22: a
# 1-ulp perturbation of 1% of the embedding entries moves the logits of
# this block by 3.7-5.8% of their range at every depth from 2 to 36
# layers (CPU, random weights, same W8A8 code), and tp=4 against one
# chip differed by 4.1% on the v5e.  A wrong shard — a missing quarter
# of the heads or of the MLP — decorrelates the logits, about 100% of
# the range.  15% sits three times above the floor and far below that.
TP_LOGITS_TOL = 0.15


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def compile_totals() -> dict:
    """What the program's one ``jax.monitoring`` listener has counted
    (``bcg_tpu/obs/compile.py``): seconds of tracing, lowering and
    backend compile, persistent-cache hits and misses."""
    from bcg_tpu.obs import compile as obs_compile

    return obs_compile.monitored_totals()


class Phase:
    """``with Phase("boot") as p:`` prints the phase's wall seconds and,
    from JAX's own monitoring events, the compile seconds inside it."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        say(f"--- {self.name}")
        self.t0 = time.perf_counter()
        self.c0 = compile_totals()["compile_s"]
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self.t0
        self.compiled = compile_totals()["compile_s"] - self.c0
        if exc_type is None:
            say(f"    {self.name}: {self.seconds:.1f} s wall, of which "
                f"{self.compiled:.1f} s backend compile")
        return False


def install_compile_listeners() -> None:
    """The program's listener, counting with the tracer on or off."""
    from bcg_tpu.obs import compile as obs_compile

    obs_compile.install_monitoring_listener(always=True)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def game_config(model: str, tp: int = 1):
    """8 honest + 2 Byzantine, the settings the 8B size class needs on a
    16 GB chip (bench.py's large-model defaults)."""
    from bcg_tpu.config import BCGConfig

    base = BCGConfig()
    return dataclasses.replace(
        base,
        game=dataclasses.replace(
            base.game, num_honest=8, num_byzantine=2, max_rounds=8, seed=SEED,
        ),
        engine=dataclasses.replace(
            base.engine, model_name=model, backend="jax",
            quantization="int8", kv_cache_dtype="int8",
            # Whole-prompt prefill activations do not fit beside 8B
            # weights + KV; the scan keeps the program one layer long.
            prefill_chunk=512, scan_layers=True,
            # Off, as bench.py sets it for this size class (prefix KV
            # beside weights + KV once OOMed the chip); whether it now
            # fits was not tried here.
            prefix_caching=False,
            # int8 weights pay off under the fast-forward chunk shapes.
            decode_fast_forward=True, guided_compact_json=True,
            tensor_parallel_size=tp,
        ),
        metrics=dataclasses.replace(
            base.metrics, save_results=False, generate_plots=False,
        ),
    )


def record_rows(engine, rows: list, batches: list) -> None:
    """Keep every (schema, result) the engine hands back, so each can be
    held to its schema afterwards, and the size of every device batch.
    Wraps the bound method on this one engine object; calls still run
    the engine's own code."""
    inner = engine.batch_generate_json

    def recording(prompts, *args, **kwargs):
        results = inner(prompts, *args, **kwargs)
        rows.extend((p[2], r) for p, r in zip(prompts, results))
        batches.append(len(prompts))
        return results

    engine.batch_generate_json = recording


def check_rows(rows: list, want: int, what: str) -> None:
    import jsonschema

    check(len(rows) >= want, f"{what}: {len(rows)} guided rows, want >= {want}")
    for schema, result in rows:
        jsonschema.validate(result, schema)  # raises on a bad row
    say(f"    {what}: {len(rows)} guided rows, each valid against its schema")


def describe_engine(engine) -> None:
    """The RESOLVED implementations; a kernel the config asks for that
    resolved to interpret mode or an XLA stand-in fails the smoke."""
    sampler = engine.sampler_stats()
    resolved = {
        "prefill_attention": engine.attention_impl,
        "decode_attention": engine.decode_attention_impl,
        "sampler": sampler["impl"],
        "sampler_interpret": sampler["interpret"],
        "kv_dtype": sampler["kv_dtype"],
        "mesh": dict(engine.mesh.shape) if engine.mesh is not None else None,
    }
    say("    resolved: " + json.dumps(resolved))
    for key in ("prefill_attention", "decode_attention", "sampler"):
        check(resolved[key] == "pallas",
              f"{key} resolved to {resolved[key]!r}, not the Pallas kernel")
    check(not resolved["sampler_interpret"], "sampler runs in interpret mode")
    say("    boot phases: " + "; ".join(
        f"{name}={p.get('seconds', 0):.1f}s" for name, p in engine.boot_phases.items()
    ))
    spec = engine.spec
    say(f"    model: {spec.name} layers={spec.num_layers} hidden={spec.hidden_size} "
        f"heads={spec.num_heads}/{spec.num_kv_heads} vocab={spec.vocab_size} "
        f"params={spec.param_count / 1e9:.2f}B")


def compile_counts() -> dict:
    from bcg_tpu.obs import counters

    return {
        name: value for name, value in counters.snapshot().items()
        if name.startswith("engine.compile.")
    }


def memory_line(devices) -> list:
    stats = [d.memory_stats() or {} for d in devices]
    for d, s in zip(devices, stats):
        say(f"    device {d.id}: in use {s.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
            f"peak {s.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{s.get('bytes_limit', 0) / 2**30:.2f} GiB")
    return stats


def build_token_dfas(sim) -> None:
    """Build the game's four token DFAs (decide/vote x honest/Byzantine)
    at the model's vocabulary through the engine's own cache, timed."""
    from bcg_tpu.guided import token_dfa
    from bcg_tpu.guided.processor import compile_schema

    engine = sim.engine
    schemas = {}
    for agent in sim.agents.values():
        for schema in (agent.decision_schema(), agent.vote_schema()):
            schemas[json.dumps(schema, sort_keys=True)] = schema
    t0 = time.perf_counter()
    for schema in schemas.values():
        compile_schema(
            schema, engine._token_bytes, vocab_id=engine.tokenizer.vocab_id,
            compact=engine.config.guided_compact_json,
        )
    say(f"    token DFAs: {len(schemas)} schemas at vocab {len(engine._token_bytes)} "
        f"in {time.perf_counter() - t0:.1f} s, builder={token_dfa.builder_in_use()}")


def play_round(sim, label: str) -> float:
    engine = sim.engine
    failed0, steps0 = engine.failed_rows, engine.total_decode_steps
    with Phase(label) as p:
        sim.run_round()
    check(engine.failed_rows == failed0,
          f"{label}: {engine.failed_rows - failed0} failed rows")
    check(engine.total_decode_steps > steps0, f"{label}: no decode steps")
    say(f"    {label}: {engine.total_decode_steps - steps0} decode steps, 0 failed rows")
    return p.seconds


# ------------------------------------------------------------------ one chip

def run_one_chip(devices) -> None:
    from bcg_tpu.runtime.orchestrator import BCGSimulation
    from bcg_tpu.serve import ServingEngine, run_serving_simulations

    cfg = game_config(MODEL_8B)
    with Phase("boot 8B int8 (weights, KV int8, one chip)"):
        sim = BCGSimulation(config=cfg)
        engine = sim.engine
    describe_engine(engine)
    build_token_dfas(sim)
    rows: list = []
    batches: list = []
    record_rows(engine, rows, batches)

    round1 = play_round(sim, "round 1 (compiles)")
    after1 = compile_counts()
    say("    compiled in round 1: " + json.dumps(after1, sort_keys=True))
    if sim.game.game_over:
        # Random-weight votes are correlated and can end a game at once;
        # the second round then opens a fresh game on the same engine.
        say("    game over after round 1: round 2 is round 1 of a new game")
        sim = BCGSimulation(
            config=dataclasses.replace(
                cfg, game=dataclasses.replace(cfg.game, seed=SEED + 100)),
            engine=engine,
        )
    round2 = play_round(sim, "round 2")
    new = {k: v - after1.get(k, 0) for k, v in compile_counts().items()
           if v != after1.get(k, 0)}
    say("    compiled in round 2: " + (json.dumps(new, sort_keys=True) if new
                                      else "nothing new"))
    say(f"    round seconds: first {round1:.1f}, second {round2:.1f}")
    # Two rounds x (decide + vote) x 10 agents, before any retry.
    check_rows(rows, 40, "two lockstep rounds")

    # Same engine, same process: two games, one round each, through the
    # continuous-batching scheduler (the path the G-games cell will use).
    del rows[:], batches[:]
    sims = [
        BCGSimulation(
            config=dataclasses.replace(
                cfg, game=dataclasses.replace(cfg.game, seed=SEED + 1 + i)),
            engine=engine,
        )
        for i in range(2)
    ]

    def served(s):
        def go(proxy):
            s.set_engine(proxy)
            try:
                s.run_round()
            finally:
                s.set_engine(engine)
        return go

    serving = ServingEngine(engine)
    failed0 = engine.failed_rows
    with Phase("served round: 2 games through ServingEngine"):
        outs = run_serving_simulations(
            engine, [served(s) for s in sims], serving=serving)
    stats = serving.stats()
    serving.shutdown()  # closes the scheduler; the engine stays ours
    for out in outs:
        if isinstance(out, BaseException):
            raise out
    check(engine.failed_rows == failed0,
          f"served round: {engine.failed_rows - failed0} failed rows")
    check(stats["failed"] == 0 and stats["engine_errors"] == 0,
          f"scheduler reports failures: {stats}")
    say(f"    scheduler: device batch sizes {batches}; " + json.dumps(
        {k: stats[k] for k in ("submitted", "completed", "dispatches",
                               "dispatched_rows", "merged_dispatches",
                               "row_cap", "batch_occupancy")}))
    check_rows(rows, 40, "served round")

    stats = memory_line(devices)[0]
    check(stats.get("peak_bytes_in_use", 0) <= stats.get("bytes_limit", 0),
          "peak HBM above the device limit")
    engine.shutdown()


# --------------------------------------------------------------- four chips

def vote_batch(sim):
    """One fixed 8H+2B vote batch: the round-1 vote prompts of a seeded
    game in which every agent proposed its initial value."""
    state = sim.game.get_game_state()
    return [agent.build_vote_prompt(state) for agent in sim.agents.values()]


def prefill_logits(engine, prompts):
    """Last-position logits of the engine's own prefill path (tokenize,
    left-pad into a bucket, fresh cache, chunked prefill) for guided
    rows ``(system, user, schema)``; also returns the filled cache."""
    import numpy as np

    from bcg_tpu.engine.chat_template import format_chat_prompt

    texts = [
        format_chat_prompt(engine.config.model_name, system, user,
                           engine.config.disable_qwen3_thinking)
        for system, user, _ in prompts
    ]
    budgets = [200] * len(texts)
    tokens, valid, L = engine._prepare_batch(texts, budgets)
    S = L + 256
    S += (-S) % engine._kv_align
    cache = engine._init_cache_sharded(len(texts), S)
    logits, cache = engine._prefill_possibly_chunked(tokens, valid, L, cache)
    return np.asarray(logits, np.float32), cache


def weights_checksum(params) -> int:
    """Exact and order-independent: every leaf's bits summed as int32,
    which wraps mod 2^32 the same way under any sharding."""
    import jax
    import jax.numpy as jnp

    ints = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32}

    @jax.jit
    def leaf_sum(x):  # fused convert + reduce: no widened copy of the leaf
        bits = jax.lax.bitcast_convert_type(x, ints[x.dtype.itemsize])
        return jnp.sum(bits.astype(jnp.int32))

    return sum(int(leaf_sum(leaf)) for leaf in jax.tree.leaves(params)) % 2**32


def check_spread(engine, cache, devices) -> None:
    """Weights and KV are spread over the four devices, not held whole
    on the first: per-device bytes from the arrays' own shardings, then
    from each device's allocator."""
    import jax

    from bcg_tpu.parallel.sharding import tree_bytes_per_device

    for name, tree in (("weights", engine.params), ("kv cache", cache)):
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        per_dev = tree_bytes_per_device(tree)
        say(f"    {name}: {total / 2**30:.2f} GiB total, "
            f"{per_dev / 2**30:.2f} GiB per device ({per_dev / total:.3f})")
        # Norm vectors replicate; everything large shards four ways.
        check(per_dev <= 0.27 * total,
              f"{name}: a device holds {per_dev / total:.2f} of the total")
    in_use = [s.get("bytes_in_use", 0) for s in memory_line(devices)]
    check(min(in_use) > 0 and max(in_use) <= 1.25 * min(in_use),
          f"devices hold unequal shares: {in_use}")


def collectives_of(engine, B: int) -> None:
    """Which collectives the compiled tp=4 prefill chunk contains."""
    import re

    import jax.numpy as jnp
    import numpy as np

    C, H = engine.prefill_chunk, 1024
    cache = engine._init_cache_sharded(B, 2048)
    text = engine._prefill_chunk_at.lower(
        engine.params, tokens=engine._put_batch(np.zeros((B, C), np.int32)),
        valid=engine._put_batch(np.ones((B, C), bool)), cache=cache,
        hist_valid=engine._put_batch(np.zeros((B, H), bool)),
        pos_offset=engine._put_batch(np.zeros((B,), np.int32)),
        write_pos=jnp.int32(H),
    ).compile().as_text()
    found = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute"):
        n = len(re.findall(rf"= \S+ {op}(?:-start)?\(", text))
        if n:
            found[op] = n
    say("    collectives in the tp=4 prefill-chunk program: " + json.dumps(found))
    check("all-reduce" in found, "tp=4 prefill has no all-reduce: not sharded?")
    check("tpu_custom_call" in text, "tp=4 prefill holds no Pallas kernel")


def run_four_chips(devices) -> None:
    import numpy as np

    from bcg_tpu.runtime.orchestrator import BCGSimulation

    # (a) one device, then tp=4, same seed -> same weights.
    cfg1 = game_config(MODEL_8B)
    with Phase("boot 8B int8 on one device"):
        sim1 = BCGSimulation(config=cfg1)
    describe_engine(sim1.engine)
    prompts = vote_batch(sim1)
    with Phase("one-device prefill of the fixed vote batch"):
        ref, cache = prefill_logits(sim1.engine, prompts)
    check(np.isfinite(ref).all(), "one-device logits not finite")
    checksum1 = weights_checksum(sim1.engine.params)
    del cache
    sim1.engine.shutdown()
    del sim1

    cfg4 = game_config(MODEL_8B, tp=4)
    with Phase("boot 8B int8 on tp=4 (mesh_from_engine_config)"):
        sim4 = BCGSimulation(config=cfg4)
    engine4 = sim4.engine
    check(engine4.mesh is not None and engine4.mesh.shape["tp"] == 4,
          "tp=4 engine has no tp=4 mesh")
    describe_engine(engine4)
    checksum4 = weights_checksum(engine4.params)
    say(f"    weights checksum: one device {checksum1:#010x}, tp=4 {checksum4:#010x}")
    check(checksum1 == checksum4, "tp=4 weights differ from one device's")
    with Phase("tp=4 prefill of the same batch"):
        got, cache = prefill_logits(engine4, prompts)
    check(got.shape == ref.shape and np.isfinite(got).all(),
          "tp=4 logits missing or not finite")
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    rel_l2 = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    say(f"    tp=4 vs one device: max |dlogit| / max |logit| = {err:.5f} "
        f"(tolerance {TP_LOGITS_TOL}), relative L2 {rel_l2:.5f}, "
        f"argmax agreement {agree:.2f} (random weights: near-ties)")
    check(err <= TP_LOGITS_TOL, f"tp=4 logits off by {err:.4f} of the logit range")
    check_spread(engine4, cache, devices)
    del cache
    collectives_of(engine4, len(prompts))

    # (b) one full round on tp=4.
    build_token_dfas(sim4)
    rows: list = []
    record_rows(engine4, rows, [])
    play_round(sim4, "tp=4 round, 8B")
    check_rows(rows, 20, "tp=4 round, 8B")
    memory_line(devices)
    engine4.shutdown()
    del sim4, engine4

    # (c) 14B int8 needs tp >= 2 on 16 GB chips: boot plus one round.
    # Its GQA group is 5; the engine keeps non-power-of-two groups off
    # the int8 decode kernel unless this flag pads them (the installed
    # compiler accepts both forms; see ROADMAP S6).
    os.environ["BCG_TPU_ALLOW_PADDED_GROUP_KERNEL"] = "1"
    with Phase("boot 14B int8 on tp=4"):
        sim14 = BCGSimulation(config=game_config(MODEL_14B, tp=4))
    describe_engine(sim14.engine)
    rows = []
    record_rows(sim14.engine, rows, [])
    play_round(sim14, "tp=4 round, 14B")
    check_rows(rows, 20, "tp=4 round, 14B")
    memory_line(devices)
    sim14.engine.shutdown()


# --------------------------------------------------------------------- main

def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()

    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu_version}, python {sys.version.split()[0]}")
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say("device: " + json.dumps(device))
    if device["platform"] != "tpu":
        say("no accelerator: this smoke runs on a TPU or not at all")
        return 2
    if device["count"] != args.chips:
        say(f"--chips {args.chips} but JAX reports {device['count']} devices")
        return 2

    from bcg_tpu.engine.jax_engine import compilation_cache_dir

    cache_dir = compilation_cache_dir()
    before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'in-checkout default'}), "
        f"{before} entries")
    install_compile_listeners()

    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(devices)
    else:
        run_one_chip(devices)
    check(jax.config.jax_compilation_cache_dir == cache_dir,
          f"JAX caches in {jax.config.jax_compilation_cache_dir!r}, not {cache_dir!r}")
    totals = compile_totals()
    say(f"compile cache: {cache_entries(cache_dir)} entries (was {before}); "
        f"persistent-cache hits {totals['cache_hits']}, misses {totals['cache_misses']}; "
        f"backend compile {totals['compile_s']:.1f} s of {time.perf_counter() - t0:.1f} s")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
