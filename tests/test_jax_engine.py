"""JAX engine tests: guided generation with the tiny random-weight model.

The decisive property: even with RANDOM weights, guided decoding must
yield schema-valid JSON for every sequence — the automaton, not the
model, guarantees structure.  This is also the full-system integration
test: BCGSimulation runs end-to-end on the JAX engine.
"""

import dataclasses
import json

import pytest

from bcg_tpu.config import BCGConfig, EngineConfig, GameConfig, MetricsConfig
from bcg_tpu.engine.chat_template import format_chat_prompt
from bcg_tpu.engine.jax_engine import JaxEngine
from bcg_tpu.engine.tokenizer import ByteTokenizer


@pytest.fixture(scope="module", params=["defaults", "qwen3-8b-int8"])
def engine(request, cell_engine_options):
    """The tiny model under ``EngineConfig``'s defaults, and under the
    options the benchmark cell's file names (what the chip runs)."""
    options = (
        {} if request.param == "defaults"
        else cell_engine_options(request.param)
    )
    engine = JaxEngine(EngineConfig(**{
        "backend": "jax", "model_name": "bcg-tpu/tiny-test",
        "max_model_len": 2048, **options,
    }))
    yield engine
    engine.shutdown()


VOTE_SCHEMA = {
    "type": "object",
    "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
    "required": ["decision"],
    "additionalProperties": False,
}
# Bounded strings keep random-weight generation inside the token budget
# (a real model closes its strings; a random one rambles to max_tokens).
DECISION_SCHEMA = {
    "type": "object",
    "properties": {
        "internal_strategy": {"type": "string", "minLength": 1, "maxLength": 30},
        "value": {"type": "integer", "minimum": 0, "maximum": 50},
        "public_reasoning": {"type": "string", "minLength": 1, "maxLength": 30},
    },
    "required": ["internal_strategy", "value", "public_reasoning"],
    "additionalProperties": False,
}


class TestMaxNumSeqs:
    @pytest.mark.parametrize("cell", [
        pytest.param(None, marks=pytest.mark.slow, id="defaults"),
        # 7 s on the CPU: the cell's options run in tier-1.
        pytest.param("qwen3-8b-int8", id="qwen3-8b-int8"),
    ])
    def test_oversized_batch_chunks(self, monkeypatch, cell,
                                    cell_engine_options):
        engine = JaxEngine(EngineConfig(**{
            "backend": "jax", "model_name": "bcg-tpu/tiny-test",
            "max_model_len": 1024, "max_num_seqs": 2,
            **(cell_engine_options(cell) if cell else {}),
        }))
        calls = []
        orig = engine._decode_batch

        def spy(*a, **k):
            calls.append(len(a[0]))
            return orig(*a, **k)

        monkeypatch.setattr(engine, "_decode_batch", spy)
        prompts = [("sys", f"user {i}", VOTE_SCHEMA) for i in range(5)]
        out = engine.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
        assert len(out) == 5
        assert all(o.get("decision") in ("stop", "continue") for o in out)
        assert len(calls) == 3  # ceil(5 / 2) chunks
        assert all(c <= 2 for c in calls)
        engine.shutdown()


class TestHbmProvisioner:
    """hbm_utilization as an actual row provisioner (the reference's
    gpu_memory_utilization provisions the vLLM KV pool)."""

    def _engine(self):
        return JaxEngine(EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test", max_model_len=512,
        ))

    def test_no_cap_when_limits_unknown_or_batch_fits(self):
        engine = self._engine()
        parts = [("sys ", "", f"user {i}") for i in range(4)]
        # CPU: no device memory limit -> no derived cap.
        engine._mem_limit = None
        assert engine._provisioned_row_cap(parts, [24] * 4) is None
        # Huge limit: batch fits -> no cap (and no chunk event).
        engine._mem_limit = 1 << 40
        assert engine._provisioned_row_cap(parts, [24] * 4) is None
        assert engine.provision_chunk_events == 0
        engine.shutdown()

    @pytest.mark.slow
    def test_oversized_batch_chunks_under_tight_limit(self, monkeypatch):
        engine = self._engine()
        parts = [("sys ", "", f"user {i}") for i in range(4)]
        # Tight limit: per-row cache bytes at these shapes are ~100 KB;
        # allow roughly two rows' worth above the (tiny) weights.
        per_row = 600 * engine.spec.num_kv_heads * engine.spec.head_dim \
            * 4 * engine.spec.num_layers
        engine._mem_limit = int(
            (engine._param_bytes + 2.5 * per_row)
            / engine.config.hbm_utilization
        )
        cap = engine._provisioned_row_cap(parts, [24] * 4)
        assert cap is not None and 1 <= cap < 4
        # The chunk-event counter bumps when the cap actually splits a
        # batch (in _run_guided), not when the cap is merely derived.
        assert engine.provision_chunk_events == 0
        # End to end: the oversized batch still answers every row.
        calls = []
        orig = engine._decode_batch

        def spy(*a, **k):
            calls.append(len(a[0]))
            return orig(*a, **k)

        monkeypatch.setattr(engine, "_decode_batch", spy)
        prompts = [("sys ", f"user {i}", VOTE_SCHEMA) for i in range(4)]
        out = engine.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
        assert len(out) == 4
        assert all(o.get("decision") in ("stop", "continue") for o in out)
        assert all(c <= cap for c in calls)
        assert len(calls) >= 2
        assert engine.provision_chunk_events >= 1, \
            "the provisioner-forced split must be counted"
        engine.shutdown()


class TestChatTemplate:
    def test_qwen3_no_think(self):
        p = format_chat_prompt("Qwen/Qwen3-14B", "sys", "user")
        assert "<|im_start|>system\nsys<|im_end|>" in p
        assert "user /no_think<|im_end|>" in p
        assert p.endswith("<|im_start|>assistant\n")

    def test_qwen3_instruct_2507_no_soft_switch(self):
        p = format_chat_prompt("Qwen/Qwen3-4B-Instruct-2507", "sys", "user")
        assert "/no_think" not in p

    def test_llama3(self):
        p = format_chat_prompt("meta-llama/Meta-Llama-3.1-8B-Instruct", "s", "u")
        assert "<|start_header_id|>assistant<|end_header_id|>" in p

    def test_mistral(self):
        p = format_chat_prompt("mistralai/Mistral-Small-Instruct-2409", "s", "u")
        assert p.startswith("<s>[INST]") and p.endswith("[/INST]")


class TestByteTokenizer:
    def test_roundtrip(self):
        tk = ByteTokenizer()
        ids = tk.encode("hello {}")
        assert tk.decode(ids) == "hello {}"

    def test_token_bytes_layout(self):
        tk = ByteTokenizer(512)
        tb = tk.token_bytes()
        assert len(tb) == 512
        assert tb[65] == b"A"
        assert tb[tk.eos_id] == b""


class TestGuidedGeneration:
    def test_vote_batch_valid_json(self, engine):
        prompts = [("you vote", f"agent {i}: stop or continue?", VOTE_SCHEMA) for i in range(3)]
        results = engine.batch_generate_json(prompts, temperature=0.7, max_tokens=48)
        assert len(results) == 3
        for r in results:
            assert r.get("decision") in ("stop", "continue"), r

    def test_decision_schema_with_random_weights(self, engine):
        results = engine.batch_generate_json(
            [("sys", "round 1", DECISION_SCHEMA)], temperature=0.9, max_tokens=220
        )
        r = results[0]
        assert "error" not in r, r
        assert isinstance(r["value"], int) and 0 <= r["value"] <= 50
        assert isinstance(r["internal_strategy"], str)

    def test_heterogeneous_schemas_one_batch(self, engine):
        byz = {
            "type": "object",
            "properties": {"decision": {"type": "string",
                                        "enum": ["stop", "continue", "abstain"]}},
            "required": ["decision"],
            "additionalProperties": False,
        }
        results = engine.batch_generate_json(
            [("s", "u", VOTE_SCHEMA), ("s", "u", byz), ("s", "u", VOTE_SCHEMA)],
            temperature=0.8, max_tokens=48,
        )
        assert results[0]["decision"] in ("stop", "continue")
        assert results[1]["decision"] in ("stop", "continue", "abstain")

    def test_greedy_is_deterministic(self, engine):
        p = [("s", "u", VOTE_SCHEMA)]
        a = engine.batch_generate_json(p, temperature=0.0, max_tokens=48)
        b = engine.batch_generate_json(p, temperature=0.0, max_tokens=48)
        assert a == b

    def test_generate_free_text(self, engine):
        out = engine.generate("hello", temperature=0.5, max_tokens=12)
        assert isinstance(out, str)

    def test_prompt_too_long_reports_error(self):
        eng = JaxEngine(EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                                     max_model_len=160))
        res = eng.batch_generate_json(
            [("s" * 400, "u" * 400, VOTE_SCHEMA)], max_tokens=64
        )
        # Prompt is truncated to fit; generation still succeeds.
        assert res[0].get("decision") in ("stop", "continue") or "error" in res[0]


@pytest.mark.slow
class TestSimulationOnJaxEngine:
    @pytest.mark.parametrize("tp", [1, 2])
    def test_full_game_on_tiny_model(self, tp):
        """Complete BCG game over the JAX engine with random weights:
        guided decoding keeps every response schema-valid, so the game
        must run to a clean termination.  With tp=2 the same serving
        stack — orchestrator batching, guided decoding, prefix caching,
        retry ladder — runs composed over the mesh (round-3 verdict
        missing #3; the reference's TP path is its engine's,
        vllm_agent.py:139-142)."""
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        engine_cfg = EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                                  max_model_len=2048, tensor_parallel_size=tp)
        cfg = BCGConfig(
            game=GameConfig(num_honest=2, num_byzantine=1, max_rounds=2, seed=3),
            engine=engine_cfg,
            metrics=MetricsConfig(save_results=False),
        )
        sim = BCGSimulation(config=cfg)
        if tp > 1:
            assert sim.engine.mesh is not None
            assert sim.engine.mesh.shape.get("tp") == tp
        stats = sim.run()
        assert stats["total_rounds"] >= 1
        assert stats["termination_reason"] in (
            "vote_with_consensus", "vote_without_consensus", "max_rounds",
        )
        # Proposals that were made must be in range.
        for r in stats["rounds_data"]:
            for v in r["honest_values"] + r["byzantine_values"]:
                assert 0 <= v <= 50
        sim.engine.shutdown()


class TestLockstepGameOnCellOptions:
    """One lockstep game of up to three rounds on the tiny real engine
    under the options the benchmark cell's file names (W8A8, stacked
    int8 cache, layer scan, chunked prefill, compact JSON, no prefix
    cache)."""

    @pytest.fixture(scope="class")
    def game(self, cell_engine_options):
        """(final statistics, compile/retrace counter movement a round)."""
        from bcg_tpu.obs import counters as obs_counters
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        sim = BCGSimulation(config=BCGConfig(
            # The votes of a random-weight model decide when a game
            # stops: seed 0 plays all three rounds today, and the tests
            # below hold for any count above one.
            game=GameConfig(num_honest=2, num_byzantine=1, max_rounds=3, seed=0),
            engine=EngineConfig(backend="jax", max_model_len=2048,
                                **cell_engine_options("qwen3-8b-int8")),
            metrics=MetricsConfig(save_results=False),
        ))
        compiled = []
        play = sim.run_round

        def counted_round():
            before = obs_counters.snapshot()
            play()
            compiled.append({
                k: v for k, v in obs_counters.delta(before).items()
                if v and k.startswith(("engine.compile.", "engine.retrace."))
            })

        sim.run_round = counted_round
        try:
            yield sim.run(), compiled
        finally:
            sim.engine.shutdown()

    def test_full_game_on_tiny_model(self, game):
        """``TestSimulationOnJaxEngine``'s game under the cell's options
        at tp=1: every response schema-valid, a clean termination."""
        stats, _ = game
        assert 1 <= stats["total_rounds"] <= 3
        assert stats["termination_reason"] in (
            "vote_with_consensus", "vote_without_consensus", "max_rounds",
        )
        for r in stats["rounds_data"]:
            for v in r["honest_values"] + r["byzantine_values"]:
                assert 0 <= v <= 50

    def test_rounds_after_the_first_compile_nothing(self, game):
        """What the benchmark's window raises on, seen in tier-1 first:
        round 1 loads every program, the rounds after it move no
        ``engine.compile.*`` or ``engine.retrace.*`` counter."""
        stats, compiled = game
        assert len(compiled) == stats["total_rounds"] >= 2, \
            "the game stopped after round 1: pick a seed that plays on"
        assert compiled[0].get("engine.compile.decode_loop", 0) >= 1
        assert all(c == {} for c in compiled[1:]), compiled[1:]


class TestGuaranteedParse:
    """Force-completion: guided output parses even when the budget is far
    too small for the model's rambling (random weights never emit EOS)."""

    def test_unbounded_strings_tiny_budget_still_parse(self, engine):
        schema = {
            "type": "object",
            "properties": {
                "internal_strategy": {"type": "string", "minLength": 3},
                "value": {"type": "integer", "minimum": 0, "maximum": 50},
                "public_reasoning": {"type": "string", "minLength": 10},
            },
            "required": ["internal_strategy", "value", "public_reasoning"],
            "additionalProperties": False,
        }
        # The minimal valid completion is ~69 byte-tokens (object skeleton
        # + minLengths); any budget >= that must yield parseable JSON.
        results = engine.batch_generate_json(
            [("sys", f"user prompt {i}", schema) for i in range(3)],
            temperature=0.9, max_tokens=96,
        )
        for r in results:
            assert "error" not in r, r
            assert isinstance(r["value"], int) and 0 <= r["value"] <= 50
            assert len(r["internal_strategy"]) >= 3
            assert len(r["public_reasoning"]) >= 10

    def test_budget_smaller_than_min_completion_ends_clean(self, engine):
        # Budget 8 can't even finish the object; the sampler walks the
        # completion path from the start and EOSes at the dead end —
        # output may be invalid JSON but decoding must not crash and the
        # engine must return the parse-failure dict, not raise.
        schema = {
            "type": "object",
            "properties": {"a": {"type": "string", "minLength": 40}},
            "required": ["a"],
            "additionalProperties": False,
        }
        out = engine.batch_generate_json(
            [("", "p", schema)], temperature=0.9, max_tokens=8
        )
        assert isinstance(out[0], dict)


@pytest.mark.slow
class TestChunkedPrefill:
    VOTE_SCHEMA = {
        "type": "object",
        "properties": {"d": {"type": "string", "enum": ["stop", "continue"]}},
        "required": ["d"],
        "additionalProperties": False,
    }

    @staticmethod
    def _engine_pair(prefill_chunk: int, prefix_caching: bool):
        """(one-pass engine, chunked engine) over identical configs."""
        import dataclasses

        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine

        base = EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                            max_model_len=2048, prefix_caching=prefix_caching)
        return JaxEngine(base), JaxEngine(
            dataclasses.replace(base, prefill_chunk=prefill_chunk)
        )

    def _assert_chunked_matches(self, prompts, prefill_chunk, prefix_caching):
        one, chunked = self._engine_pair(prefill_chunk, prefix_caching)
        r_one = one.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
        r_chunked = chunked.batch_generate_json(
            prompts, temperature=0.0, max_tokens=24
        )
        assert r_chunked == r_one
        assert all("error" not in r for r in r_one)
        one.shutdown()
        chunked.shutdown()

    def test_chunk_offsets_share_one_compiled_program(self):
        """The single-shape chunk step (prefill_chunk_at) must serve every
        full-width chunk offset from ONE traced program — per-offset
        shapes cost minutes of compiles on an 8B boot."""
        import dataclasses

        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine

        engine = JaxEngine(EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test",
            max_model_len=1024, prefix_caching=False, prefill_chunk=64,
        ))
        # ~7 chunks of prompt; all full-width offsets must share a trace.
        prompts = [("sys " * 60, "user prompt " * 25, self.VOTE_SCHEMA)]
        out = engine.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
        assert "error" not in out[0]
        traces = engine._prefill_chunk_at._cache_size()
        assert traces <= 2, f"expected <=2 chunk-program traces, got {traces}"
        engine.shutdown()

    def test_chunked_matches_single_pass(self):
        """prefill_chunk slices the full-prompt prefill through the
        prefix-suffix jit; greedy output must be identical to one-pass
        prefill (same KV, same positions, chunk boundaries invisible)."""
        self._assert_chunked_matches(
            [
                ("sys " * 40, "user prompt " * 30, self.VOTE_SCHEMA),  # multi-chunk
                ("other sys " * 25, "short", self.VOTE_SCHEMA),        # ragged lengths
            ],
            prefill_chunk=64, prefix_caching=False,
        )

    def test_chunked_with_prefix_caching_matches(self):
        """The suffix region of a prefix-cached prefill chunks too (each
        chunk extends the cached prefix) — greedy-identical output."""
        self._assert_chunked_matches(
            [("sys " * 60, "user prompt " * 40, self.VOTE_SCHEMA)],
            prefill_chunk=64, prefix_caching=True,
        )

    def test_non_divisor_chunk_matches(self):
        """A chunk size that does not divide the bucketed length (512 %
        100 != 0) leaves a ragged final slice — output must still match
        one-pass exactly."""
        self._assert_chunked_matches(
            [("sys " * 50, "user words " * 25, self.VOTE_SCHEMA)],
            prefill_chunk=100, prefix_caching=False,
        )

    def test_negative_chunk_rejected(self):
        import dataclasses

        import pytest

        from bcg_tpu.config import EngineConfig
        from bcg_tpu.engine.jax_engine import JaxEngine

        with pytest.raises(ValueError, match="prefill_chunk"):
            JaxEngine(dataclasses.replace(
                EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test"),
                prefill_chunk=-64,
            ))

    def test_non_bf16_dtype_rejected(self):
        """EngineConfig.dtype exists for serving-config interface parity
        but TPU serving computes in bf16 — other values must be a loud
        error, not a silently ignored knob."""
        with pytest.raises(ValueError, match="bfloat16"):
            JaxEngine(dataclasses.replace(
                EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test"),
                dtype="float32",
            ))


class TestChunkedPrefillSkipsDeadChunks:
    """The chunked prefill's host loop starts at the first chunk in which
    any row of the LEFT-padded window holds a token: fewer calls of the
    same compiled program, the same served tokens."""

    VOTE_SCHEMA = TestChunkedPrefill.VOTE_SCHEMA
    # Byte tokens: the chat template adds some 80 to a prompt's own.
    LONG_ROW = ("sys " * 40, "user prompt " * 12, VOTE_SCHEMA)
    SHORT_ROW = ("other sys " * 5, "short", VOTE_SCHEMA)
    STACKED_INT8 = {"scan_layers": True, "kv_cache_dtype": "int8"}

    @pytest.fixture
    def traced(self, monkeypatch):
        from bcg_tpu.obs import tracer as obs_tracer

        monkeypatch.setenv("BCG_TPU_TRACE", "1")
        monkeypatch.delenv("BCG_TPU_TRACE_OUT", raising=False)
        monkeypatch.delenv("BCG_TPU_TRACE_RING", raising=False)
        obs_tracer.reset()
        yield obs_tracer.get_tracer()
        obs_tracer.reset()

    @staticmethod
    def _engine(**overrides):
        return JaxEngine(dataclasses.replace(
            EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                         max_model_len=2048, prefix_caching=False,
                         prefill_chunk=64),
            **overrides,
        ))

    @staticmethod
    def _spy(engine):
        """Record every dense prefill's (valid, L) and every chunk
        program the engine sends (its ``write_pos``)."""
        windows, sent = [], []
        prefill, chunk_at = engine._prefill_possibly_chunked, engine._prefill_chunk_at

        def spy_prefill(tokens, valid, L, cache, **kw):
            windows.append((valid.copy(), L))
            return prefill(tokens, valid, L, cache, **kw)

        def spy_chunk_at(*args, **kw):
            sent.append(int(kw["write_pos"]))
            return chunk_at(*args, **kw)

        engine._prefill_possibly_chunked = spy_prefill
        engine._prefill_chunk_at = spy_chunk_at
        return windows, sent, chunk_at

    @staticmethod
    def _live_chunks(valid, L, C):
        """ceil((L - first_valid) / C) counted on the chunk grid: the
        chunks from the one holding the first valid column to the end."""
        first = int(valid.any(axis=0).argmax())
        return -(-L // C) - first // C

    @pytest.mark.parametrize("rows, skips", [
        ([LONG_ROW], True),
        ([("sys " * 40, "user prompt " * 20, VOTE_SCHEMA)], False),
    ], ids=["pad_in_front", "token_in_first_chunk"])
    def test_sends_only_live_chunks(self, traced, rows, skips):
        from bcg_tpu.obs import counters as obs_counters

        engine = self._engine()
        windows, sent, chunk_at = self._spy(engine)
        before = obs_counters.snapshot()
        try:
            out = engine.batch_generate_json(rows, temperature=0.0, max_tokens=24)
        finally:
            engine.shutdown()
        moved = obs_counters.delta(before)
        assert "error" not in out[0]
        (valid, L), = windows
        C, B = 64, len(rows)
        total, live = L // C, self._live_chunks(valid, L, C)
        assert L % C == 0 and L > C
        if skips:
            # At least two whole chunks of pad in front of the window.
            assert total - live >= 2
        else:
            assert valid[:, :C].any() and live == total
        assert sent == list(range(L - live * C, L, C))
        assert moved["engine.prefill.positions_run"] == B * live * C
        assert moved["engine.prefill.positions_padded"] == B * L
        assert moved["engine.prefill.positions_real"] == int(valid.sum())
        end = next(e[6] for e in traced.events()
                   if e[0] == "E" and e[1] == "engine.prefill")
        assert (end["chunks"], end["chunks_skipped"]) == (live, total - live)
        assert end["prompt_window"] == L
        # Fewer calls of the SAME program: nothing new compiles.
        assert chunk_at._cache_size() <= 2

    @pytest.mark.parametrize("cell", ["qwen3-8b-int8", "olmo-hybrid-7b-int8"])
    def test_flash_step_counters_are_the_kernels_guard(
            self, cell, cell_engine_options):
        """``engine.prefill.attn_steps_live`` / ``_grid``: over the chunk
        programs a call sends, the flash kernel's own ``blk_any`` table
        from each program's whole mask, times KV heads and attention
        layers.  The count is the host's: the CPU programs keep their
        XLA attention and the engine is told its prefill runs the
        kernel (a CPU boot resolves "xla")."""
        import jax.numpy as jnp
        import numpy as np

        from bcg_tpu.obs import counters as obs_counters
        from bcg_tpu.ops.attention import _pad_to, flash_blocks

        engine = JaxEngine(EngineConfig(
            backend="jax", max_model_len=2048, **cell_engine_options(cell)))
        engine.attention_impl = "pallas"
        seen, chunk_at = [], engine._prefill_chunk_at

        def spy_chunk_at(*args, **kw):
            seen.append((np.asarray(kw["hist_valid"]), np.asarray(kw["valid"])))
            return chunk_at(*args, **kw)

        engine._prefill_chunk_at = spy_chunk_at
        before = obs_counters.snapshot()
        try:
            out = engine.batch_generate_json(
                [self.LONG_ROW, self.SHORT_ROW], temperature=0.0, max_tokens=24)
        finally:
            engine.shutdown()
        moved = obs_counters.delta(before)
        assert all("error" not in r for r in out)
        spec = engine.spec
        group, live, grid = spec.num_heads // spec.num_kv_heads, 0, 0
        assert len(seen) > 1
        for hist, valid in seen:
            B, C = valid.shape
            causal = np.tril(np.ones((C, C), bool))
            mask = np.concatenate(
                [hist[:, None, :] & valid[:, :, None],
                 causal[None] & valid[:, None, :] & valid[:, :, None]], axis=2)
            block_q, block_kv = flash_blocks(group, C, spec.head_dim)
            mp = np.asarray(_pad_to(_pad_to(jnp.asarray(mask), 1, block_q),
                                    2, block_kv))
            nT, nS = mp.shape[1] // block_q, mp.shape[2] // block_kv
            live += int(mp.reshape(B, nT, block_q, nS, block_kv)
                        .any(axis=(2, 4)).sum())
            grid += B * nT * nS
        heads = spec.num_kv_heads * engine._kv_layers
        assert moved["engine.prefill.attn_steps_live"] == live * heads
        assert moved["engine.prefill.attn_steps_grid"] == grid * heads
        assert 0 < live < grid

    @pytest.mark.parametrize("rows, overrides", [
        ([LONG_ROW], {}),
        # The suffix window's leading chunk goes (and the entry build's).
        ([("sys " * 40, "user prompt " * 9, VOTE_SCHEMA)],
         {"prefix_caching": True}),
        ([LONG_ROW], {"prefill_chunk": 100}),
        # The short row's pad covers chunks the long row's tokens reach:
        # only chunks dead in EVERY row go.
        ([LONG_ROW, SHORT_ROW], {}),
        # The cells' cache: skipped slots keep a STACKED int8 cache's
        # init zeros and unit scales.  The last two are the prompts of
        # TestChunkedPrefill's single-pass and non-divisor cases.
        ([LONG_ROW], STACKED_INT8),
        ([LONG_ROW, SHORT_ROW], STACKED_INT8),
        ([("sys " * 40, "user prompt " * 30, VOTE_SCHEMA),
          ("other sys " * 25, "short", VOTE_SCHEMA)], STACKED_INT8),
        ([("sys " * 50, "user words " * 25, VOTE_SCHEMA)],
         {**STACKED_INT8, "prefill_chunk": 100}),
    ], ids=["full_prompt", "prefix_cached", "non_divisor_chunk", "mixed_rows",
            "full_prompt-stacked_int8", "mixed_rows-stacked_int8",
            "ragged_multi_chunk-stacked_int8",
            "non_divisor_chunk-stacked_int8"])
    def test_greedy_output_matches_single_pass(self, rows, overrides):
        from bcg_tpu.obs import counters as obs_counters

        one = self._engine(**{**overrides, "prefill_chunk": 0})
        chunked = self._engine(**overrides)
        C = chunked.prefill_chunk
        windows, sent, _ = self._spy(chunked)
        try:
            r_one = one.batch_generate_json(rows, temperature=0.0, max_tokens=24)
            before = obs_counters.snapshot()
            r_chunked = chunked.batch_generate_json(
                rows, temperature=0.0, max_tokens=24
            )
            moved = obs_counters.delta(before)
        finally:
            one.shutdown()
            chunked.shutdown()
        assert r_chunked == r_one
        assert all("error" not in r for r in r_one)
        # The batch's own window (the last dense prefill; entry builds
        # come before it) left chunks out, and by the longest row alone.
        valid, L = windows[-1]
        live = self._live_chunks(valid, L, C)
        assert 0 < live < -(-L // C)
        assert len(sent) >= live
        assert (moved["engine.prefill.positions_run"]
                < moved["engine.prefill.positions_padded"])

    def test_live_slots_bit_equal_to_all_chunks(self):
        """First logits and every token's cache slot are, bit for bit,
        those of a loop over ALL chunks; the slots passed over keep the
        cache's zeros."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from bcg_tpu.models.transformer import init_kv_cache

        engine = self._engine()
        C = engine.prefill_chunk
        prompts = ["sys " * 40 + "user prompt " * 12, "short " * 9]
        tokens, valid, L = engine._prepare_batch(prompts, [24, 24])
        B, S = len(prompts), L + 40
        start0 = engine._prefill_chunk_starts(valid, L).start
        assert start0 >= 2 * C and valid[:, start0:start0 + C].any()

        def fresh():
            return init_kv_cache(engine.spec, B, S, stacked=engine.scan_layers)

        # The reference loop runs the engine's own jit of prefill_chunk_at:
        # bit equality holds within one compiled program, not across an
        # eager and a fused one.
        try:
            logits, cache = engine._prefill_possibly_chunked(
                tokens, valid, L, fresh()
            )
            ref_cache, ref_logits = fresh(), None
            for start in range(0, L, C):
                hist = np.zeros((B, L - C), dtype=bool)
                hist[:, :start] = valid[:, :start]
                ref_logits, ref_cache = engine._prefill_chunk_at(
                    engine.params,
                    tokens=jnp.asarray(tokens[:, start:start + C]),
                    valid=jnp.asarray(valid[:, start:start + C]),
                    cache=ref_cache, hist_valid=jnp.asarray(hist),
                    pos_offset=jnp.asarray(
                        valid[:, :start].sum(axis=1), jnp.int32),
                    write_pos=jnp.int32(start),
                )
        finally:
            engine.shutdown()
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        # Bf16 leaves are [B, S, Hkv, Dh].  A pad position's KV (a query
        # with nothing to attend averages whatever the cache holds) is
        # masked and never read: what must agree is every token's.
        for got, ref in zip(jax.tree_util.tree_leaves(cache),
                            jax.tree_util.tree_leaves(ref_cache)):
            got, ref = np.asarray(got), np.asarray(ref)
            assert got.shape[:2] == (B, S)
            np.testing.assert_array_equal(got[:, :L][valid], ref[:, :L][valid])
            assert not got[:, :start0].any() and ref[:, :start0].any()


def test_fine_suffix_ladder_config(monkeypatch):
    """EngineConfig.fine_suffix_buckets selects the 1536/3072-rung
    ladder PER ENGINE (opt-in: decode streams allocated suffix slots
    every step, and measured vote suffixes land just past the coarse
    rungs); env BCG_TPU_FINE_SUFFIX=1 is the bench/sweep override."""
    import dataclasses

    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    monkeypatch.delenv("BCG_TPU_FINE_SUFFIX", raising=False)
    base = EngineConfig(
        backend="jax", model_name="bcg-tpu/tiny-test", max_model_len=512,
    )
    coarse = JaxEngine(base)
    fine = JaxEngine(
        dataclasses.replace(base, fine_suffix_buckets=True),
        params=coarse.params,
    )
    assert 1536 not in coarse._suffix_buckets
    assert 3072 not in coarse._suffix_buckets
    assert 1536 in fine._suffix_buckets and 3072 in fine._suffix_buckets

    monkeypatch.setenv("BCG_TPU_FINE_SUFFIX", "1")
    via_env = JaxEngine(base, params=coarse.params)
    assert 1536 in via_env._suffix_buckets
    via_env.shutdown()
    fine.shutdown()
    coarse.shutdown()


def test_int8_decode_kernel_kill_switch(monkeypatch):
    """BCG_TPU_DISABLE_INT8_DECODE_KERNEL=1 routes int8-KV decode to the
    dequant fallback (operational escape for a kernel lowering failure;
    scripts/probe_int8_decode.py)."""
    import warnings

    import jax as _jax

    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    # tiny-dh128 has the lane-aligned head dim the Pallas gate requires;
    # the monkeypatched backend makes the selection logic believe it is
    # on TPU (construction only — nothing is generated).
    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    # ...but must not make it persist this CPU process's compiles in the
    # TPU backend's in-checkout cache for the rest of the session.
    monkeypatch.setattr(
        "bcg_tpu.engine.jax_engine._enable_compilation_cache", lambda: None
    )
    # A pre-set ambient kill-switch (the escape hatch's own use case)
    # must not poison the default-path assertion.
    monkeypatch.delenv("BCG_TPU_DISABLE_INT8_DECODE_KERNEL", raising=False)
    cfg = EngineConfig(
        backend="jax", model_name="bcg-tpu/tiny-dh128",
        max_model_len=512, kv_cache_dtype="int8",
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng_default = JaxEngine(cfg)
    assert eng_default.decode_attention_impl == "pallas"

    monkeypatch.setenv("BCG_TPU_DISABLE_INT8_DECODE_KERNEL", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # Weight sharing is valid here: shutdown() nulls .params, so the
        # donor must stay alive until the recipient is constructed.
        eng = JaxEngine(cfg, params=eng_default.params)
    assert eng.decode_attention_impl != "pallas"
    eng.shutdown()
    eng_default.shutdown()


class TestEngineUnderMesh:
    """The FULL engine composed under a mesh (round-3 verdict missing #3).

    The reference's TP path is its engine's, not its game's
    (vllm_agent.py:139-142 boots vLLM with tensor_parallel_size and a
    multiprocess executor); parity demands the same here: JaxEngine
    built with tensor_parallel_size=2 over the virtual 8-device CPU
    mesh, serving batch_generate_json end-to-end — guided DFA gathers,
    prefix-cache assembly, and the jitted decode loop all running over
    sharded params.
    """

    def _engine(self, **kw):
        from bcg_tpu.engine.interface import create_engine

        kw.setdefault("max_model_len", 1024)
        cfg = EngineConfig(
            backend="jax", model_name="bcg-tpu/tiny-test", **kw,
        )
        return create_engine(cfg)

    @staticmethod
    def _spy_prefill_sp(eng):
        """Wrap eng._prefill_sp with a call counter (dispatch reads the
        attribute per call, so the wrapper is seen)."""
        calls = []
        orig = eng._prefill_sp
        eng._prefill_sp = lambda *a, **kw: (calls.append(1) or orig(*a, **kw))
        return calls

    def test_params_actually_sharded_tp2(self):
        eng = self._engine(tensor_parallel_size=2)
        assert eng.mesh is not None and eng.mesh.shape["tp"] == 2
        # A column-parallel projection must be split over two devices.
        wq = eng.params["layers"][0]["wq"]
        devs = {s.device for s in wq.addressable_shards}
        assert len(devs) == 2
        shard_shape = wq.addressable_shards[0].data.shape
        assert shard_shape[1] == wq.shape[1] // 2
        eng.shutdown()

    def test_batch_generate_json_tp2_end_to_end(self):
        """Heterogeneous schemas, one batch, greedy, under tp=2: every
        row schema-valid and repeated runs byte-identical.  (No
        cross-engine byte comparison: the TP all-reduce changes float
        reduction order, which flips greedy argmax on the near-ties
        random weights produce — and once any token diverges, every
        later token is conditioned on a different prefix.  Schema
        validity is the automaton's guarantee, the property that must
        survive sharding.)"""
        eng_tp = self._engine(tensor_parallel_size=2)
        prompts = [
            ("You are honest.", "Pick a value.", DECISION_SCHEMA),
            ("You vote.", "Stop or continue?", VOTE_SCHEMA),
            ("You are honest.", "Pick another value.", DECISION_SCHEMA),
        ]
        out_tp = eng_tp.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        out_tp2 = eng_tp.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        for o in out_tp:
            assert "error" not in o, o
        assert out_tp == out_tp2  # deterministic under the mesh
        assert out_tp[1]["decision"] in ("stop", "continue")
        assert 0 <= out_tp[0]["value"] <= 50
        assert 0 <= out_tp[2]["value"] <= 50
        eng_tp.shutdown()

    def test_quant_scan_tp_sp_full_composition(self):
        """The widest serving composition in one engine: int4 weights x
        scan-over-layers x tp=2 x sp=2 — the 32B-preset pod-slice layout
        WITH long context (ring prefill + sp-sharded decode inside the
        lax.scan layer loop).  Every triple is covered elsewhere; the
        quadruple is what a 32B long-context deployment actually boots."""
        eng = self._engine(
            tensor_parallel_size=2, sequence_parallel_size=2,
            quantization="int4", scan_layers=True, prefix_caching=False,
        )
        assert eng.mesh.shape["tp"] == 2 and eng.mesh.shape["sp"] == 2
        calls = self._spy_prefill_sp(eng)
        out = eng.batch_generate_json(
            [("You are honest.", "Pick a value.", DECISION_SCHEMA),
             ("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        assert calls and eng._decode_ring_active and eng.sp_bypasses == 0
        for o in out:
            assert "error" not in o, o
        assert 0 <= out[0]["value"] <= 50
        assert out[1]["decision"] in ("stop", "continue")
        eng.shutdown()

    @pytest.mark.slow
    def test_maximal_composition_dp_tp_sp_quant_scan_int8kv(self):
        """Every serving axis at once on the full 8-device virtual mesh:
        int4 weights x int8 KV cache x scan-over-layers x dp=2 x tp=2 x
        sp=2.  The quantized cache tree-shards over all three axes
        (kv_cache_tree_sharding), batches dp-align and dp-place, ring
        prefill + sp decode run inside the scan loop over physically
        tp-split int4 leaves — the widest configuration any pod-slice
        deployment of the 14B/32B presets would boot."""
        eng = self._engine(
            data_parallel_size=2, tensor_parallel_size=2,
            sequence_parallel_size=2, quantization="int4",
            kv_cache_dtype="int8", scan_layers=True, prefix_caching=False,
        )
        assert eng.mesh.shape == {"dp": 2, "tp": 2, "sp": 2}
        out = eng.batch_generate_json(
            [("You are honest.", "Pick a value.", DECISION_SCHEMA),
             ("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        assert eng.dp_batches >= 1 and eng.dp_bypasses == 0
        assert eng.sp_bypasses == 0
        for o in out:
            assert "error" not in o, o
        assert 0 <= out[0]["value"] <= 50
        assert out[1]["decision"] in ("stop", "continue")
        eng.shutdown()

    @pytest.mark.parametrize("quant", ["int8", "int4"])
    def test_quantized_scan_tp2_end_to_end(self, quant):
        """The pod-slice serving configuration for the reference's
        large presets (8B: int8 + scan + tp; 14B/32B: int4 + scan + tp —
        config.py:20-25 presets served at vllm_agent.py:139-142 with
        tensor_parallel_size>1): quantized stacked weight trees sharded
        over a tp mesh, serving guided JSON through the full engine.
        Each pairwise composition is covered elsewhere; this is the
        triple the real large-model boot actually runs."""
        eng = self._engine(
            tensor_parallel_size=2, quantization=quant, scan_layers=True,
        )
        assert eng.mesh is not None and eng.mesh.shape["tp"] == 2
        # The stacked quantized projection must be physically split over
        # two devices (axis 0 of each leaf is the layer stack).
        wq = eng.params["layers"]["wq"]
        q = wq["q4"] if quant == "int4" else wq["q"]
        assert q.shape[0] == eng.spec.num_layers  # stacked for lax.scan
        assert len({s.device for s in q.addressable_shards}) == 2
        out = eng.batch_generate_json(
            [("You are honest.", "Pick a value.", DECISION_SCHEMA),
             ("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        for o in out:
            assert "error" not in o, o
        assert 0 <= out[0]["value"] <= 50
        assert out[1]["decision"] in ("stop", "continue")
        eng.shutdown()

    def test_sequence_parallel_prefill_end_to_end(self):
        """sequence_parallel_size=2: the engine's full-prompt prefill
        dispatches to the ring-attention path (transformer.prefill_sp)
        and the game-facing contract — schema-valid guided JSON — holds.
        Long-context SP is an ENGINE capability, not just an op."""
        eng = self._engine(sequence_parallel_size=2, prefix_caching=False)
        assert eng._prefill_sp is not None and eng._sp_devices == 2
        calls = self._spy_prefill_sp(eng)
        out = eng.batch_generate_json(
            [("You are honest.", "Pick a value.", DECISION_SCHEMA),
             ("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        assert calls, "ring prefill path was never taken"
        # Decode ran over the sp-sharded cache (sp_decode_attention
        # inside the jitted loop), not a replicated one.
        assert eng._decode_ring_active
        for o in out:
            assert "error" not in o, o
        assert 0 <= out[0]["value"] <= 50
        assert out[1]["decision"] in ("stop", "continue")
        eng.shutdown()

    def test_sequence_parallel_fast_forward_decode(self):
        """The fast-forward loop (the bench-default decode path) also
        keeps its bf16 cache sp-sharded (sp_chunk_decode_attention)."""
        eng = self._engine(sequence_parallel_size=2, prefix_caching=False,
                           decode_fast_forward=True)
        out = eng.batch_generate_json(
            [("You are honest.", "Pick a value.", DECISION_SCHEMA),
             ("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        assert eng._decode_ring_active
        assert eng.sp_bypasses == 0
        for o in out:
            assert "error" not in o, o
        assert out[1]["decision"] in ("stop", "continue")
        # Same schema-valid result twice: deterministic under the mesh.
        assert out == eng.batch_generate_json(
            [("You are honest.", "Pick a value.", DECISION_SCHEMA),
             ("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        eng.shutdown()

    def test_sequence_parallel_speculative_decode(self):
        """The speculative loop keeps the cache sp-sharded too: its
        verify chunk goes through sp_chunk_decode_attention with
        PER-ROW scatter writes into the sharded cache, and its greedy
        output matches the plain loop's under the same mesh."""
        eng = self._engine(sequence_parallel_size=2, prefix_caching=False,
                           spec_decode=True)
        plain = self._engine(sequence_parallel_size=2, prefix_caching=False)
        prompts = [
            ("You are honest.", "Pick a value.", DECISION_SCHEMA),
            ("You vote.", "Stop or continue?", VOTE_SCHEMA),
        ]
        out = eng.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        n_spec = eng.last_decode_steps
        assert eng._decode_ring_active
        assert eng.sp_bypasses == 0
        ref = plain.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        assert out == ref
        assert n_spec < plain.last_decode_steps
        eng.shutdown()
        plain.shutdown()

    @pytest.mark.slow
    def test_long_context_serving_via_sp(self):
        """An ~8K-byte-token prompt served end-to-end under sp=4: ring
        prefill shards the long prompt's activations, decode attends the
        long sp-sharded cache — the long-context capability claim (the
        reference TRUNCATES at this scale, SURVEY §5.7) exercised as one
        serving call, not just op tests.  The prompt deliberately
        exceeds the window limit so L clamps to max_model_len - budget
        - 1 = 8095 — the sp-indivisible shape that once bypassed the
        ring path (the engine now sp-aligns the window)."""
        eng = self._engine(sequence_parallel_size=4, prefix_caching=False,
                           max_model_len=8192)
        calls = self._spy_prefill_sp(eng)
        long_history = " ".join(
            f"Round {i}: agent_{i % 10} proposed {i % 50}." for i in range(260)
        )
        out = eng.batch_generate_json(
            [("You are honest.", long_history + " Pick a value.",
              DECISION_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        assert calls, "long prompt did not take the ring prefill path"
        assert eng._decode_ring_active
        assert eng.sp_bypasses == 0  # window clamp stayed sp-aligned
        assert "error" not in out[0], out[0]
        assert 0 <= out[0]["value"] <= 50
        # Pin the clamp scenario: the tokenized prompt must exceed every
        # ladder bucket, or this test degrades to an already-divisible
        # bucket and stops covering the alignment fix.
        assert len(eng.tokenizer.encode(long_history)) > 6144
        eng.shutdown()

    @pytest.mark.parametrize("ff", [False, True])
    def test_sequence_parallel_int8_kv_decode(self, ff):
        """int8 KV cache under sp=2: the decode loops shard the
        quantized cache and dequantize per-slice — no bypass."""
        eng = self._engine(sequence_parallel_size=2, prefix_caching=False,
                           kv_cache_dtype="int8", decode_fast_forward=ff)
        out = eng.batch_generate_json(
            [("You vote.", "Stop or continue?", VOTE_SCHEMA)],
            temperature=0.0, max_tokens=64,
        )
        assert eng._decode_ring_active
        assert eng.sp_bypasses == 0
        assert "error" not in out[0], out[0]
        assert out[0]["decision"] in ("stop", "continue")
        eng.shutdown()

    def test_chunked_prefill_runs_sp_sharded(self):
        """prefill_chunk and sequence_parallel_size compose: the large
        size class DEFAULTS to chunked prefill, so sp must shard the
        chunk path (transformer.prefill_chunk_at ring branch), not
        bypass it — and the output must match the unchunked sp engine."""
        eng = self._engine(sequence_parallel_size=2, prefix_caching=False,
                           prefill_chunk=64)
        prompts = [("You are honest.", "Pick a value. " * 20,
                    DECISION_SCHEMA)]
        out = eng.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        assert "error" not in out[0], out[0]
        assert eng.sp_bypasses == 0
        # Deterministic per config; schema-valid.  (No byte comparison
        # against the unchunked sp engine: per-chunk partial-softmax
        # merges change bf16 reduction order, which flips greedy argmax
        # on random-weight near-ties — the same caveat as the tp tests.
        # The plain path's chunked==one-pass identity is covered by
        # test_chunked_matches_single_pass.)
        assert out == eng.batch_generate_json(
            prompts, temperature=0.0, max_tokens=96
        )
        assert 0 <= out[0]["value"] <= 50
        eng.shutdown()

    def test_cached_prefix_prefill_runs_sp_sharded(self):
        """Prefix caching composes with sp: the suffix serves as ONE
        chunk against the cached prefix through the ring-capable chunk
        jit — no sp path remains that bypasses sharding."""
        eng = self._engine(sequence_parallel_size=2, prefix_caching=True)
        prompts = [("You are honest.", "Pick a value.", DECISION_SCHEMA)]
        out = eng.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        assert "error" not in out[0], out[0]
        # Non-vacuous: tiny-test's template family IS prefix-split-safe,
        # so the prefix path engaged — and it must not have bypassed sp.
        assert eng._prefix_safe
        assert eng.prefix_fallbacks == 0
        assert eng.sp_bypasses == 0
        assert 0 <= out[0]["value"] <= 50
        # Deterministic on the warm prefix cache too.
        assert out == eng.batch_generate_json(
            prompts, temperature=0.0, max_tokens=96
        )
        eng.shutdown()

    def test_near_cap_clamp_prefix_sp_aligns_up(self):
        """A system prefix that only fits the UNALIGNED clamp rung
        (limit - 64, with no ladder rung left below the limit) must be
        cached at the next sp multiple UP — padded entry, not the
        counted replicated fallback.  Closes the last off-ladder bypass
        class by construction (VERDICT r4 #4)."""
        from bcg_tpu.engine.chat_template import format_chat_parts

        eng = self._engine(sequence_parallel_size=4, prefix_caching=True,
                           max_model_len=1024)
        # ByteTokenizer: 1 ASCII char = 1 token.  limit = 1024-96-1 =
        # 927; clamp = 863; sp=4 aligns down to 860 — a prefix of 862
        # tokens fits ONLY the unaligned clamp, forcing the align-UP
        # rung (864).
        probe, _ = format_chat_parts(
            "bcg-tpu/tiny-test", "", "u", eng.config.disable_qwen3_thinking)
        overhead = len(eng.tokenizer.encode(probe))
        system = "R" * (862 - overhead)
        prefix, _ = format_chat_parts(
            "bcg-tpu/tiny-test", system, "u", eng.config.disable_qwen3_thinking)
        assert len(eng.tokenizer.encode(prefix)) == 862
        out = eng.batch_generate_json(
            [(system, "Pick a value.", DECISION_SCHEMA)],
            temperature=0.0, max_tokens=96,
        )
        assert "error" not in out[0], out[0]
        assert eng.sp_bypasses == 0
        assert eng.prefix_fallbacks == 0
        buckets = [b for (_p, b) in eng._prefix_cache]
        assert buckets and all(b % 4 == 0 for b in buckets)
        assert any(b >= 862 for b in buckets)
        eng.shutdown()

    def test_randomized_prompt_length_sweep_no_bypasses(self):
        """Seeded random prompt lengths spanning ladder rungs plus the
        near-cap clamp region: NO reachable shape may bypass sp —
        the flipped all-shapes assertion from VERDICT r4 #4."""
        import numpy as np

        eng = self._engine(sequence_parallel_size=2, prefix_caching=True,
                           max_model_len=1024)
        rng = np.random.RandomState(42)
        # Two random in-ladder lengths (cheap: shared bucket compiles)
        # plus both sides of the clamp boundary at limit-64 = 863.
        lengths = sorted(set(
            [int(x) for x in rng.randint(40, 700, size=2)] + [861, 863]
        ))
        for n in lengths:
            system = "R" * n
            out = eng.batch_generate_json(
                [(system, "Pick a value.", DECISION_SCHEMA)],
                temperature=0.0, max_tokens=96,
            )
            assert "error" not in out[0], (n, out[0])
        assert eng.sp_bypasses == 0, f"bypass at one of {lengths}"
        eng.shutdown()

    def test_shared_core_rows_under_sp(self):
        """(system, (core, tail)) rows with sp=2: the two-level core
        entry build routes through the ring-capable chunk jit
        (_get_core_entry), and serving stays schema-valid and
        deterministic with zero sp bypasses."""
        eng = self._engine(sequence_parallel_size=2)
        system = "You are an honest agent voting. " + "Rules. " * 30
        core = "=== PROPOSALS ===\n  agent_0: 5\n  agent_1: 5\n" * 4
        rows = [(system, (core, f"\n\nYou are agent_{i}. Decide now."),
                 VOTE_SCHEMA) for i in range(2)]
        out = eng.batch_generate_json(rows, temperature=0.0, max_tokens=48)
        assert all(r.get("decision") in ("stop", "continue") for r in out)
        assert eng.sp_bypasses == 0
        assert [k for k, _b in eng._prefix_cache if "\x1e" in k], \
            "core entry never built - the sp core path was not exercised"
        assert out == eng.batch_generate_json(
            rows, temperature=0.0, max_tokens=48
        )
        eng.shutdown()

    def test_batch_generate_json_dp2_tp2(self):
        """Composed dp x tp mesh: batch rows shard over dp while weights
        shard over tp — the one-agent-per-device scale-out layout."""
        eng = self._engine(tensor_parallel_size=2, data_parallel_size=2)
        prompts = [
            ("sys", f"user {i}", VOTE_SCHEMA if i % 2 else DECISION_SCHEMA)
            for i in range(4)
        ]
        out = eng.batch_generate_json(prompts, temperature=0.0, max_tokens=96)
        assert len(out) == 4
        for i, o in enumerate(out):
            assert "error" not in o, (i, o)
            if i % 2:
                assert o["decision"] in ("stop", "continue")
            else:
                assert 0 <= o["value"] <= 50
        eng.shutdown()



def test_spmd_exchange_composes_with_engine_mesh():
    """Real serving engine (tp=2 mesh) + SPMD collective exchange (dp
    mesh) in ONE simulation: two meshes over the same devices, the
    layout a one-agent-per-chip sweep with a TP-sharded model uses.
    Previously covered only separately (dryrun stages 7/8)."""
    import dataclasses

    from bcg_tpu.runtime.orchestrator import BCGSimulation

    base = BCGConfig()
    cfg = dataclasses.replace(
        base,
        game=GameConfig(num_honest=3, num_byzantine=1, max_rounds=2, seed=7),
        network=dataclasses.replace(base.network, spmd_exchange=True),
        engine=EngineConfig(backend="jax", model_name="bcg-tpu/tiny-test",
                            max_model_len=2048, tensor_parallel_size=2),
        metrics=MetricsConfig(save_results=False),
    )
    sim = BCGSimulation(config=cfg)
    stats = sim.run()
    assert stats["total_rounds"] >= 1
    assert sim._spmd_mesh is not None and sim._spmd_mesh.shape["dp"] == 4
    assert sim.engine.mesh is not None and sim.engine.mesh.shape["tp"] == 2
    sim.engine.shutdown()
