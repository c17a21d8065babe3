"""The Solar-Open2 block through ``JaxEngine`` and its counters: the
W8A8 transform of its leaves (experts included), the counts of expert
work the decode loop and the chunk programs carry, and the device-trace
names of its kernels (``tests/test_solar_open2.py`` holds the model
against its reference)."""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.models import transformer as T
from bcg_tpu.models.configs import MODEL_SPECS
from bcg_tpu.models.loader import init_random_params_sharded
from bcg_tpu.models.quantize import is_quantized, quantize_leaf_transform
from bcg_tpu.ops import moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SPEC = MODEL_SPECS["bcg-tpu/tiny-solar"]
SEED = 11
CELL_CONFIG = json.load(open(os.path.join(BENCH, "configs", "solar-open2-250b-int8.json")))


@functools.lru_cache(maxsize=None)
def made_params(quantized: bool = False):
    transform = quantize_leaf_transform(SPEC, "int8") if quantized else None
    return init_random_params_sharded(
        SPEC, jax.random.PRNGKey(SEED), leaf_transform=transform)


def left_padded(lengths, L, seed: int = 0):
    rng = np.random.default_rng(seed)
    tokens = np.zeros((len(lengths), L), np.int32)
    valid = np.zeros((len(lengths), L), bool)
    for i, n in enumerate(lengths):
        tokens[i, L - n:] = rng.integers(0, 256, n)
        valid[i, L - n:] = True
    return tokens, valid


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


class TestWeights:
    def test_plan_and_quantised_leaves(self):
        names = [p[0].split(".")[-1] for p in T.param_plan(SPEC) if p[0].startswith("layers.1.")]
        assert names == ["lin_wq", "lin_wk", "lin_wv", "lin_conv", "lin_wb", "kda_fa",
                         "kda_fb", "lin_a_log", "lin_dt_bias", "kda_ga", "kda_gb",
                         "lin_out_norm", "lin_wo", "attn_norm", "mlp_norm", "moe_router",
                         "w_gate", "w_up", "w_down", "moe_w_gate", "moe_w_up", "moe_w_down"]
        params = made_params(quantized=True)
        full, kda = params["layers"][0], params["layers"][1]
        assert {k for k, v in full.items() if is_quantized(v)} == {
            "wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down",
            "moe_w_gate", "moe_w_up", "moe_w_down"}
        assert {k for k, v in kda.items() if is_quantized(v)} == {
            "lin_wq", "lin_wk", "lin_wv", "lin_wo", "w_gate", "w_up", "w_down",
            "moe_w_gate", "moe_w_up", "moe_w_down"}
        # a scale per expert and output channel
        assert kda["moe_w_down"]["scale"].shape == (8, 64)

    def test_w8a8_tracks_bf16_within_the_quantiser(self):
        """int8 weights, experts included, with per-token int8
        activations against the bfloat16 model, layer by layer on the
        same input: each matmul's relative error is of the order of 1/127
        per operand, so each layer's update keeps a cosine above 0.995
        (0.9987 to 0.9996 read here).  End to end the random tiny model
        amplifies it, to 0.94 over the last positions' logits (0.97 with
        every expert chosen: not the routing alone), so the layers are
        held one at a time."""
        tokens, valid = left_padded([190, 70, 130], 192)
        plain, quant = made_params(), made_params(quantized=True)
        caches = T.init_kv_cache(SPEC, 3, 192), T.init_kv_cache(SPEC, 3, 192, quantized="int8")
        mask = np.tril(np.ones((192, 192), bool))[None] & valid[:, None, :] & valid[:, :, None]

        @functools.partial(jax.jit, static_argnums=0)
        def layer(kind, weights, x, entry):
            ctx = T._Ctx(None, None, jnp.int32(0), jnp.asarray(mask), 0, jnp.asarray(valid),
                         T.HybridImpl("xla", "xla"))
            return T._hybrid_layer(kind, weights, SPEC, x, entry, ctx)[0]

        x = plain["embed"][jnp.asarray(tokens)]
        for li, kind in enumerate(SPEC.layer_types):
            out = [layer(kind, p["layers"][li], x, c[li]) for p, c in zip((plain, quant), caches)]
            a, b = (np.asarray(o - x, np.float64)[valid] for o in out)
            assert (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b)) > 0.995, li
            x = out[0]


class TestCounts:
    def test_decode_step_counts_the_experts_its_rows_hit(self):
        """The count the decode loop carries, against one made on the
        host from each layer's routing of the step's rows."""
        params = jax.tree.map(lambda a: a.astype(jnp.float32), made_params())
        toks = np.array([3, 77, 200], np.int32)
        cache = T.init_kv_cache(SPEC, 3, 8, dtype=jnp.float32)
        mask = jnp.zeros((3, 8), bool).at[:, 0].set(True)
        _, _, hits = T.decode_step(T.stack_layer_params(params, spec=SPEC), SPEC, jnp.asarray(toks),
                                   jnp.int32(0), jnp.zeros((3,), jnp.int32),
                                   T.init_kv_cache(SPEC, 3, 8, dtype=jnp.float32, stacked=True),
                                   mask, tally=True)
        x, want = params["embed"][toks][:, None], 0
        ctx = T._Ctx(None, None, jnp.int32(0), mask, 0, None, T.HybridImpl("xla", "xla"))
        for layer, kind, entry in zip(params["layers"], SPEC.layer_types, cache):
            x, _ = T._HYBRID_BLOCKS[kind](layer, SPEC, x, entry, ctx)
            h = T.rms_norm(x, layer["mlp_norm"], SPEC.rms_eps)[:, 0]
            chosen = np.asarray(moe.route(h, layer["moe_router"], 4, True, 1.0).experts)
            want += len({int(e) for e in chosen.ravel() if e < SPEC.experts_held})
            x, _ = T._HYBRID_BLOCKS["moe"](layer, SPEC, x, ctx)
        assert int(hits) == want > 0

    def test_a_game_round_counts_its_experts_in_six_syncs(self, monkeypatch,
                                                        cell_engine_options):
        """A lockstep game on the tiny preset under the cell's options:
        each call's expert counts beside the slots and tokens they are a
        share of, read with the steps, so a round is still six syncs
        (prefill barrier, decode readback, steps readback; decide and
        vote), as in the other cells (``tests/test_hostsync.py``)."""
        from bcg_tpu.config import BCGConfig, GameConfig, MetricsConfig
        from bcg_tpu.obs import counters, hostsync, tracer
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        monkeypatch.setenv("BCG_TPU_HOSTSYNC", "1")
        monkeypatch.delenv("BCG_TPU_TRACE", raising=False)
        hostsync.reset()
        tracer.reset()
        sim = BCGSimulation(config=BCGConfig(
            game=GameConfig(num_honest=2, num_byzantine=1, max_rounds=2, seed=0),
            engine=EngineConfig(backend="jax", max_model_len=2048,
                                **cell_engine_options("solar-open2-250b-int8")),
            metrics=MetricsConfig(save_results=False),
        ))
        synced = [counters.value(k) for k in ("game.host_syncs.count", "game.host_syncs.sum")]
        before = counters.snapshot()
        try:
            stats = sim.run()
            steps = sim.engine.total_decode_steps
        finally:
            sim.engine.shutdown()
            hostsync.reset()
        moved = counters.delta(before)
        rounds, syncs = (counters.value(k) - n for k, n in zip(
            ("game.host_syncs.count", "game.host_syncs.sum"), synced))
        assert rounds == stats["total_rounds"] >= 1
        assert syncs == 6 * rounds
        assert moved["engine.moe.decode.expert_slots"] == steps * 4 * 8
        assert 0 < moved["engine.moe.decode.experts_hit"] <= moved["engine.moe.decode.expert_slots"]
        tokens = moved["engine.moe.prefill.tokens"]
        assert tokens % 4 == 0 and 0 < moved["engine.moe.prefill.assignments"] <= tokens * 4
        # three KDA layers' float32 state and bfloat16 conv tail a row
        rows = moved["engine.linear.state_rows"] // 3
        assert moved["engine.cache.linear_state_bytes"] == \
            3 * rows * (4 * 16 * 16 * 4 + 3 * 192 * 2)


class TestTraceNames:
    """Each new kernel's device-trace name matches its own pattern of the
    cell's file and no other; the other cells' patterns take none."""

    OPS = {
        "kda_prefill": "kda_prefill.12 custom-call f32[10,64,512,128]",
        "moe_prefill": "moe_prefill.3 custom-call f32[46080,1280]",
        "moe_decode": "moe_decode.7 custom-call f32[32,4096]",
        "flash_prefill": "closed_call.14 custom-call bf16[10,64,512,128]",
        "decode_attention": "closed_call.14 custom-call bf16[10,8,8,128]",
    }

    def test_each_kernel_matches_its_own_pattern(self):
        names = CELL_CONFIG["trace_names"]
        for kernel, op in self.OPS.items():
            assert [k for k in self.OPS if re.search(names[k], op)] == [kernel], op

    @pytest.mark.parametrize("config", ["qwen3-8b-int8", "olmo-hybrid-7b-int8"])
    def test_other_cells_take_none(self, config):
        """Their kernel patterns (a ``*_program`` pattern is of a
        program's name, not of a kernel's)."""
        names = json.load(open(os.path.join(BENCH, "configs", config + ".json")))["trace_names"]
        kernels = [p for k, p in names.items() if not k.endswith("_program")]
        for kernel in ("kda_prefill", "moe_prefill", "moe_decode"):
            assert not any(re.search(p, self.OPS[kernel]) for p in kernels), (config, kernel)
