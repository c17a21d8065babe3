"""The state-space family through ``JaxEngine``: ``bcg-tpu/tiny-jamba``
under the options of its benchmark configuration's file (bfloat16 weights,
int8 KV, layer scan over periods of 14 layers, chunked prefill) serves
guided calls and a game round on the normal path, counts its scan state
beside its K/V, and every option not built for a recurrent state raises
at boot by name (``tests/test_jamba.py`` holds the model against its
reference)."""

import dataclasses

import jax
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.models import transformer as T
from bcg_tpu.models.configs import MODEL_SPECS

SPEC = MODEL_SPECS["bcg-tpu/tiny-jamba"]
CELL = "jamba2-3b-bf16"

VOTE = {
    "type": "object",
    "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
    "required": ["decision"], "additionalProperties": False,
}
LONG_ROW = ("sys " * 40, "user prompt " * 20, VOTE)
SHORT_ROW = ("sys", "short", VOTE)


def engine_config(**kw):
    base = dict(backend="jax", model_name="bcg-tpu/tiny-jamba", max_model_len=1024,
                prefix_caching=False, quantization=None)
    return EngineConfig(**{**base, **kw})


class TestRefusals:
    @pytest.mark.parametrize("change, named", [
        ({"prefix_caching": True}, "prefix_caching"),
        ({"decode_fast_forward": True}, "decode_fast_forward"),
        ({"spec_decode": True}, "spec_decode"),
        ({"paged_kv": True}, "paged_kv"),
        ({"kv_cache_dtype": "int4"}, "kv_cache_dtype='int4'"),
    ])
    def test_unbuilt_options_raise_at_boot_naming_the_kind(self, change, named):
        from bcg_tpu.engine.jax_engine import JaxEngine

        with pytest.raises(ValueError) as e:
            JaxEngine(engine_config(**change))
        assert "recurrent layers (mamba)" in str(e.value) and named in str(e.value)

    def test_a_mesh_raises_at_boot(self):
        from jax.sharding import Mesh

        from bcg_tpu.engine.jax_engine import JaxEngine

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1), ("dp", "tp", "sp"))
        with pytest.raises(ValueError, match="multi-device mesh"):
            JaxEngine(engine_config(), mesh=mesh)


class TestServed:
    @pytest.fixture(scope="class")
    def served(self, cell_engine_options):
        """The deployment's options at tiny size, from the configuration's file;
        one engine for the class."""
        from bcg_tpu.engine.jax_engine import JaxEngine

        options = cell_engine_options(CELL)
        assert options["model_name"] == SPEC.name and options["quantization"] is None
        engine = JaxEngine(engine_config(**options))
        yield engine
        engine.shutdown()

    def test_serves_guided_json_and_counts_its_state(self, served):
        from bcg_tpu.obs import counters

        before = counters.snapshot()
        out = served.batch_generate_json([LONG_ROW, SHORT_ROW], temperature=0.0,
                                         max_tokens=24)
        moved = counters.delta(before)
        assert all(o.get("decision") in ("stop", "continue") for o in out)
        rows_run = moved["engine.prefill.positions_run"]
        assert moved["engine.linear.prefill_positions"] == 13 * rows_run
        assert moved["engine.linear.state_rows"] == 13 * 2
        # a row's scan state [16, 128] float32 and conv tail [3, 128]
        # bfloat16 in each of 13 Mamba layers
        assert moved["engine.cache.linear_state_bytes"] == \
            13 * 2 * (16 * 128 * 4 + 3 * 128 * 2)
        assert moved["engine.cache.kv_bytes"] > 0
        assert rows_run % (2 * 64) == 0          # whole 64-wide chunks of 2 rows

    def test_weights_are_served_in_bfloat16(self, served):
        layers = served.params["layers"]
        assert layers["mamba"]["ssm_in"].dtype == jax.numpy.bfloat16
        assert layers["mamba"]["ssm_a_log"].dtype == jax.numpy.float32
        assert layers["full_attention"]["wq"].dtype == jax.numpy.bfloat16

    def test_chunked_greedy_output_matches_single_pass(self, served):
        from bcg_tpu.engine.jax_engine import JaxEngine

        one = JaxEngine(dataclasses.replace(served.config, prefill_chunk=0))
        try:
            rows = [LONG_ROW, SHORT_ROW]
            assert served.batch_generate_json(rows, temperature=0.0, max_tokens=24) == \
                one.batch_generate_json(rows, temperature=0.0, max_tokens=24)
        finally:
            one.shutdown()

    def test_row_cap_reads_both_kinds_of_state(self, served):
        kv = served._kv_bytes_per_device(4, 1024)
        by_kind = T.cache_bytes(SPEC, 4, 1024, quantized="int8", stacked=True)
        assert kv == by_kind["kv"] + by_kind["linear_state"]
        assert by_kind["linear_state"] == 4 * 13 * (16 * 128 * 4 + 3 * 128 * 2)
        served._mem_limit = served._param_bytes_per_device + 10 * kv // 4
        try:
            assert 1 <= served.cap_for(1024) < 10
        finally:
            served._mem_limit = None

    def test_a_game_round_runs_on_the_normal_path(self, served):
        from bcg_tpu.config import BCGConfig
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        base = BCGConfig()
        cfg = dataclasses.replace(
            base,
            game=dataclasses.replace(base.game, num_honest=2, num_byzantine=1,
                                     max_rounds=2, seed=3),
            llm=dataclasses.replace(base.llm, max_tokens_decide=60, max_tokens_vote=20),
            engine=served.config,
            metrics=dataclasses.replace(base.metrics, save_results=False,
                                        generate_plots=False),
        )
        sim = BCGSimulation(config=cfg, engine=served)
        rows0 = served.total_rows
        sim.run_round()
        assert served.total_rows - rows0 >= 6      # 3 agents x (decide + vote)
