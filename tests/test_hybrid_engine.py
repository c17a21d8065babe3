"""The hybrid family through ``JaxEngine``: what is built for two kinds
of per-row state serves a game round on the normal path, and every
option that is not raises at boot by name (``tests/test_hybrid.py``
holds the model against its reference)."""

import dataclasses
import glob
import json
import os

import jax
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.models import transformer as T
from bcg_tpu.models.configs import MODEL_SPECS

SPEC = MODEL_SPECS["bcg-tpu/tiny-hybrid"]

VOTE = {
    "type": "object",
    "properties": {"decision": {"type": "string", "enum": ["stop", "continue"]}},
    "required": ["decision"], "additionalProperties": False,
}
LONG_ROW = ("sys " * 40, "user prompt " * 20, VOTE)
SHORT_ROW = ("sys", "short", VOTE)


CELL_FILES = sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "configs", "*.json",
)))


@pytest.mark.parametrize(
    "path", CELL_FILES,
    ids=[os.path.splitext(os.path.basename(p))[0] for p in CELL_FILES],
)
def test_cell_file_names_engine_fields_and_boots_at_tiny_size(
        path, cell_engine_options):
    """Every key of a benchmark configuration's ``program.engine`` is an
    ``EngineConfig`` field, and the tiny preset of its family boots and
    serves a guided call under them: a configuration added as files
    alone brings its own case."""
    from bcg_tpu.engine.jax_engine import JaxEngine

    with open(path) as f:
        named = json.load(f)["program"]["engine"]
    assert set(named) <= {f.name for f in dataclasses.fields(EngineConfig)}
    name = os.path.splitext(os.path.basename(path))[0]
    engine = JaxEngine(EngineConfig(
        backend="jax", max_model_len=1024, **cell_engine_options(name)))
    try:
        out = engine.batch_generate_json(
            [LONG_ROW, SHORT_ROW], temperature=0.0, max_tokens=24)
    finally:
        engine.shutdown()
    assert all(o.get("decision") in ("stop", "continue") for o in out)
    for key in set(named) - {"prefill_chunk"}:
        assert getattr(engine.config, key) == named[key], key


def engine_config(**kw):
    base = dict(backend="jax", model_name="bcg-tpu/tiny-hybrid", max_model_len=1024,
                prefix_caching=False)
    return EngineConfig(**{**base, **kw})


class TestEngine:
    @pytest.mark.parametrize("change, named", [
        ({"prefix_caching": True}, "prefix_caching"),
        ({"decode_fast_forward": True}, "decode_fast_forward"),
        ({"spec_decode": True}, "spec_decode"),
        ({"paged_kv": True}, "paged_kv"),
        ({"kv_cache_dtype": "int4"}, "kv_cache_dtype='int4'"),
        ({"quantization": "int4"}, "quantization='int4'"),
    ])
    def test_unbuilt_options_raise_at_boot(self, change, named):
        from bcg_tpu.engine.jax_engine import JaxEngine

        with pytest.raises(ValueError) as e:
            JaxEngine(engine_config(**change))
        assert "hybrid" in str(e.value) and named in str(e.value)

    @pytest.mark.parametrize("axis", ["tp", "sp", "dp"])
    def test_a_mesh_raises_at_boot(self, axis):
        from jax.sharding import Mesh

        from bcg_tpu.engine.jax_engine import JaxEngine

        shape = {"dp": 1, "tp": 1, "sp": 1, axis: 2}
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(
            shape["dp"], shape["tp"], shape["sp"]), ("dp", "tp", "sp"))
        with pytest.raises(ValueError, match="multi-device mesh"):
            JaxEngine(engine_config(), mesh=mesh)

    def test_env_requests_raise_too(self, monkeypatch):
        from bcg_tpu.engine.jax_engine import JaxEngine

        monkeypatch.setenv("BCG_TPU_PAGED_KV", "1")
        with pytest.raises(ValueError, match="paged_kv"):
            JaxEngine(engine_config())

    @pytest.fixture(scope="class")
    def served(self, cell_engine_options):
        """The deployment's options at tiny size, from the cell's own
        file: W8A8, int8 KV, layer scan, chunked prefill, compact JSON;
        one engine for the class."""
        from bcg_tpu.engine.jax_engine import JaxEngine

        engine = JaxEngine(engine_config(
            **cell_engine_options("olmo-hybrid-7b-int8")))
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
        assert moved["engine.linear.prefill_positions"] == 3 * rows_run
        assert moved["engine.linear.state_rows"] == 3 * 2
        assert moved["engine.cache.linear_state_bytes"] == \
            3 * 2 * (4 * 16 * 8 * 4 + 3 * 128 * 2)
        assert moved["engine.cache.kv_bytes"] > 0
        assert rows_run % (2 * 64) == 0          # whole 64-wide chunks of 2 rows

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
        served._mem_limit = served._param_bytes_per_device + 10 * kv // 4
        try:
            # budget 0.9 x limit less the weights: under 10 rows' worth
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
