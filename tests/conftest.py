"""Test configuration.

Run JAX on an 8-device virtual CPU mesh so multi-chip sharding logic
(tp/dp/sp over a Mesh) is exercised hermetically without TPU hardware
(SURVEY.md §4's test-strategy requirement).  Both settings are plain
environment, read by JAX when it is first imported.
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="session")
def cell_engine_options():
    """``cell_engine_options(name)``: the ``EngineConfig`` fields a
    benchmark cell's file names (``benchmark/configs/<name>.json``,
    ``program.engine``; read only), for the tiny preset of the same
    family: ``prefill_chunk`` scaled to tiny prompts and nothing else
    changed, so a tier-1 engine runs the code path the chip runs."""
    import json

    from bcg_tpu.models.configs import MAMBA, spec_for_model

    configs = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs",
    )

    def options(name: str) -> dict:
        with open(os.path.join(configs, f"{name}.json")) as f:
            program = json.load(f)["program"]
        spec = spec_for_model(program["model_name"])
        return {
            **program["engine"],
            "prefill_chunk": 64,
            "model_name": (
                "bcg-tpu/tiny-solar" if spec.moe
                else "bcg-tpu/tiny-jamba" if spec.layers_of(MAMBA)
                else "bcg-tpu/tiny-hybrid" if spec.hybrid else "bcg-tpu/tiny-test"
            ),
        }

    return options
