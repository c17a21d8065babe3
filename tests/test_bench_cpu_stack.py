"""The 8B serving flag stack, end-to-end through bench.py on the CPU.

The most expensive round-3/4 failure mode: the chip returns for a short
window and bench_8b dies on a host-side bug before any number lands.
This test runs the EXACT flag combination the 8B bench serves —
int8 weights + int8 KV + scan-over-layers + chunked prefill +
fast-forward + compact JSON, prefix caching off — through the real
bench entrypoint (size-class gating, warmup, measured window, contract
JSON) with the tiny model on the in-process CPU
backend (``BENCH_FORCE_CPU=1``).  If this passes, a hardware bench_8b
failure isolates to scale or Mosaic lowering, never bench plumbing.
"""

import json
import os
import subprocess
import sys

import pytest

from bcg_tpu.runtime.envflags import get_bool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.skipif(
    get_bool("BCG_TPU_SKIP_SLOW"),
    reason="~10 min of 1-core work; BCG_TPU_SKIP_SLOW=1 opts out for "
           "interim local runs (default ON — this is the 8B-path "
           "insurance the driver's suite must keep)",
)
@pytest.mark.slow
def test_bench_8b_flag_stack_on_cpu():
    env = dict(
        os.environ,
        BENCH_FORCE_CPU="1",
        BENCH_MODEL="bcg-tpu/tiny-test",
        BENCH_BACKEND="jax",
        BENCH_QUANTIZATION="int8",
        BENCH_KV_DTYPE="int8",
        BENCH_SCAN_LAYERS="1",
        BENCH_PREFIX_CACHING="0",
        BENCH_PREFILL_CHUNK="64",
        BENCH_ROUNDS="1",
        BENCH_WARMUP="1",
    )
    # Drop the conftest's 8-virtual-device flag: the bench subprocess is
    # single-device, and compiling every program for 8 CPU devices
    # triples this test's wall-clock for nothing.
    env["XLA_FLAGS"] = ""
    # Persistent compile cache: the first run pays ~10 min of 1-core XLA
    # compilation for the full 8B program stack; subsequent suite runs
    # replay it in seconds.
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    # A host-CPU run carries counts and labels, never a device metric.
    assert "metric" not in result and "value" not in result, result
    assert result["cpu_smoke"]["decisions"] > 0
    extra = result["extra"]
    assert extra["window_decode_steps"] > 0
    assert extra["quantization"] == "int8"
    assert extra["kv_cache_dtype"] == "int8"
    assert extra["scan_layers"] is True
    assert extra["prefill_chunk"] == 64
    assert extra["prefix_caching"] is False
    assert extra["platform"] == "cpu"
