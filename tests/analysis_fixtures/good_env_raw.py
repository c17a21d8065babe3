"""GOOD: registry accessors; raw reads only of EXTERNAL names."""
import os

from bcg_tpu.runtime.envflags import get_bool, get_int, get_str, is_set

TRACE = get_bool("BCG_TPU_TRACE")
ROUNDS = get_int("BENCH_ROUNDS")
MODEL = get_str("BENCH_MODEL")
XLA_FLAGS = os.environ.get("XLA_FLAGS", "")  # external env: allowed
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # external env: allowed


def overridden():
    return is_set("BENCH_QUANTIZATION")


def scenario_override():
    # Plain WRITES of registered names stay legal: harnesses (bench,
    # perf_gate scenarios) configure the flags they then read through
    # the registry.
    os.environ["BCG_TPU_SPEC"] = "1"
    return get_bool("BCG_TPU_SPEC")
