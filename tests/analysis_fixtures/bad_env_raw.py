"""BAD: raw environment reads of registered flag names."""
import os

TRACE = os.environ.get("BCG_TPU_TRACE", "") not in ("", "0")  # BCG-ENV-RAW
VERBOSE = os.getenv("VERBOSE") == "1"                           # BCG-ENV-RAW
MODEL = os.environ["BENCH_MODEL"]                               # BCG-ENV-RAW


def overridden():
    return "BENCH_QUANTIZATION" in os.environ                   # BCG-ENV-RAW


def sticky_default():
    return os.environ.setdefault("BCG_TPU_SPEC", "1")           # BCG-ENV-RAW
