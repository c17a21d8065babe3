"""GOOD: accessors with registered names only."""
from bcg_tpu.config import env_flag
from bcg_tpu.runtime import envflags

A = envflags.get_bool("BCG_TPU_TRACE")
B = env_flag("BCG_TPU_FINE_SUFFIX")
