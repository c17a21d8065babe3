"""Test configuration.

Run JAX on an 8-device virtual CPU mesh so multi-chip sharding logic
(tp/dp/sp over a Mesh) is exercised hermetically without TPU hardware
(SURVEY.md §4's test-strategy requirement).  Both settings are plain
environment, read by JAX when it is first imported.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
