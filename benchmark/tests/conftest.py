"""The benchmark's own tests: they run on the CPU (``python -m pytest
benchmark/tests``), at the tiny sizes under ``tests/configs`` and
``tests/traffic``; they measure nothing."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))   # the program
sys.path.insert(0, BENCH)                    # lib, readers, run
# what tests/configs/tiny-named.json names: references.recording, costs.recording
sys.path.insert(0, os.path.join(HERE, "stubs"))
