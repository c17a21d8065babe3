"""Where a configuration's cost model is found, and the roofline.

A configuration's file names its cost model (``"costs": "<name>"``):
``benchmark/costs/<name>.py`` holds the operations and bytes its model
needs, from shapes alone.  Every such module offers ``prefill_flops(cfg,
call)`` and ``decode_flops(cfg, call)`` (the whole step's operations,
which ``round_mfu_pct`` reads) and whatever its kernels' metric files
name under ``need`` or ``cost``, all with the one calling convention:
the configuration's dict and one recorded call as
``readers/work.py::recorded`` gives it (``prompt_lens``, ``passes``,
``steps``).  A ``need`` returns a number, a ``cost`` a dict of ``flops``
and ``bytes``.
"""

from __future__ import annotations

import importlib


def module_for(config: dict):
    """The cost model the configuration's file names (``costs/<name>.py``)."""
    return importlib.import_module("costs." + config["costs"])


def roofline_seconds(flops: int, nbytes: int, peak_flops: float, peak_bw: float) -> tuple:
    """Least time the chip could take, and which roof sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
