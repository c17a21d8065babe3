"""A program family's share of a peak: what its calls needed over the
device time of the programs whose name matches, from the trace.  The
programs are found by the pattern the configuration's file keeps under
``trace_names[names]``; ``need`` names the function of the
configuration's cost model that counts what a call needed, ``peak`` the
entry of the table of peaks it is held against (``matmul``: the peak of
the configuration's matmul dtype)."""

from lib import peaks, trace
from readers import work


def read(ctx, names, need, peak):
    pattern = ctx["config"].get("trace_names", {}).get(names)
    seconds = pattern and trace.seconds_matching(ctx["trace"]["modules_s"], pattern)
    if not seconds:
        return None
    kind, chips = ctx["device"]["kind"], ctx["cell"]["chips"]
    per_chip = peaks.matmul_peak(kind, ctx["config"]["matmul_dtype"]) \
        if peak == "matmul" else peaks.peaks_for(kind)[peak]
    return 100.0 * work.total(ctx, need) / (seconds * chips * per_chip)
