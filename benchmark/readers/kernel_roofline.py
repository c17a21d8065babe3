"""A kernel's share of its roofline: the least time the chips could take
for what its calls needed (the larger of operations over peak and bytes
over peak) over the device time of the kernel's events in the trace.
The events are found by the pattern the configuration's file keeps under
``trace_names[names]``; what the calls needed comes from the function of
the configuration's cost model that ``cost`` names."""

from lib import costs, peaks, trace
from readers import work


def read(ctx, names, cost):
    pattern = ctx["config"].get("trace_names", {}).get(names)
    seconds = pattern and trace.seconds_matching(ctx["trace"]["ops_s"], pattern)
    if not seconds:
        return None
    need = work.total(ctx, cost)
    table = peaks.peaks_for(ctx["device"]["kind"])
    chips = ctx["cell"]["chips"]
    least, _bound = costs.roofline_seconds(
        need["flops"], need["bytes"],
        table["bf16_flops"] * chips, table["hbm_bytes_per_s"] * chips)
    return 100.0 * least / seconds
