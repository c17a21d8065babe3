"""The whole window's share of the chips' peak: operations every call
needed by the configuration's cost model (prefill at true prompt
lengths, decode per served token) over
window seconds x chips x the peak of the configuration's matmul dtype."""

from lib import peaks
from readers import work


def read(ctx):
    peak = peaks.matmul_peak(ctx["device"]["kind"], ctx["config"]["matmul_dtype"])
    flops = work.total(ctx, "prefill_flops") + work.total(ctx, "decode_flops")
    return 100.0 * flops / (ctx["window"]["seconds"] * ctx["cell"]["chips"] * peak)
