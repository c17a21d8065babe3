"""The whole window's share of the chips' peak: operations every call
needed (prefill at true prompt lengths, decode per served token) over
window seconds x chips x the peak of the configuration's matmul dtype."""

from lib import peaks
from readers import work


def read(ctx):
    peak = peaks.matmul_peak(ctx["device"]["kind"], ctx["config"]["matmul_dtype"])
    flops = work.prefill_flops(ctx) + work.decode_flops(ctx)
    return 100.0 * flops / (ctx["window"]["seconds"] * ctx["cell"]["chips"] * peak)
