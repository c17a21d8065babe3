"""One counter of the program's registry over another, both over the
window (``engine.decode.tokens`` over ``engine.decode.row_steps``: the
decode loop's yield, 1.0 when every row runs to the last step)."""

from lib import program_spans


def read(ctx, numerator, denominator):
    counters = program_spans.record(ctx).counters
    if not counters.get(denominator):
        return None
    return counters.get(numerator, 0) / counters[denominator]
