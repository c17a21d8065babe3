"""Mean, over the window's ``bcg.<span>`` spans of the program's own
tracer, of the span's seconds less those its ``bcg.<inner>`` spans cover:
the span's self time (for ``round`` less ``engine.call``: the host's own
work of a game round)."""

from lib import program_spans


def read(ctx, span, inner):
    rec = program_spans.record(ctx)
    outer = rec.spans(span)
    if not outer:
        return None
    whole = sum(e - s for s, e in outer)
    return (whole - program_spans.covered(outer, rec.spans(inner))) \
        * program_spans.NS / len(outer)
