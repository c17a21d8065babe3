"""Seconds of the window's ``bcg.<name>`` spans, for each name of
``spans``, summed and divided by the count of ``bcg.<per>`` spans (for
``engine.guides`` + ``engine.tokenize`` + ``engine.detokenize`` per
``engine.call``: the engine's host work of a call)."""

from lib import program_spans


def read(ctx, spans, per):
    rec = program_spans.record(ctx)
    calls = rec.spans(per)
    if not calls:
        return None
    total = sum(e - s for name in spans for s, e in rec.spans(name))
    return total * program_spans.NS / len(calls)
