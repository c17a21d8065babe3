"""Seconds of set-up that the program's tracer puts under ``names``:
the union of its intervals of those names that ended before the window's
first round (a model made twice counts twice; an interval inside
another counts once)."""

from lib import program_spans, trace


def read(ctx, names):
    merged = program_spans.record(ctx).before_window(names)
    if not merged:
        return None
    return trace.total(merged)
