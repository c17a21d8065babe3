"""Seconds of set-up that the program's tracer puts under ``names``:
the union of its intervals of those names that ended by the end of the
first round the process played, where ``setup_s`` ends (an interval
inside another counts once)."""

from lib import program_spans, trace


def read(ctx, names):
    merged = program_spans.record(ctx).in_setup(names)
    if not merged:
        return None
    return trace.total(merged)
