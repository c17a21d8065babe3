"""Of the device's idle seconds in the traced window, the share that the
program's spans cannot name: idle time during which the innermost
``bcg.*`` span is one of ``containers`` (spans that only hold other
spans' work: inside ``engine.call`` but in none of its children), or
during which no span is open at all.  An idle gap is cut where a span
starts or ends, and each piece goes to the span that holds it."""

from lib import program_spans


def read(ctx, containers):
    rec = program_spans.record(ctx)
    spans = rec.program_spans()
    if not spans or not rec.device:
        return None
    edges = sorted({t for _n, s, e in spans for t in (s, e)})
    idle = unnamed = 0.0
    for s, e in program_spans.idle_gaps(rec):
        cuts = [s] + [t for t in edges if s < t < e] + [e]
        for a, b in zip(cuts, cuts[1:]):
            name = program_spans.innermost(spans, (a + b) / 2)
            if name is None or name[len(program_spans.PREFIX):] in containers:
                unnamed += b - a
        idle += e - s
    return 100.0 * unnamed / idle if idle else 0.0
