"""Seconds of a round in which no engine call was open: the host's own
work (prompt render, parse, exchange, tally), mean over the rounds."""


def read(ctx):
    rounds = ctx["spans"].take("bench.round")
    calls = ctx["spans"].take("bench.engine_call")
    if not rounds:
        return None
    outside = 0.0
    for r0, r1 in rounds:
        inside = sum(min(c1, r1) - max(c0, r0) for c0, c1 in calls
                     if c1 > r0 and c0 < r1)
        outside += (r1 - r0) - inside
    return outside / len(rounds)
