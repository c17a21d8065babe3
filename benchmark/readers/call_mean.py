"""Mean over the window's engine calls of one recorded field (the
engine's own prefill/decode seconds, which end in block_until_ready, or
its decode iterations)."""


def read(ctx, field):
    calls = ctx["calls"]
    if not calls:
        return None
    return sum(getattr(c, field) for c in calls) / len(calls)
