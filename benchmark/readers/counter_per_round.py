"""A counter of the program's registry, per game round of the window."""


def read(ctx, counter):
    value = ctx["counters"].get(counter)
    if not value:
        return None
    return value / ctx["window"]["game_rounds"]
