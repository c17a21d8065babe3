"""Device seconds a round of the operations in the model parts
``scopes`` (the innermost ``bcg.*`` segment of each operation's name
stack, ``lib/scopes.py``); with ``scopes`` null, the share of the
window's operation seconds that carries no part.  None where no
operation of the window carries a part: a program without the scopes."""

from lib import scopes as lib_scopes


def read(ctx, scopes):
    table = lib_scopes.seconds(ctx)
    if table is None:
        return None
    if scopes is None:
        return table.get(None, 0.0) / sum(table.values())
    return sum(table.get(s, 0.0) for s in scopes) / ctx["window"]["game_rounds"]
