"""Rows the engine was sent per agent decision made: 1.0 with no retry."""


def read(ctx):
    w = ctx["window"]
    return w["rows"] / w["decisions"]
