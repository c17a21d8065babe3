"""The harness's own spans: kept in memory, and mirrored into the
profiler's trace (``TraceAnnotation``) so that a traced run carries them
on the device trace's clock."""

from __future__ import annotations

import contextlib
import time

RECORDED: list = []          # (name, t0, t1), host clock, seconds


@contextlib.contextmanager
def span(name: str):
    import jax.profiler

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            RECORDED.append((name, t0, time.perf_counter()))


def take(name: str) -> list:
    return [(t0, t1) for n, t0, t1 in RECORDED if n == name]
