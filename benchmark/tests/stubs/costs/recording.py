"""The cost model that ``tests/configs/tiny-named.json`` names: a test's
stand-in for a second architecture's.  Every function a metric's file or
``round_mfu_pct`` asks for is the dense decoder's, with a record of the
asking."""

from costs import dense_gqa

CALLS: list = []


def __getattr__(name):
    fn = getattr(dense_gqa, name)

    def recorded(cfg, call):
        CALLS.append((name, cfg["name"]))
        return fn(cfg, call)

    return recorded
