"""One of set-up's recorded numbers: ``boot_s`` (weights from the seed
plus the engine's constructor), ``compile_s`` (backend compile seconds
of the whole set-up, from jax.monitoring) or ``rounds_passed_over``
(rounds of the seed's stream that set-up played and did not keep,
because they retried)."""


def read(ctx, field):
    return ctx["boot"][field]
