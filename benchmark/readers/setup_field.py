"""One of set-up's recorded numbers: ``boot_s`` (weights from the seed
plus the engine's constructor), ``compile_s`` (backend compile seconds
up to the end of the first round played, where ``setup_s`` ends, from
jax.monitoring), ``rounds_passed_over`` (rounds of the seed's stream
that were played and not kept because they retried or stopped short:
the program's) or ``rounds_off_band`` (not kept because the game put a
call's prompts off the traffic file's band: nobody's fault)."""


def read(ctx, field):
    return ctx["boot"][field]
