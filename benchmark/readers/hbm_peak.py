"""Peak device memory on the fullest chip, GiB."""


def read(ctx):
    return ctx["device"]["memory_peak_bytes"] / 2 ** 30
