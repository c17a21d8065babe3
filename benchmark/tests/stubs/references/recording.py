"""The reference that ``tests/configs/tiny-named.json`` names: a test's
stand-in for a second architecture's.  It computes what the dense
decoder's reference computes and records that it was the one asked."""

from references import dense_gqa

CALLS: list = []


def logits(cfg, seed, tokens, lengths, cols, weights="bf16"):
    CALLS.append((cfg["name"], weights, tuple(tokens.shape)))
    return dense_gqa.logits(cfg, seed, tokens, lengths, cols, weights)
