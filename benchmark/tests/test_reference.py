"""The plain reference that the tiny configuration's file names, against
the program at a size a test run can hold: same weights from the same
seed, logits that agree to bfloat16's rounding, and a control (the
precision below the stated one) that does not."""

import json
import os

import numpy as np
import pytest

from lib import correct

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = json.load(open(os.path.join(HERE, "configs", "tiny.json")))
reference = correct.reference_for(TINY)
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def program():
    import jax

    from bcg_tpu.models.configs import spec_for_model
    from bcg_tpu.models.loader import init_random_params_sharded

    spec = spec_for_model(TINY["program"]["model_name"])
    return spec, init_random_params_sharded(spec, jax.random.PRNGKey(SEED))


def test_weights_are_the_loaders_bit_for_bit(program):
    import jax
    import jax.numpy as jnp

    spec, params = program
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4 + 7 * spec.num_layers)
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        mine = reference._leaf(keys[1 + 7 * 1 + 4], (spec.hidden_size, spec.intermediate_size), "bf16")
        head = reference._leaf(keys[1 + 7 * spec.num_layers], (spec.hidden_size, spec.vocab_size), "bf16")
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
    assert np.array_equal(np.asarray(mine), np.asarray(params["layers"][1]["w_gate"].astype(jnp.float32)))
    assert np.array_equal(np.asarray(head), np.asarray(params["lm_head"].astype(jnp.float32)))


def _rows():
    rng = np.random.default_rng(0)
    tokens = np.zeros((2, 512), np.int32)
    lengths = np.array([37, 32])
    for i, n in enumerate(lengths):
        tokens[i, :n] = rng.integers(0, 256, n)
    return tokens, lengths


def test_logits_agree_with_the_programs_prefill(program):
    import jax.numpy as jnp

    from bcg_tpu.models import transformer

    spec, params = program
    tokens, lengths = _rows()
    ref = reference.logits(TINY, SEED, tokens, lengths, 259)
    L = 64
    padded = np.zeros((2, L), np.int32)
    valid = np.zeros((2, L), bool)
    for i, n in enumerate(lengths):          # the engine left-pads
        padded[i, L - n:], valid[i, L - n:] = tokens[i, :n], True
    got, _ = transformer.prefill(params, spec, jnp.asarray(padded), jnp.asarray(valid),
                                 transformer.init_kv_cache(spec, 2, L + 8))
    got = np.asarray(got, np.float32)[:, :259]
    for i, n in enumerate(lengths):
        want = ref[i, n - 1]
        # bfloat16 activations against float32: about 1% of the range
        assert np.abs(got[i] - want).max() < 0.03 * np.abs(want).max()
        assert got[i].argmax() == want.argmax()


def test_control_precision_is_told_apart():
    """int4-rounded weights move the logits by several times what
    bfloat16 arithmetic does: the control cannot pass for the model."""
    tokens, lengths = _rows()
    ref = reference.logits(TINY, SEED, tokens, lengths, 259)
    low = reference.logits(TINY, SEED, tokens, lengths, 259, TINY["control"]["weights"])
    i, n = 0, lengths[0]
    rel = np.abs(low[i, :n] - ref[i, :n]).max() / np.abs(ref[i, :n]).max()
    assert rel > 0.1
