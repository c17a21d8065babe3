"""The expert layer of ``ops/moe.py`` that the Solar-Open2 block runs:
a chip's share of an expert-parallel layer (the shares add up to the
uncut layer, in the benchmark's own reference of it), and its two
kernels against its XLA form (``tests/test_solar_open2.py`` holds the
whole model against that reference)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.models.quantize import quantize_weight
from bcg_tpu.ops import moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from references import solar_open2 as reference  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


class TestExperts:
    """The expert layer of ``ops/moe.py``: a held share's part."""

    D, F, E = 64, 32, 32

    @pytest.fixture(scope="class")
    def layer(self):
        """A tiny uncut layer of 32 experts in the reference's own weights
        (keys in the recipe's order: router, shared expert, stacks)."""
        keys = jax.random.split(jax.random.PRNGKey(5), 8)
        dims = (self.D, self.E, self.E, 4, self.F, True, 1.0)
        x = jax.random.normal(keys[7], (3, 40, self.D))
        with jax.threefry_partitionable(True):
            mats = {
                "router": reference._matrix(keys[0], (self.D, self.E), "bf16"),
                "shared": [reference._matrix(keys[i], s, "bf16") for i, s in
                           ((1, (self.D, self.F)), (2, (self.D, self.F)), (3, (self.F, self.D)))],
                "stacks": [reference._matrix(keys[i], s, "bf16") for i, s in
                           ((4, (self.E, self.D, self.F)), (5, (self.E, self.D, self.F)),
                            (6, (self.E, self.F, self.D)))],
            }
            whole = reference._moe(x, keys[:7], dims, "bf16", 120)   # every token
        return x, mats, whole

    def _shared(self, h, mats):
        g, u, d = mats["shared"]
        return jnp.dot(jax.nn.silu(h @ g) * (h @ u), d)

    def test_eight_shares_sum_to_the_uncut_layer(self, layer):
        """Eight chips of four experts each, the shared expert (which
        every chip computes alike) counted once: their parts add up to
        the reference's whole layer."""
        x, mats, whole = layer
        h = x.reshape(-1, self.D)
        routing = moe.route(h, mats["router"], 4, True, 1.0)
        parts = sum(
            moe.routed_experts(h, routing, *(s[4 * c: 4 * c + 4] for s in mats["stacks"]),
                               first=4 * c)
            for c in range(8))
        np.testing.assert_allclose((self._shared(h, mats) + parts).reshape(x.shape), whole,
                                   atol=1e-5)
        # one share alone is not the layer
        alone = moe.routed_experts(h, routing, *(s[:4] for s in mats["stacks"]))
        assert float(jnp.abs(alone - parts).max()) > 1e-2

    @pytest.mark.parametrize("decode", [False, True], ids=["grouped", "decode"])
    def test_kernels_are_the_xla_form(self, layer, decode):
        """The grouped int8 matmul (a prefill's tokens, pads left out)
        and the decode kernel (a step's rows) against the XLA form on
        the same int8 stacks: the same products summed in int32."""
        x, mats, _ = layer
        h = x.reshape(-1, self.D)[:10 if decode else None]
        stacks = [quantize_weight(s[8:16]) for s in mats["stacks"]]
        routing = moe.route(h, mats["router"], 4, True, 1.0)
        valid = None if decode else jnp.arange(h.shape[0]) % 5 != 0
        got, want = (moe.routed_experts(h, routing, *stacks, first=8, valid=valid,
                                        decode=decode, impl=impl)
                     for impl in (moe.PALLAS_INTERPRET, moe.XLA))
        np.testing.assert_allclose(got, want, atol=1e-5)
        if valid is not None:
            assert float(jnp.abs(got[~valid]).max()) == 0.0

    @pytest.mark.parametrize("decode", [False, True], ids=["grouped", "decode"])
    def test_kernels_at_the_published_width(self, decode):
        """Hidden width 4096: ``W_down`` in two column blocks, a tile past
        the last live one mapped onto the last live tile's blocks.  The
        interpreter the kernels run in here refuses a block that lies
        past an array's end, as the chip's bounds check does."""
        keys = jax.random.split(jax.random.PRNGKey(7), 5)
        D, F, held = 4096, 128, 4
        h = jax.random.normal(keys[0], (10 if decode else 64, D)).astype(jnp.bfloat16)
        router = jax.random.normal(keys[1], (D, 16)) / 64
        stacks = [quantize_weight(jax.random.normal(k, s) / 64) for k, s in
                  zip(keys[2:], ((2, held, D, F), (2, held, D, F), (2, held, F, D)))]
        routing = moe.route(h, router, 4, True, 1.0)
        got, want = (moe.routed_experts(h, routing, *stacks, first=4, decode=decode,
                                        impl=impl, layer=jnp.int32(1))
                     for impl in (moe.PALLAS_INTERPRET, moe.XLA))
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_a_layer_of_a_stack_is_read_in_place(self, layer):
        """Stacks of every layer and the layer's index give what that
        layer's own stacks give, in both forms."""
        x, mats, _ = layer
        h = x.reshape(-1, self.D)[:10]
        routing = moe.route(h, mats["router"], 4, True, 1.0)
        one = [quantize_weight(s[:8]) for s in mats["stacks"]]
        two = [jax.tree.map(lambda a, b: jnp.stack([b, a]), o,
                            quantize_weight(s[8:16])) for o, s in zip(one, mats["stacks"])]
        for impl in (moe.XLA, moe.PALLAS_INTERPRET):
            np.testing.assert_allclose(
                moe.routed_experts(h, routing, *two, decode=True, impl=impl,
                                   layer=jnp.int32(1)),
                moe.routed_experts(h, routing, *one, decode=True, impl=impl), atol=1e-6)

    def test_prefill_layout_rows(self):
        cw = jnp.array([[0.5, 0.0, 0.5], [0.0, 0.0, 1.0], [0.25, 0.0, 0.0]])
        row_token, row_weight, tile_expert, live = moe.prefill_layout(cw, cw > 0, 2)
        # rows: expert 0's two tokens, then expert 2's two, each group a tile
        assert row_token.shape == ((1 + 3) * moe.ROWS,)
        assert list(np.asarray(row_token[:2])) == [0, 2]
        assert list(np.asarray(row_token[moe.ROWS: moe.ROWS + 2])) == [0, 1]
        assert int(live[0]) == 2 and list(np.asarray(tile_expert[:2])) == [0, 2]
        assert float(row_weight.sum()) == pytest.approx(2.25)
