"""The 8B path's kernels, compiled by the installed TPU compiler for a
DESCRIBED v5e:2x2 — no chip attached (on-chip-measurement guide §2.3).

Interpret mode and the cross-lowering census never reach Mosaic; these
do, at the real widths (Qwen3-8B geometry: H=32, Hkv=8, Dh=128, vocab
151,936, B=10 agents), so a kernel the chip's compiler would refuse
fails here first.  A compile that passes is not a chip run — nothing
executes — and asserts only that the program holds a ``tpu_custom_call``
(the kernel is really in it, not an XLA stand-in).

Sorts last on purpose: these cases must not push other tier-1 tests
past the suite's wall-clock cut.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else compiler logs land in /tmp

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

B, H, HKV, DH, VOCAB = 10, 32, 8, 128, 151_936
# 3072-slot suffix bucket + 300-token fast-forward tail, ALIGN_S-rounded.
S_CACHE = 4096
SCALE = 1.0 / np.sqrt(DH)


@pytest.fixture(scope="module")
def topo():
    """One described v5e:2x2 for the whole file, with the persistent
    compile cache off around it: a described-device compile is written
    to the cache but cannot be read back without a chip, so later
    compiles would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no compile-only client here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, shardings, *shapes):
    """Lower + compile ``fn`` for described devices; ``shapes`` are
    ``(shape, dtype)`` pairs placed by the matching entry of
    ``shardings`` (one sharding = the same for every operand)."""
    if not isinstance(shardings, (tuple, list)):
        shardings = (shardings,) * len(shapes)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
        for (shape, dtype), sh in zip(shapes, shardings)
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _int8_cache_shapes(rows_shape):
    return (
        rows_shape,
        ((B, HKV, S_CACHE, DH), jnp.int8), ((B, HKV, S_CACHE, DH), jnp.int8),
        ((B, HKV, S_CACHE), jnp.float32), ((B, HKV, S_CACHE), jnp.float32),
    )


def _decode_int8(q, k, v, ks, vs, mask, mesh=None, layer=None):
    from bcg_tpu.ops.decode_attention import decode_attention

    return decode_attention(q, k, v, mask, SCALE, k_scale=ks, v_scale=vs,
                            mesh=mesh, layer=layer)


def _chunk_int8(q, k, v, ks, vs, mask, mesh=None):
    from bcg_tpu.ops.decode_attention import chunk_decode_attention

    return chunk_decode_attention(q, k, v, mask, SCALE, k_scale=ks,
                                  v_scale=vs, mesh=mesh)


def _flash(q, k, v, mask, mesh=None):
    from bcg_tpu.ops.attention import flash_attention

    return flash_attention(q, k, v, mask, SCALE, mesh=mesh)


class TestOneChip:
    """Each kernel alone on one described chip."""

    @pytest.fixture
    def one(self, topo):
        return SingleDeviceSharding(topo.devices[0])

    def test_flash_prefill_chunk(self, one):
        # One 512-token prefill chunk against a 3072-slot history: the
        # 8B size class's chunked-prefill attention shape.
        T, S = 512, 3072 + 512
        _compile(
            _flash, one,
            ((B, T, H, DH), jnp.bfloat16), ((B, S, HKV, DH), jnp.bfloat16),
            ((B, S, HKV, DH), jnp.bfloat16), ((B, T, S), jnp.bool_),
        )

    def test_int8_decode(self, one):
        _compile(
            _decode_int8, one,
            *_int8_cache_shapes(((B, H, DH), jnp.bfloat16)),
            ((B, S_CACHE), jnp.bool_),
        )

    def test_int8_chunk_decode(self, one):
        from bcg_tpu.guided.processor import FF_CHUNK

        _compile(
            _chunk_int8, one,
            *_int8_cache_shapes(((B, FF_CHUNK, H, DH), jnp.bfloat16)),
            ((B, FF_CHUNK, S_CACHE), jnp.bool_),
        )

    def test_int8_decode_30_kv_heads(self, one):
        # An MHA model's full-attention layers (30 KV heads of 128,
        # group 1): the all-heads kernel holds every head's K and V
        # block at once (at a 1024 block that was over a v5e's scoped
        # VMEM; the block follows the heads held, _pick_block).
        hkv, s_cache = 30, 5120
        _compile(
            _decode_int8, one,
            ((B, hkv, DH), jnp.bfloat16),
            ((B, hkv, s_cache, DH), jnp.int8), ((B, hkv, s_cache, DH), jnp.int8),
            ((B, hkv, s_cache), jnp.float32), ((B, hkv, s_cache), jnp.float32),
            ((B, s_cache), jnp.bool_),
        )

    @pytest.mark.parametrize("ranged", ["by_the_kernel", "by_the_caller"])
    @pytest.mark.parametrize("hkv, rows", [(8, 4), (30, 1)])
    def test_int8_decode_reads_its_layer_of_a_stack(self, one, hkv, rows, ranged):
        # The layer scan's form: the whole stacked cache as the operand,
        # the layer index scalar-prefetched, the layer's blocks found by
        # the K/V/scale index maps (no slice of the layer beforehand).
        # Both cells' geometry: 8 KV heads of group 4 and 30 of group 1,
        # at the block the kernel picks for both (256: 20 grid steps a row).  The output shape is how the benchmark's
        # ``trace_names.decode_attention`` finds the kernel.
        # The kernel is bounded to each row's live blocks, which ride the
        # scalar prefetch beside the layer index and steer every S-axis
        # index map: read off the mask in the call, or handed over with
        # it (``LiveMask``: the decode step reduces the mask once, outside
        # the layer scan).
        from bcg_tpu.ops.decode_attention import (
            LiveMask, decode_attention, kernel_block,
        )

        layers, s_cache = 4, 5120
        assert kernel_block(hkv, DH) == 256

        def stacked(q, k, v, ks, vs, mask, layer, *slots):
            return decode_attention(
                q, k, v, LiveMask(mask, *slots) if slots else mask, SCALE,
                k_scale=ks, v_scale=vs, layer=layer)

        kv = ((layers, B, hkv, s_cache, DH), jnp.int8)
        sc = ((layers, B, hkv, s_cache), jnp.float32)
        text = _compile(
            stacked, one, ((B, hkv * rows, DH), jnp.bfloat16), kv, kv, sc, sc,
            ((B, s_cache), jnp.bool_), ((), jnp.int32),
            *([((2, B), jnp.int32)] if ranged == "by_the_caller" else []),
        ).as_text()
        assert f"bf16[{B},{hkv},{rows},{DH}]" in text
        # nothing the size of a layer is produced on the way to the kernel
        assert f"s8[{B},{hkv},{s_cache},{DH}]" not in text

    def test_gated_delta_prefill_chunk(self, one):
        # One 512-token prefill chunk of a delta-rule layer at the
        # hybrid's published widths: 30 heads, key dim 96, value dim 192
        # (neither a multiple of the 128 lanes), float32 state.
        from bcg_tpu.ops import gated_delta

        heads, dk, dv, T = 30, 96, 192, 512

        def chunk(q, k, v, g, beta, S):
            return gated_delta.gated_delta_prefill(
                q, k, v, g, beta, S, impl=gated_delta.PALLAS)

        _compile(
            chunk, one,
            ((B, T, heads, dk), jnp.bfloat16), ((B, T, heads, dk), jnp.bfloat16),
            ((B, T, heads, dv), jnp.bfloat16), ((B, T, heads), jnp.float32),
            ((B, T, heads), jnp.float32), ((B, heads, dv, dk), jnp.float32),
        )

    def test_kda_prefill_chunk(self, one):
        # One 512-token prefill chunk of a KDA layer at Solar-Open2's
        # widths: 64 heads of 128, a decay per key channel, float32 state.
        from bcg_tpu.ops import gated_delta

        heads, d, T = 64, 128, 512

        def chunk(q, k, v, g, beta, S):
            return gated_delta.kda_prefill(q, k, v, g, beta, S, impl=gated_delta.PALLAS)

        text = _compile(
            chunk, one,
            ((B, T, heads, d), jnp.bfloat16), ((B, T, heads, d), jnp.bfloat16),
            ((B, T, heads, d), jnp.bfloat16), ((B, T, heads, d), jnp.float32),
            ((B, T, heads), jnp.float32), ((B, heads, d, d), jnp.float32),
        ).as_text()
        assert f"f32[{B},{heads},{T},{d}]" in text   # how trace_names finds it

    def test_mamba_prefill_chunk(self, one):
        # One 512-token prefill chunk of a Mamba layer at Jamba2-3B's
        # widths: 5,120 channels, state 16 a channel, float32 state.
        from bcg_tpu.ops import selective_scan

        C, N, T = 5120, 16, 512

        def chunk(u, delta, Bm, Cm, A, D, S):
            return selective_scan.mamba_prefill(u, delta, Bm, Cm, A, D, S,
                                                impl=selective_scan.PALLAS)

        text = _compile(
            chunk, one,
            ((B, T, C), jnp.float32), ((B, T, C), jnp.float32),
            ((B, T, N), jnp.float32), ((B, T, N), jnp.float32),
            ((C, N), jnp.float32), ((C,), jnp.float32), ((B, N, C), jnp.float32),
        ).as_text()
        assert list(_kernels(text)) == ["mamba_prefill"]
        # the [T, N, C] expansion of the discretised A and B is never made
        assert f"f32[{B},{T},{N},{C}]" not in text and f"f32[{B},{T},{C},{N}]" not in text

    @pytest.mark.parametrize("phase", ["flash", "decode"])
    def test_mqa_group_of_20(self, one, phase):
        # Jamba2-3B's attention: 20 query heads on one KV head of 128.
        # Flash prefill takes the whole group's query block in each grid
        # step; the decode kernel pads the group's 20 rows to 32.
        from bcg_tpu.ops.decode_attention import LiveMask, decode_attention

        hq, hkv, T, s_cache = 20, 1, 512, 5120
        if phase == "flash":
            text = _compile(
                _flash, one,
                ((B, T, hq, DH), jnp.bfloat16), ((B, 2048 + T, hkv, DH), jnp.bfloat16),
                ((B, 2048 + T, hkv, DH), jnp.bfloat16), ((B, T, 2048 + T), jnp.bool_),
            ).as_text()
            assert f"bf16[{B},{hq},{T},{DH}]" in text
            return

        def stacked(q, k, v, ks, vs, mask, layer, slots):
            return decode_attention(q, k, v, LiveMask(mask, slots), SCALE,
                                    k_scale=ks, v_scale=vs, layer=layer)

        kv = ((2, B, hkv, s_cache, DH), jnp.int8)
        sc = ((2, B, hkv, s_cache), jnp.float32)
        text = _compile(
            stacked, one, ((B, hq, DH), jnp.bfloat16), kv, kv, sc, sc,
            ((B, s_cache), jnp.bool_), ((), jnp.int32), ((2, B), jnp.int32),
        ).as_text()
        assert f"bf16[{B},{hkv},32,{DH}]" in text

    @pytest.mark.parametrize("decode", [False, True], ids=["grouped", "decode"])
    def test_expert_kernels(self, one, decode):
        # The held experts of Solar-Open2's share, 40 of 320 (top 8),
        # SwiGLU 4096 -> 1280 -> 4096 int8, read out of a 9-layer stack by
        # the layer index: a 512-token chunk of 10 rows through the
        # grouped matmul, or a decode step's 10 rows.
        from bcg_tpu.ops import moe

        layers, held, D, F, E, n = 9, 40, 4096, 1280, 320, B if decode else B * 512

        def experts(h, router, gq, gs, uq, us, dq, ds, layer):
            routing = moe.route(h, router, 8, True, 1.0)
            return moe.routed_experts(
                h, routing, {"q": gq, "scale": gs}, {"q": uq, "scale": us},
                {"q": dq, "scale": ds}, decode=decode, impl=moe.PALLAS, layer=layer)

        up = ((layers, held, D, F), jnp.int8), ((layers, held, F), jnp.float32)
        text = _compile(
            experts, one, ((n, D), jnp.bfloat16), ((D, E), jnp.bfloat16), *up, *up,
            ((layers, held, F, D), jnp.int8), ((layers, held, D), jnp.float32),
            ((), jnp.int32),
        ).as_text()
        # nothing the size of a layer's experts is produced on the way
        assert f"s8[{held},{D},{F}]" not in text

    @pytest.mark.parametrize("top_p", [1.0, 0.9])
    def test_fused_sampler_qwen_vocab(self, one, top_p):
        from bcg_tpu.ops import guided_sampler as gs

        _, vs = gs.vocab_rows(VOCAB)
        n_dfa, n_states = 2, 64

        def sample(logits3, minb4, meta_i, meta_f, dfa_ids, states):
            return gs._sampler_call(
                logits3, minb4, meta_i, meta_f, dfa_ids, states,
                eos_id=151_645, top_p=top_p, vocab=VOCAB, interpret=False,
            )

        _compile(
            sample, one,
            ((B, vs, 128), jnp.float32),
            ((n_dfa, n_states, vs, 128), jnp.int16),
            ((B, 1, 4), jnp.int32), ((B, 1, 2), jnp.float32),
            ((B,), jnp.int32), ((B,), jnp.int32),
        )

    @pytest.mark.parametrize("quantized", [False, "int8"])
    def test_paged_decode(self, one, quantized):
        from bcg_tpu.ops.paged_attention import (
            PALLAS, init_block_pool, paged_decode_attention,
        )

        bs, nblk, n_blocks = 16, S_CACHE // 16, 4096
        pool = jax.eval_shape(
            lambda: init_block_pool(
                _Spec, n_blocks, bs, quantized=quantized
            )[0]
        )

        def attend(q, tbl, mask, *leaves):
            entry = dict(zip(sorted(pool), leaves), tbl=tbl)
            return paged_decode_attention(q, entry, mask, SCALE, impl=PALLAS)

        _compile(
            attend, one,
            ((B, 1, H, DH), jnp.bfloat16), ((B, nblk), jnp.int32),
            ((B, nblk * bs), jnp.bool_),
            *[(pool[k].shape, pool[k].dtype) for k in sorted(pool)],
        )


# A Pallas call's line of a compiled program: its instruction name.
_CUSTOM_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*custom_call_target="tpu_custom_call"')


def _kernels(text: str) -> dict:
    """``{kernel name: [op_name, ...]}`` of a compiled program's Pallas
    calls (the instruction name less its ``.N``)."""
    out = {}
    for line in text.splitlines():
        m = _CUSTOM_CALL.match(line)
        if m:
            op_name = re.search(r'op_name="([^"]*)"', line)
            out.setdefault(m.group(1).split(".")[0], []).append(
                op_name and op_name.group(1))
    return out


class TestKernelParts:
    """The device trace names each kernel by its HLO instruction, and
    reads its model part from the op_name stack (bcg_tpu/obs/scopes.py):
    every kernel on the cells' path has a name of its own, which the
    configurations' shape-keyed ``trace_names`` still match, and sits
    under its own part when the model calls it."""

    @pytest.fixture
    def one(self, topo):
        return SingleDeviceSharding(topo.devices[0])

    @pytest.mark.parametrize("kernel", ["flash_prefill", "decode_attention", "guided_sampler",
                                        "mamba_prefill"])
    def test_named_kernels_keep_their_trace_patterns(self, one, kernel):
        import json
        import sys

        from bcg_tpu.ops import guided_sampler as gs

        bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                             "benchmark")
        sys.path.insert(0, bench)
        from lib import trace

        if kernel == "flash_prefill":
            T, S = 512, 3072 + 512
            text = _compile(
                _flash, one,
                ((B, T, H, DH), jnp.bfloat16), ((B, S, HKV, DH), jnp.bfloat16),
                ((B, S, HKV, DH), jnp.bfloat16), ((B, T, S), jnp.bool_)).as_text()
        elif kernel == "decode_attention":
            text = _compile(
                _decode_int8, one, *_int8_cache_shapes(((B, H, DH), jnp.bfloat16)),
                ((B, S_CACHE), jnp.bool_)).as_text()
        elif kernel == "mamba_prefill":
            from bcg_tpu.ops import selective_scan

            C, N, T = 5120, 16, 512
            text = _compile(
                functools.partial(selective_scan.mamba_prefill, impl=selective_scan.PALLAS),
                one, ((B, T, C), jnp.float32), ((B, T, C), jnp.float32),
                ((B, T, N), jnp.float32), ((B, T, N), jnp.float32),
                ((C, N), jnp.float32), ((C,), jnp.float32), ((B, N, C), jnp.float32)).as_text()
        else:
            _, vs = gs.vocab_rows(VOCAB)
            text = _compile(
                functools.partial(gs._sampler_call, eos_id=151_645, top_p=1.0,
                                  vocab=VOCAB, interpret=False), one,
                ((B, vs, 128), jnp.float32), ((2, 64, vs, 128), jnp.int16),
                ((B, 1, 4), jnp.int32), ((B, 1, 2), jnp.float32),
                ((B,), jnp.int32), ((B,), jnp.int32)).as_text()
        assert list(_kernels(text)) == [kernel]
        line = next(re.sub(r"^ROOT\s+", "", ln.strip()) for ln in text.splitlines()
                    if re.match(rf"\s*(ROOT\s+)?%{kernel}(\.\d+)? = ", ln))
        event = trace.op_name(line)
        assert event.startswith(kernel)
        for name in ("qwen3-8b-int8", "olmo-hybrid-7b-int8", "solar-open2-250b-int8",
                     "jamba2-3b-bf16"):
            with open(os.path.join(bench, "configs", name + ".json")) as f:
                patterns = json.load(f)["trace_names"]
            found = [k for k in ("flash_prefill", "decode_attention", "mamba_prefill")
                     if k in patterns and re.search(patterns[k], event)]
            assert found == ([] if kernel == "guided_sampler"
                             else [kernel] if kernel in patterns else []), (name, event)

    # (preset, layer index, phase) -> the kernels the layer calls
    CASES = {
        ("bcg-tpu/tiny-test", 0, "prefill"): {"flash_prefill"},
        ("bcg-tpu/tiny-test", 0, "decode"): {"decode_attention"},
        ("bcg-tpu/tiny-hybrid", 0, "prefill"): {"gated_delta_prefill"},
        ("bcg-tpu/tiny-hybrid", 3, "decode"): {"decode_attention"},
        ("bcg-tpu/tiny-solar", 0, "prefill"): {"flash_prefill", "moe_prefill"},
        ("bcg-tpu/tiny-solar", 1, "prefill"): {"kda_prefill", "moe_prefill"},
        ("bcg-tpu/tiny-solar", 0, "decode"): {"decode_attention", "moe_decode"},
        ("bcg-tpu/tiny-jamba", 0, "prefill"): {"mamba_prefill"},
        ("bcg-tpu/tiny-jamba", 7, "prefill"): {"flash_prefill"},
        ("bcg-tpu/tiny-jamba", 7, "decode"): {"decode_attention"},
    }
    PART = {"flash_prefill": "bcg.attn", "decode_attention": "bcg.attn",
            "gated_delta_prefill": "bcg.linear_mixer", "kda_prefill": "bcg.linear_mixer",
            "mamba_prefill": "bcg.linear_mixer",
            "moe_prefill": "bcg.ffn", "moe_decode": "bcg.ffn"}

    @pytest.mark.parametrize("case", list(CASES), ids=lambda c: f"{c[0][8:]}-{c[1]}-{c[2]}")
    def test_each_kernel_sits_under_its_part(self, one, case):
        """One layer of the tiny preset, its heads widened to the 128
        lanes the kernels tile, W8A8 with an int8 cache, compiled as the
        chunk program or the decode loop calls it with the Pallas
        kernels."""
        import dataclasses

        from bcg_tpu.models import transformer as T
        from bcg_tpu.models.configs import MODEL_SPECS
        from bcg_tpu.models.quantize import quantize_leaf_transform
        from bcg_tpu.obs import scopes
        from bcg_tpu.ops import HybridImpl, gated_delta, moe, selective_scan

        preset, li, phase = case
        spec = dataclasses.replace(
            MODEL_SPECS[preset], head_dim=128, use_rope=False,
            **({"linear_key_head_dim": 128, "linear_value_head_dim": 128}
               if MODEL_SPECS[preset].hybrid else {}))
        rows, Tq, hist = 2, (1 if phase == "decode" else 128), 128
        S = hist + 128
        params = jax.eval_shape(lambda: T.init_params(
            spec, jax.random.PRNGKey(0), leaf_transform=quantize_leaf_transform(spec, "int8")))
        cache = jax.eval_shape(lambda: T.init_kv_cache(spec, rows, S, quantized="int8"))
        layer, entry = params["layers"][li], cache[li]
        mask_shape = (rows, S) if phase == "decode" else (rows, Tq, hist + Tq)
        prefill_phase = phase == "prefill"
        impl = (HybridImpl("pallas", gated_delta.PALLAS if prefill_phase else "xla", moe.PALLAS,
                           selective_scan.PALLAS if prefill_phase else "xla")
                if spec.hybrid else "pallas")

        def run(layer, x, entry, mask, valid):
            if not spec.hybrid:
                return T._block(layer, spec, x, None, None, jnp.int32(hist), entry,
                                mask, impl, hist_len=0 if phase == "decode" else hist)
            ctx = T._Ctx(None, None, jnp.int32(hist), mask, 0 if phase == "decode" else hist,
                         None if phase == "decode" else valid, impl)
            return T._hybrid_layer(spec.layer_types[li], layer, spec, x, entry, ctx)

        place = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
        text = jax.jit(run).lower(
            place(layer), place(jax.ShapeDtypeStruct((rows, Tq, spec.hidden_size), jnp.bfloat16)),
            place(entry), place(jax.ShapeDtypeStruct(mask_shape, jnp.bool_)),
            place(jax.ShapeDtypeStruct((rows, Tq), jnp.bool_)),
        ).compile().as_text()
        kernels = _kernels(text)
        assert set(kernels) == self.CASES[case]
        for kernel, op_names in kernels.items():
            assert {scopes.part(n or "") for n in op_names} == {self.PART[kernel]}, kernel


class _Spec:
    """The slice of ModelSpec init_block_pool reads, at 8B widths with
    one layer (a kernel sees one layer's pool entry)."""

    num_layers, num_kv_heads, head_dim = 1, HKV, DH


class TestFourChips:
    """The tp=4 forms: each kernel inside shard_map over a Mesh of the
    described devices, operands head-sharded as GSPMD hands them over."""

    @pytest.fixture
    def mesh(self, topo):
        return Mesh(np.asarray(topo.devices).reshape(1, 4, 1),
                    ("dp", "tp", "sp"))

    def test_flash_prefill_tp4(self, mesh):
        T, S = 512, 3072 + 512
        heads = NamedSharding(mesh, P(None, None, "tp", None))
        compiled = _compile(
            functools.partial(_flash, mesh=mesh),
            (heads, heads, heads, NamedSharding(mesh, P())),
            ((B, T, H, DH), jnp.bfloat16), ((B, S, HKV, DH), jnp.bfloat16),
            ((B, S, HKV, DH), jnp.bfloat16), ((B, T, S), jnp.bool_),
        )
        # Heads are independent: the kernel needs no collective.
        assert "all-gather" not in compiled.as_text()

    @pytest.mark.parametrize("layers", [(), (2,)], ids=["entry", "stacked"])
    def test_int8_decode_tp4(self, mesh, layers):
        # ``stacked``: the layer scan's operands, a leading [Lyr] axis
        # that replicates and a prefetched layer index.
        lead = (None,) * len(layers)
        kv = NamedSharding(mesh, P(*lead, None, "tp", None, None))
        sc = NamedSharding(mesh, P(*lead, None, "tp", None))
        q, *cache = _int8_cache_shapes(((B, H, DH), jnp.bfloat16))
        fn = functools.partial(_decode_int8, mesh=mesh)
        if layers:
            fn = functools.partial(fn, layer=1)
        _compile(
            fn,
            (NamedSharding(mesh, P(None, "tp", None)), kv, kv, sc, sc,
             NamedSharding(mesh, P())),
            q, *[(layers + shape, dtype) for shape, dtype in cache],
            ((B, S_CACHE), jnp.bool_),
        )

    def test_fused_sampler_tp4(self, mesh):
        """The whole guided-sampler closure with logits arriving
        vocab-sharded, as the tp lm_head leaves them."""
        from bcg_tpu.ops.guided_sampler import make_fused_sampler

        case = jax.eval_shape(lambda: _sampler_case(VOCAB, n_states=64, b=B))
        names = sorted(case)  # any fixed order; operands go by keyword
        sample = make_fused_sampler(151_645, 0.9, mesh=mesh)
        rep = NamedSharding(mesh, P())
        _compile(
            lambda logits, *rest: sample(logits, **dict(zip(names, rest)))[:2],
            (NamedSharding(mesh, P(None, "tp")),) + (rep,) * len(names),
            ((B, VOCAB), jnp.float32),
            *[(case[n].shape, case[n].dtype) for n in names],
        )

    def test_int8_chunk_decode_tp4(self, mesh):
        from bcg_tpu.guided.processor import FF_CHUNK

        kv = NamedSharding(mesh, P(None, "tp", None, None))
        sc = NamedSharding(mesh, P(None, "tp", None))
        _compile(
            functools.partial(_chunk_int8, mesh=mesh),
            (NamedSharding(mesh, P(None, None, "tp", None)), kv, kv, sc, sc,
             NamedSharding(mesh, P())),
            *_int8_cache_shapes(((B, FF_CHUNK, H, DH), jnp.bfloat16)),
            ((B, FF_CHUNK, S_CACHE), jnp.bool_),
        )


def _sampler_case(vocab, n_dfa=2, n_states=8, b=4, seed=0):
    """Concrete operands of a guided sampler call (make_masked_sampler
    signature after ``logits``), greedy and sampled rows mixed."""
    rng = np.random.default_rng(seed)
    tables = rng.integers(-1, n_states, (n_dfa, n_states, vocab)).astype(np.int16)
    minb = np.where(tables >= 0, rng.integers(1, 9, tables.shape), 32767)
    return dict(
        states=jnp.asarray(rng.integers(0, n_states, b), jnp.int32),
        rng=jax.random.PRNGKey(seed),
        emitted=jnp.zeros((b,), jnp.int32),
        tables=jnp.asarray(tables),
        accepting=jnp.asarray(rng.random((n_dfa, n_states)) < 0.3),
        min_budget=jnp.asarray(minb.astype(np.int16)),
        dfa_ids=jnp.asarray(rng.integers(0, n_dfa, b), jnp.int32),
        row_temp=jnp.asarray([0.0, 0.7] * (b // 2), jnp.float32),
        row_budget=jnp.full((b,), 16, jnp.int32),
    )


class TestShardedKernelsOnCpuMesh:
    """The same shard_map wrappers on four virtual CPU devices, kernels
    in interpret mode: head-sharded results equal the unsharded call
    (what a described-device compile cannot show — nothing runs there)."""

    @pytest.fixture
    def mesh(self):
        return Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4, 1),
                    ("dp", "tp", "sp"))

    def test_flash_matches_unsharded(self, mesh):
        from bcg_tpu.ops.attention import flash_attention

        b, t, s, h, hkv = 2, 128, 256, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, t, h, DH), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, DH), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, DH), jnp.float32)
        mask = jnp.tril(jnp.ones((t, s), bool), k=s - t)[None].repeat(b, 0)
        ref = flash_attention(q, k, v, mask, SCALE, interpret=True)
        out = jax.jit(functools.partial(
            flash_attention, scale=SCALE, mesh=mesh, interpret=True,
        ))(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)

    @pytest.mark.parametrize("dp", [1, 2])
    @pytest.mark.parametrize("stacked", [False, True])
    @pytest.mark.parametrize("chunk", [False, True])
    def test_int8_decode_matches_unsharded(self, chunk, stacked, dp):
        # Rows of different live ranges (blocks 0-1 and, behind a left
        # pad, block 1 alone): the prefetched range is a column a batch
        # row, so under ``dp`` it splits with the batch (``dp=2``: each
        # device must find its own row's range in column 0).
        from bcg_tpu.ops.decode_attention import (
            chunk_decode_attention, decode_attention, quantize_kv,
        )

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(dp, 4 // dp, 1),
                    ("dp", "tp", "sp"))
        b, s, h, hkv, kk = 2, 256, 8, 4, 4
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(
            ks[0], (b, kk, h, DH) if chunk else (b, h, DH), jnp.float32)
        kq, ksc = quantize_kv(jax.random.normal(ks[1], (b, hkv, s, DH)))
        vq, vsc = quantize_kv(jax.random.normal(ks[2], (b, hkv, s, DH)))
        slots = jnp.arange(s)[None, :]
        mask = ((slots >= jnp.asarray([0, 150])[:, None])
                & (slots < jnp.asarray([200, 240])[:, None]))
        if chunk:
            mask = mask[:, None, :].repeat(kk, 1)
        fn = functools.partial(
            chunk_decode_attention if chunk else decode_attention,
            scale=SCALE, block_s=128, interpret=True,
        )
        ref = fn(q, kq, vq, mask, k_scale=ksc, v_scale=vsc)
        if stacked:   # the entry as layer 1 of a stack whose layer 0 is zeros
            kq, vq, ksc, vsc = (
                jnp.stack([jnp.zeros_like(a), a]) for a in (kq, vq, ksc, vsc))
            fn = functools.partial(fn, layer=1)
        out = jax.jit(functools.partial(fn, mesh=mesh))(
            q, kq, vq, mask, k_scale=ksc, v_scale=vsc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)

    def test_fused_sampler_matches_unsharded(self, mesh):
        from bcg_tpu.ops.guided_sampler import make_fused_sampler

        vocab = 1024
        case = _sampler_case(vocab)
        logits = jax.random.normal(jax.random.PRNGKey(2), (4, vocab))
        ref = make_fused_sampler(7, 0.9, interpret=True)(logits, **case)
        out = jax.jit(
            make_fused_sampler(7, 0.9, interpret=True, mesh=mesh)
        )(logits, **case)
        for a, b in zip(out[:2], ref[:2]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestBringUpContracts:
    """Where compiled programs persist, and what chip_smoke.py does on a
    machine with no chip — the launch-surface rules of the bring-up."""

    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them."""
        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: seen.append((name, value)))
        return seen

    def test_cache_dir_from_environment_sets_nothing_in_code(
            self, monkeypatch, tmp_path, updates):
        from bcg_tpu.engine import jax_engine

        target = tmp_path / "elsewhere"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
        jax_engine._enable_compilation_cache()
        assert jax_engine.compilation_cache_dir() == str(target)
        assert target.is_dir()
        assert updates == []

    def test_cache_dir_default_is_one_fixed_path_in_the_checkout(
            self, monkeypatch, updates):
        from bcg_tpu.engine import jax_engine

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert jax_engine.compilation_cache_dir() == os.path.join(
            repo, ".jax_cache")
        jax_engine._enable_compilation_cache()   # CPU backend: no cache
        assert updates == []
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jax_engine._enable_compilation_cache()
        assert updates == [
            ("jax_compilation_cache_dir", os.path.join(repo, ".jax_cache"))
        ]

    def test_cache_dir_that_cannot_be_written_raises(
            self, monkeypatch, tmp_path, updates):
        from bcg_tpu.engine import jax_engine

        blocker = tmp_path / "a_file"
        blocker.write_text("not a directory")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(blocker / "cache"))
        with pytest.raises(OSError):
            jax_engine._enable_compilation_cache()

    def test_chip_smoke_refuses_a_machine_without_a_chip(self):
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "chip_smoke.py")],
            env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
            capture_output=True, text=True, timeout=120, cwd=repo,
        )
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert "boot" not in proc.stdout   # refused before booting anything
        assert '"platform": "cpu"' in proc.stdout
