"""Scan-over-layers (transformer.stack_layer_params / _run_layers).

The stacked execution path exists to keep 8B-class programs one layer
long (VERDICT round-1 item #2); it must be
numerically IDENTICAL to the unrolled per-layer loop — same blocks, same
cache contents, same logits — and must shard on a mesh.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.models import init_params, prefill, spec_for_model
from bcg_tpu.models import transformer as T
from bcg_tpu.models.transformer import (
    decode_chunk,
    decode_chunk_spec,
    decode_step,
    init_kv_cache,
    layers_stacked,
    prefill_chunk_at,
    prefill_with_prefix,
    stack_layer_params,
)

SPEC = spec_for_model("bcg-tpu/tiny-test")


@pytest.fixture(scope="module")
def params():
    return init_params(SPEC, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def stacked(params):
    return stack_layer_params(params)


def _prompt(B=2, L=16, seed=1):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 256, size=(B, L)), jnp.int32)
    valid = jnp.ones((B, L), bool).at[0, :3].set(False)  # left padding
    return tokens, valid


def test_stack_is_idempotent(stacked):
    assert layers_stacked(stacked)
    again = stack_layer_params(stacked)
    assert again is stacked


def test_prefill_equivalence(params, stacked):
    tokens, valid = _prompt()
    B, L = tokens.shape
    cache_l = init_kv_cache(SPEC, B, L + 4)
    cache_s = init_kv_cache(SPEC, B, L + 4, stacked=True)
    logits_l, new_l = prefill(params, SPEC, tokens, valid, cache_l)
    logits_s, new_s = prefill(stacked, SPEC, tokens, valid, cache_s)
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)
    # Cache contents match up to bf16 reassociation noise (scan and the
    # unrolled loop fuse differently).
    for li in range(SPEC.num_layers):
        np.testing.assert_allclose(
            np.asarray(new_l[li]["k"], np.float32),
            np.asarray(new_s["k"][li], np.float32),
            rtol=6e-2, atol=6e-2,
        )


def test_decode_step_equivalence(params, stacked):
    tokens, valid = _prompt()
    B, L = tokens.shape
    S = L + 4
    _, cache_l = prefill(params, SPEC, tokens, valid, init_kv_cache(SPEC, B, S))
    _, cache_s = prefill(
        stacked, SPEC, tokens, valid, init_kv_cache(SPEC, B, S, stacked=True)
    )
    tok = jnp.asarray([5, 9], jnp.int32)
    lens = valid.sum(axis=1).astype(jnp.int32)
    mask = jnp.zeros((B, S), bool).at[:, :L].set(valid).at[:, L].set(True)
    logits_l, _ = decode_step(params, SPEC, tok, L, lens, cache_l, mask)
    logits_s, _ = decode_step(stacked, SPEC, tok, L, lens, cache_s, mask)
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)


@pytest.mark.slow
def test_decode_chunk_equivalence(params, stacked):
    tokens, valid = _prompt()
    B, L = tokens.shape
    K, S = 4, L + 8
    _, cache_l = prefill(params, SPEC, tokens, valid, init_kv_cache(SPEC, B, S))
    _, cache_s = prefill(
        stacked, SPEC, tokens, valid, init_kv_cache(SPEC, B, S, stacked=True)
    )
    chunk = jnp.asarray([[7, 8, 9, 10], [3, 4, 5, 6]], jnp.int32)
    chunk_valid = jnp.asarray([[1, 1, 1, 0], [1, 1, 0, 0]], bool)
    lens = valid.sum(axis=1).astype(jnp.int32)
    positions = lens[:, None] + jnp.arange(K)[None]
    cache_valid = jnp.zeros((B, S), bool).at[:, :L].set(valid)
    logits_l, _ = decode_chunk(
        params, SPEC, chunk, chunk_valid, L, positions, cache_l, cache_valid
    )
    logits_s, _ = decode_chunk(
        stacked, SPEC, chunk, chunk_valid, L, positions, cache_s, cache_valid
    )
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)


def test_prefill_with_prefix_equivalence(params, stacked):
    """Suffix prefill against pre-populated cache slots works under scan
    (used by chunked prefill, which scan-mode 8B serving relies on)."""
    tokens, valid = _prompt(B=2, L=8, seed=3)
    B, L = tokens.shape
    P, S = 8, 24
    ptoks, pvalid = _prompt(B=2, L=P, seed=4)
    _, cache_l = prefill(params, SPEC, ptoks, pvalid, init_kv_cache(SPEC, B, S))
    _, cache_s = prefill(
        stacked, SPEC, ptoks, pvalid, init_kv_cache(SPEC, B, S, stacked=True)
    )
    plens = pvalid.sum(axis=1).astype(jnp.int32)
    logits_l, _ = prefill_with_prefix(
        params, SPEC, tokens, valid, cache_l, pvalid, plens
    )
    logits_s, _ = prefill_with_prefix(
        stacked, SPEC, tokens, valid, cache_s, pvalid, plens
    )
    np.testing.assert_allclose(logits_l, logits_s, rtol=6e-2, atol=6e-2)


def test_quantized_stack_equivalence(params):
    """int8 leaves stack inside their {"q", "scale"} dicts."""
    from bcg_tpu.models.quantize import quantize_params

    qparams = quantize_params(params, SPEC)
    qstacked = stack_layer_params(qparams)
    assert qstacked["layers"]["wq"]["q"].shape[0] == SPEC.num_layers
    tokens, valid = _prompt()
    B, L = tokens.shape
    logits_l, _ = prefill(qparams, SPEC, tokens, valid, init_kv_cache(SPEC, B, L + 2))
    logits_s, _ = prefill(
        qstacked, SPEC, tokens, valid, init_kv_cache(SPEC, B, L + 2, stacked=True)
    )
    # int8-quantized bf16 math: scan vs unrolled reassociates reductions,
    # and on CPU XLA (jax 0.4.37) a single tail element lands at 0.078
    # abs — widen just past it; a real stacking bug moves everything.
    np.testing.assert_allclose(logits_l, logits_s, rtol=8e-2, atol=8e-2)


def test_stacked_params_shard_on_mesh(stacked):
    from bcg_tpu.parallel.mesh import build_mesh
    from bcg_tpu.parallel.sharding import shard_params

    mesh = build_mesh(tp=2, dp=4)
    sharded = shard_params(stacked, SPEC, mesh)
    wq = sharded["layers"]["wq"]  # [Lyr, D, H*Dh]
    assert wq.shape == (SPEC.num_layers, SPEC.hidden_size, SPEC.q_size)
    spec_axes = wq.sharding.spec
    assert spec_axes[0] is None  # layer axis replicates
    # Output dim shards over tp (Megatron column-parallel).
    assert spec_axes[-1] == "tp"


@pytest.mark.slow
def test_engine_greedy_equivalence_scan_vs_unrolled():
    """Whole-engine proof: guided greedy generation is identical with
    scan_layers on and off (same schema, same prompt, temperature 0)."""
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    schema = {
        "type": "object",
        "properties": {
            "value": {"type": "integer", "minimum": 0, "maximum": 50},
        },
        "required": ["value"],
    }
    base = EngineConfig(
        model_name="bcg-tpu/tiny-test", backend="jax", max_model_len=512,
        prefix_caching=False,
    )
    prompts = [("You are agent_1.", "Pick a value.", schema)]
    eng_scan = JaxEngine(dataclasses.replace(base, scan_layers=True))
    eng_plain = JaxEngine(base)
    out_scan = eng_scan.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    out_plain = eng_plain.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    assert out_scan == out_plain


def test_engine_scan_with_prefix_caching():
    """Scan mode composes with prefix caching (stacked-entry assembly):
    same greedy output with the cache on and off."""
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    schema = {
        "type": "object",
        "properties": {
            "value": {"type": "integer", "minimum": 0, "maximum": 50},
        },
        "required": ["value"],
    }
    base = EngineConfig(
        model_name="bcg-tpu/tiny-test", backend="jax", max_model_len=512,
        scan_layers=True,
    )
    prompts = [
        ("You are agent_1. " + "Rules. " * 40, "Pick a value.", schema),
        ("You are agent_2. " + "Rules. " * 40, "Pick a value.", schema),
    ]
    eng_cached = JaxEngine(base)
    eng_plain = JaxEngine(dataclasses.replace(base, prefix_caching=False))
    out_cached = eng_cached.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    out_plain = eng_plain.batch_generate_json(prompts, temperature=0.0, max_tokens=24)
    assert out_cached == out_plain
    assert len(eng_cached._prefix_cache) == 2


@pytest.mark.parametrize("loop", [
    {"decode_fast_forward": True}, {"spec_decode": True},
], ids=["fast_forward", "speculative"])
def test_engine_loops_serve_plain_greedy_on_stacked_int8(loop):
    """The fast-forward and speculative loops address the stacked int8
    cache through the same seam as the plain loop (``_carried_entry`` /
    ``_carry_with``) and no benchmark cell drives them: each serves the
    plain loop's greedy tokens, from forced chains and accepted drafts."""
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    schema = {
        "type": "object",
        "properties": {
            "decision": {"type": "string", "enum": ["stop", "continue"]},
            "value": {"type": "integer", "minimum": 0, "maximum": 50},
        },
        "required": ["decision", "value"],
        "additionalProperties": False,
    }
    base = EngineConfig(
        model_name="bcg-tpu/tiny-test", backend="jax", max_model_len=2048,
        prefix_caching=False, scan_layers=True, kv_cache_dtype="int8",
    )
    prompts = [
        ("You are agent_1.", 'Round 2. {"decision": "continue", "value": 41}', schema),
        ("You are agent_2. " + "Rules. " * 20, "Pick a value.", schema),
    ]
    eng_plain = JaxEngine(base)
    eng_loop = JaxEngine(dataclasses.replace(base, **loop))
    try:
        out_plain = eng_plain.batch_generate_json(
            prompts, temperature=0.0, max_tokens=40)
        steps_plain = eng_plain.total_decode_steps
        out_loop = eng_loop.batch_generate_json(
            prompts, temperature=0.0, max_tokens=40)
    finally:
        eng_plain.shutdown()
        eng_loop.shutdown()
    assert all("error" not in r for r in out_plain), out_plain
    assert out_loop == out_plain
    # The loop did its own work: fewer device iterations for the same tokens.
    assert 0 < eng_loop.total_decode_steps < steps_plain


# ---------------------------------------- the stacked cache, updated in place

HYBRID = spec_for_model("bcg-tpu/tiny-hybrid")


def _layer_of(stack, li):
    return jax.tree.map(lambda a: a[li], stack)


@pytest.mark.parametrize("rows", [False, True], ids=["scalar_pos", "row_pos"])
@pytest.mark.parametrize("kv", [False, "int8", "int4"])
def test_write_into_stack_equals_write_into_entry(kv, rows):
    """``_write_cache`` on a stacked cache's layer (``_Layer``: the
    stack and an index, nothing sliced out) leaves in that layer, bit
    for bit, what it leaves in the layer's own entry, and no other
    layer moves."""
    B, S, Tn, li = 3, 24, 2, 1
    stack = init_kv_cache(SPEC, B, S, quantized=kv, stacked=True)
    key = jax.random.PRNGKey(5)
    stack = jax.tree.map(   # a cache already written to, not zeros
        lambda a: jax.random.randint(key, a.shape, -9, 9).astype(a.dtype), stack)
    k, v = jax.random.normal(key, (2, B, Tn, SPEC.num_kv_heads, SPEC.head_dim))
    pos = jnp.asarray([3, 11, 20], jnp.int32) if rows else jnp.int32(7)
    write = jax.jit(T._write_cache)
    new = write(T._Layer(stack, jnp.int32(li)), k, v, pos).stack
    want = write(_layer_of(stack, li), k, v, pos)
    for name, leaf in want.items():
        np.testing.assert_array_equal(np.asarray(new[name][li]), np.asarray(leaf))
        np.testing.assert_array_equal(   # tiny-test has two layers
            np.asarray(new[name][0]), np.asarray(stack[name][0]))
        assert not np.array_equal(np.asarray(leaf), np.asarray(stack[name][li]))


def _close_caches(list_cache, stacked):
    """K, V and scales of every layer, list form against stacked, up to
    bf16 reassociation noise (scan and the unrolled loop fuse
    differently); int8 values are compared as what they stand for."""
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    for li, entry in enumerate(list_cache):
        for name in entry:
            a, b = f32(entry[name]), f32(stacked[name][li])
            if f"{name}_scale" in entry:
                a = a * f32(entry[f"{name}_scale"])[..., None]
                b = b * f32(stacked[f"{name}_scale"][li])[..., None]
            np.testing.assert_allclose(a, b, rtol=6e-2, atol=6e-2, err_msg=name)


@pytest.mark.parametrize("rows", [False, True], ids=["scalar_pos", "row_pos"])
@pytest.mark.parametrize("kv", [False, "int8"])
def test_chunk_then_decode_equivalence(params, stacked, kv, rows):
    """Two chunk programs, then three decode steps (``decode_step`` at
    one shared slot, or ``decode_chunk_spec`` at per-row slots): list
    and stacked caches hold the same K, V and scales at every layer and
    give the same logits at every step."""
    tokens, valid = _prompt(B=2, L=16, seed=7)
    B, L = tokens.shape
    C, S, steps = 8, 32, 3
    caches = {
        "list": (params, init_kv_cache(SPEC, B, S, quantized=kv)),
        "stacked": (stacked, init_kv_cache(SPEC, B, S, quantized=kv, stacked=True)),
    }
    out = {}
    for form, (p, cache) in caches.items():
        got = []
        for start in range(0, L, C):
            hist = jnp.zeros((B, L - C), bool).at[:, :start].set(valid[:, :start])
            logits, cache = prefill_chunk_at(
                p, SPEC, tokens[:, start:start + C], valid[:, start:start + C],
                cache, hist, valid[:, :start].sum(axis=1).astype(jnp.int32),
                jnp.int32(start))
        got.append(logits)
        lens = valid.sum(axis=1).astype(jnp.int32)
        mask = jnp.zeros((B, S), bool).at[:, :L].set(valid)
        for j in range(steps):
            tok = jnp.asarray([5 + j, 9 + j], jnp.int32)
            if rows:   # rows write at their own slots, one apart
                at = jnp.asarray([L + j, L + 4 + j], jnp.int32)
                logits, cache = decode_chunk_spec(
                    p, SPEC, tok[:, None], jnp.ones((B, 1), bool), at,
                    (lens + j)[:, None], cache, mask)
                logits = logits[:, 0]
                mask = mask.at[jnp.arange(B), at].set(True)
            else:
                mask = mask.at[:, L + j].set(True)
                logits, cache = decode_step(
                    p, SPEC, tok, jnp.int32(L + j), lens + j, cache, mask)
            got.append(logits)
        out[form] = (got, cache)
    for a, b in zip(out["list"][0], out["stacked"][0]):
        np.testing.assert_allclose(a, b, rtol=6e-2, atol=6e-2)
    _close_caches(out["list"][1], out["stacked"][1])


def _interpreted_kernel(monkeypatch):
    """The int8 decode kernel in interpret mode wherever the model calls
    it (the model passes no such flag: the test steers it)."""
    from bcg_tpu.ops import decode_attention as da

    monkeypatch.setattr(
        da, "decode_attention", functools.partial(da.decode_attention, interpret=True))


def _decode_lowering(spec, impl, S, B=2):
    """The lowered text of one stacked int8 decode step."""
    params = jax.eval_shape(
        lambda k: stack_layer_params(init_params(spec, k), spec=spec),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    cache = jax.eval_shape(functools.partial(
        init_kv_cache, spec, B, S, quantized="int8", stacked=True))
    step = functools.partial(decode_step, impl=impl)
    return jax.jit(step, static_argnums=1).lower(
        params, spec, jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), jax.ShapeDtypeStruct((B,), jnp.int32),
        cache, jax.ShapeDtypeStruct((B, S), jnp.bool_)).as_text()


def _moved_int8(text, op):
    """Dims of what each ``stablehlo.<op>`` on an int8 tensor moves: a
    ``dynamic_slice``'s result, a ``dynamic_update_slice``'s update."""
    moved = []
    for line in text.splitlines():
        if f"stablehlo.{op} " not in line and f"stablehlo.{op}(" not in line:
            continue
        types = re.findall(r"tensor<([0-9x]+)xi8>", line.split(" : ")[-1])
        if types:
            part = types[-1] if op == "dynamic_slice" else types[1]
            moved.append(tuple(int(d) for d in part.split("x")))
    return moved


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_no_layer_of_the_stacked_cache_is_copied(family, impl, monkeypatch):
    """Static guard on the lowered decode step: the layer scan writes
    ONE token's K and V into the stacked int8 cache in place and moves
    no layer's whole entry.  S = 1536 is six blocks of the kernel and
    no other extent of the program.  Under the kernel (interpret mode
    here) nothing int8 with all S slots is sliced or written at all;
    the XLA twin reads the one layer it dequantises, never the stack."""
    S = 1536
    spec = SPEC if family == "dense" else HYBRID
    if impl == "pallas":
        _interpreted_kernel(monkeypatch)
    text = _decode_lowering(
        spec, T.HybridImpl(impl, "xla") if spec.hybrid else impl, S)
    writes = _moved_int8(text, "dynamic_update_slice")
    reads = _moved_int8(text, "dynamic_slice")
    # K and V: one token of one layer, once in the scan's body (interpret
    # mode's own block buffers are written besides; never S slots wide)
    token = (1, 2, spec.num_kv_heads, 1, spec.head_dim)
    assert writes.count(token) == 2 and all(S not in d for d in writes), writes
    if impl == "pallas":
        assert reads and all(S not in dims for dims in reads), reads
    else:
        assert len(reads) == 2 and all(d[0] == 1 and S in d for d in reads), reads


def _primitives(jaxpr, inside_scan=False):
    """``(name, inside a scan's body)`` of every equation, sub-jaxprs
    (a scan's body, a kernel's, a pjit's) included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_scan
        inner = inside_scan or eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, inner)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_live_range_is_reduced_outside_the_layer_scan(family, monkeypatch):
    """The traced decode step under the cells' form (layer scan over a
    stacked int8 cache, the kernel attending): each row's first and last
    attendable slot are read off the mask ONCE a step, outside the scan
    over layers; the scan's body holds the kernel and no reduction over
    the mask's S slots."""
    S = 1536
    spec = SPEC if family == "dense" else HYBRID
    _interpreted_kernel(monkeypatch)
    impl = T.HybridImpl("pallas", "xla") if spec.hybrid else "pallas"
    params = jax.eval_shape(
        lambda k: stack_layer_params(init_params(spec, k), spec=spec),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    cache = jax.eval_shape(functools.partial(
        init_kv_cache, spec, 2, S, quantized="int8", stacked=True))
    jaxpr = jax.make_jaxpr(
        lambda p, tok, pos, seq, c, m: decode_step(p, spec, tok, pos, seq, c, m, impl)
    )(params, jax.ShapeDtypeStruct((2,), jnp.int32),
      jax.ShapeDtypeStruct((), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32),
      cache, jax.ShapeDtypeStruct((2, S), jnp.bool_))
    seen = list(_primitives(jaxpr.jaxpr))
    assert ("pallas_call", True) in seen
    # first and last slot: two argmax over S, both before the scan
    assert seen.count(("argmax", False)) == 2, seen
    assert ("argmax", True) not in seen and ("reduce_or", True) not in seen

