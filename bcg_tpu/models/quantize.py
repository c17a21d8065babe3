"""int8 (W8A8) and int4 (grouped W4A16) weight quantization.

Autoregressive decode reads every weight byte once per token, so on TPU it
is HBM-bandwidth-bound; storing the dense weights as int8 with per-output-
channel absmax scales halves that traffic, and the MXU multiplies int8 at
twice the bf16 rate.  Activations are quantized dynamically per token
(per-row absmax) right before each matmul, the matmul runs int8 x int8 ->
int32 on the MXU, and the result is rescaled in f32 — the standard
"dynamic W8A8" serving recipe.

This replaces the role of vLLM's quantization support in the reference's
engine layer (``quantization`` knob in `EngineConfig`; the reference
passes its engine config straight to vLLM, vllm_agent.py:100-157).
Enable with ``EngineConfig(quantization="int8")`` / ``--quantization int8``.

Scope: the seven dense matmuls per block plus the LM head.  Embedding
lookups stay bf16 (gathers, not matmuls); for tied-embedding models a
separate quantized head copy is materialized so the [D, V] projection —
the single largest weight in small-vocab-heavy models — still benefits.
Norm vectors stay bf16.

int4 (``quantization="int4"``) exists for CAPACITY, not speed: grouped
absmax int4 (group 128 along the contraction dim, two values packed per
byte) halves weight memory again vs int8 — the difference between the
reference's 14B preset (config.py:20-25; "24GB+ VRAM" per its README)
fitting a single 16 GB v5e chip or needing tp>=2.  The matmul runs
W4A16: nibbles are sign-extended and dequantized to bf16 (in VMEM by the
Pallas kernel on TPU, ops/w4_matmul.py; materialized by XLA elsewhere)
and the dot runs on the MXU in bf16.

Packing layout (shared contract with the Pallas kernel): a [in, out]
weight packs row ``i`` of the TOP half (rows [0, in/2)) into the low
nibble and row ``i + in/2`` into the high nibble of byte ``[i, out]`` —
contraction is a sum over rows, so splitting ``x`` into matching column
halves needs no nibble interleave on the unpack path.  Group scales are
``[in/group, out]`` bf16; ``in/2`` must divide by the group size so no
group straddles the halves (group shrinks via gcd for tiny test dims).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Union

import jax
import jax.numpy as jnp

from bcg_tpu.models.configs import ModelSpec
from bcg_tpu.obs import tracer as obs_tracer

# A quantized dense weight is a dict:
#   int8: {"q": int8 [in, out], "scale": f32 [out]}
#   int4: {"q4": int8 [in//2, out] (two nibbles/byte), "gscale": bf16 [in//group, out]}
QuantizedDense = Dict[str, jax.Array]
DenseWeight = Union[jax.Array, QuantizedDense]

# The block matmuls that quantize: the dense family's seven, a gated full
# attention's output gate, the five projections of a hybrid's delta-rule
# layer (its per-head gate projections, a KDA layer's low-rank decay and
# output-gate pairs, its conv taps and its vectors stay as they are), and
# the held experts' stacks ([E, in, out]: a scale per expert and output
# channel).  An expert layer's router stays bfloat16.
_QUANT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "wg",
                 "lin_wq", "lin_wk", "lin_wv", "lin_wg", "lin_wo",
                 "moe_w_gate", "moe_w_up", "moe_w_down")

INT4_GROUP = 128


def _quantize_impl(w: jax.Array) -> QuantizedDense:
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)     # over the contraction axis
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


_quantize_consuming = partial(jax.jit, donate_argnums=0)(_quantize_impl)
_quantize_preserving = jax.jit(_quantize_impl)


def quantize_weight(w, consume: bool = False) -> QuantizedDense:
    """[in, out] bf16/f32 -> int8 + per-output-channel f32 absmax scale
    (a stack [E, in, out]: a scale per expert and output channel).

    Jitted so the op chain fuses: run eagerly it materializes a full f32
    copy of the weight (2x bf16) — quantizing an 8B model's [D, V] head
    that way OOMs a 16 GB chip during INIT.  ``consume=True``
    additionally donates the source buffer (peak = int8 output only) —
    pass it ONLY for a tensor the caller owns exclusively; the default
    preserves the input, matching ``quantize_params(consume=False)``'s
    contract that the bf16 tree stays usable.
    """
    fn = _quantize_consuming if consume else _quantize_preserving
    return fn(jnp.asarray(w))


def int4_group_for(in_dim: int, group: int = INT4_GROUP) -> int:
    """Effective group size for a weight's contraction dim:
    ``gcd(in_dim // 2, group)`` — a divisor of the packed half, shrunk
    from the requested group when it cannot divide (tiny test models
    have in-dims like 64; non-power-of-two dims shrink further than the
    largest-divisor-below-group would)."""
    if in_dim % 2:
        raise ValueError(f"int4 packing needs an even in-dim, got {in_dim}")
    return math.gcd(in_dim // 2, group)


def _quantize4_impl(w: jax.Array, group: int) -> QuantizedDense:
    w32 = w.astype(jnp.float32)
    *lead, in_dim, out_dim = w32.shape          # a stack [E, in, out]: per expert
    grouped = w32.reshape(*lead, in_dim // group, group, out_dim)
    absmax = jnp.max(jnp.abs(grouped), axis=-2)
    scale = jnp.maximum(absmax, 1e-12) / 7.0                  # [in/group, out]
    # Quantize against the bf16-ROUNDED scale (what dequant will read),
    # so the half-step error bound holds exactly.
    scale = scale.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(jnp.round(grouped / scale[..., None, :]), -8, 7)
    q = q.astype(jnp.int8).reshape(*lead, in_dim, out_dim)
    half = in_dim // 2
    packed = jnp.bitwise_or(
        jnp.bitwise_and(q[..., :half, :], jnp.int8(0x0F)),
        jnp.left_shift(q[..., half:, :], 4),
    ).astype(jnp.int8)
    return {"q4": packed, "gscale": scale.astype(jnp.bfloat16)}


_quantize4_consuming = partial(jax.jit, static_argnums=1, donate_argnums=0)(_quantize4_impl)
_quantize4_preserving = partial(jax.jit, static_argnums=1)(_quantize4_impl)


def quantize_weight_int4(w, consume: bool = False, group: int = INT4_GROUP) -> QuantizedDense:
    """[in, out] bf16/f32 -> packed int4 + per-(group, output) bf16 scale.

    Same jit/donate discipline as :func:`quantize_weight` (eager absmax
    would materialize a full f32 copy during a 14B load)."""
    w = jnp.asarray(w)
    g = int4_group_for(w.shape[-2], group)
    fn = _quantize4_consuming if consume else _quantize4_preserving
    return fn(w, g)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Packed [in//2, out] int8 -> [in, out] int8 in [-8, 7].

    Low nibbles are the top half's rows, high nibbles the bottom half's
    (see module docstring); right_shift on int8 is arithmetic, which is
    exactly the sign-extension the low nibble needs after the left
    shift."""
    low = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    high = jnp.right_shift(packed, 4)
    return jnp.concatenate([low, high], axis=0)


def dequantize_int4(w: QuantizedDense) -> jax.Array:
    """Materialize the bf16 weight from an int4 dict (XLA fallback path
    and test oracle; the Pallas kernel does this per-tile in VMEM)."""
    q = unpack_int4(w["q4"]).astype(jnp.float32)              # [in, out]
    gscale = w["gscale"].astype(jnp.float32)                  # [in/g, out]
    group = q.shape[0] // gscale.shape[0]
    scaled = q.reshape(gscale.shape[0], group, -1) * gscale[:, None, :]
    return scaled.reshape(q.shape).astype(jnp.bfloat16)


# ------------------------------------------------------------- int4 KV cache
# Packed-int4 KV entries reuse the int8 cache's axes ([.., Hkv, S, Dh]
# storage with [.., Hkv, S] scales) with the head dim PACKED two values
# per byte and the scales bf16: the nibble split mirrors the weight
# contract above — dims [0, Dh/2) in the low nibble, [Dh/2, Dh) in the
# high nibble of byte [.., d] — so the paged Pallas kernel never
# interleaves nibbles either: it dots each query half against its
# nibble's dequantized half (contraction over Dh splits cleanly).
# Scales are bf16 (not the int8 arm's f32) for the same reason gscale
# is: the scale overhead is what separates a 1.67x capacity win from
# the 2x the packing actually buys at small head dims, and quantizing
# against the bf16-ROUNDED scale keeps the half-step error bound exact.


def kv_int4_layout(head_dim: int):
    """(storage head dim, scale dtype) of the packed-int4 KV layout —
    the ONE definition every allocator (dense slab, paged pool) and the
    engine's boot check derive from, so the packing contract and the
    scale-dtype layout marker cannot drift apart across sites."""
    if head_dim % 2:
        raise ValueError(
            f"int4 KV packing needs an even head dim, got {head_dim}"
        )
    return head_dim // 2, jnp.bfloat16


def quantize_kv_int4(x):
    """bf16/f32 ``[..., Dh]`` -> (packed int8 ``[..., Dh//2]``, bf16
    per-(position, head) absmax scale ``[...]``).  Symmetric absmax
    over the head dim (the LAST axis — packing is last-axis only),
    range [-8, 7]."""
    half, scale_dtype = kv_int4_layout(x.shape[-1])
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 7.0)
    # Quantize against the bf16-ROUNDED scale (what dequant will read).
    scale = scale.astype(scale_dtype).astype(jnp.float32)
    q = jnp.clip(jnp.round(x32 / scale), -8, 7).astype(jnp.int8)
    packed = jnp.bitwise_or(
        jnp.bitwise_and(q[..., :half], jnp.int8(0x0F)),
        jnp.left_shift(q[..., half:], 4),
    ).astype(jnp.int8)
    return packed, scale.squeeze(-1).astype(scale_dtype)


def unpack_kv_int4(packed: jax.Array) -> jax.Array:
    """Packed ``[..., Dh//2]`` int8 -> ``[..., Dh]`` int8 in [-8, 7]
    (low nibbles = first half of the head dim; arithmetic right shift
    sign-extends, exactly like :func:`unpack_int4`)."""
    low = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    high = jnp.right_shift(packed, 4)
    return jnp.concatenate([low, high], axis=-1)


def dequantize_kv_int4(packed: jax.Array, scale: jax.Array):
    """Materialize f32 KV from a packed entry slice (XLA fallback path
    and test oracle; the paged Pallas kernel dequantizes per page in
    VMEM without ever forming the unpacked array).  Last-axis only,
    like the quantizer."""
    return unpack_kv_int4(packed).astype(jnp.float32) * jnp.expand_dims(
        scale.astype(jnp.float32), -1
    )


def is_quantized(w: DenseWeight) -> bool:
    return isinstance(w, dict)


def is_int4(w: DenseWeight) -> bool:
    return isinstance(w, dict) and "q4" in w


def _w8a16_prefill_rows() -> int:
    """Row threshold for the experimental W8A16 prefill path (0 = off).

    Read from the environment at TRACE time (first call per shape
    signature), not import time, so tests can monkeypatch it; it is a
    bench A/B knob, not a per-engine config field — if the hardware A/B
    wins it becomes an unconditional shape dispatch like int4's."""
    from bcg_tpu.runtime.envflags import get_int

    return get_int("BCG_TPU_W8A16_PREFILL")


def dense(x: jax.Array, w: DenseWeight, out_dtype=None) -> jax.Array:
    """``x @ w`` where ``w`` is bf16 or a quantized dict.

    Quantized path: per-token (last-axis) dynamic absmax activation quant,
    int8 x int8 -> int32 dot on the MXU, f32 rescale cast to ``out_dtype``
    (default ``x.dtype``; pass f32 on the logits path to keep the full
    accumulator precision instead of bouncing through bf16).
    """
    if out_dtype is None:
        out_dtype = x.dtype
    if not is_quantized(w):
        return (x @ w).astype(out_dtype)
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if is_int4(w):
        # W4A16: dequantize to bf16, dot on the MXU.  Path choice is by
        # row count: DECODE shapes (few rows) take the Pallas kernel —
        # one [P, block_f] strip DMA per output tile, weights streamed
        # once as packed int4, dequant in VMEM.  PREFILL shapes (many
        # rows) take the XLA fallback: it materializes the bf16 weight
        # in HBM once per call, which beats the kernel's per-M-block
        # weight re-streaming when the materialization is amortized
        # over thousands of rows (and prefill is compute-bound anyway).
        # Kernel only on a SINGLE device: pallas_call has no SPMD
        # partitioning rule, so under a tp/dp mesh GSPMD would have to
        # replicate (all-gather) the packed weight per call — the XLA
        # fallback partitions normally there.
        # BCG_TPU_DISABLE_W4_KERNEL=1 is the operational kill-switch
        # (read at trace time): if the kernel fails hardware lowering
        # (scripts/probe_w4_kernel.py), large-model serving degrades to
        # the XLA dequant path instead of crashing.
        from bcg_tpu.config import env_flag

        kernel_off = env_flag("BCG_TPU_DISABLE_W4_KERNEL")
        if (rows <= 256 and not kernel_off
                and jax.default_backend() == "tpu" and jax.device_count() == 1):
            from bcg_tpu.ops.w4_matmul import w4a16_matmul

            return w4a16_matmul(x, w["q4"], w["gscale"]).astype(out_dtype)
        return (x.astype(jnp.bfloat16) @ dequantize_int4(w)).astype(out_dtype)
    # EXPERIMENTAL A/B knob (BCG_TPU_W8A16_PREFILL=<row threshold>):
    # at/above the threshold, skip the dynamic activation quantization
    # and run dequantized int8 -> bf16 x bf16 on the MXU instead
    # (W8A16).  Rationale: prefill-shaped matmuls (thousands of rows)
    # measured only ~16% MFU under W8A8 — if the per-row act-quant +
    # f32 rescale chain (VPU-bound elementwise over the full activation)
    # is the tax, W8A16 trades 2x MXU rate for its removal while keeping
    # the int8 weight memory.  0 (default) = off; promote to a plain
    # shape dispatch (like int4's) if hardware A/B wins.
    if 0 < _w8a16_prefill_rows() <= rows:
        w_bf = (w["q"].astype(jnp.float32) * w["scale"]).astype(jnp.bfloat16)
        return (x.astype(jnp.bfloat16) @ w_bf).astype(out_dtype)
    x32 = x.astype(jnp.float32)
    a_absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    a_scale = jnp.maximum(a_absmax, 1e-12) / 127.0
    xq = jnp.clip(jnp.round(x32 / a_scale), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, w["q"],
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc.astype(jnp.float32) * a_scale * w["scale"]).astype(out_dtype)


def _quantizer(mode: str):
    if mode == "int8":
        return quantize_weight
    if mode == "int4":
        return quantize_weight_int4
    raise ValueError(f"quantization mode {mode!r}: expected 'int8' or 'int4'")


def _sharded_quantizer(mode: str, spec: ModelSpec, mesh):
    """Per-leaf jitted quantizer whose ``out_shardings`` is the leaf's
    ``param_sharding`` (q like the parent weight, scale per-output-
    channel) and whose input is DONATED under ``consume`` — so a
    tp-sharded bf16 leaf quantizes shard-wise with the int8 result laid
    out directly on the mesh, never re-staged replicated.  Jits are
    memoized per (leaf name, shape, consume): layers share shapes, so a
    14B tree compiles each transform once, not once per layer."""
    from bcg_tpu.parallel.sharding import param_sharding

    fns: Dict = {}

    def quantize(logical: str, w, consume: bool):
        leaf = logical.split(".")[-1]
        key = (leaf, w.shape, str(w.dtype), consume)
        fn = fns.get(key)
        if fn is None:
            if mode == "int8":
                impl = _quantize_impl
            else:
                impl = partial(_quantize4_impl, group=int4_group_for(w.shape[-2]))
            out_struct = jax.eval_shape(impl, jax.ShapeDtypeStruct(w.shape, w.dtype))
            outs = {
                sub: param_sharding(f"{logical}.{sub}", spec, mesh)
                for sub in out_struct
            }
            fn = jax.jit(
                impl, out_shardings=outs,
                donate_argnums=(0,) if consume else (),
            )
            fns[key] = fn
        # Donation frees the bf16 source shard-wise; it can never ALIAS
        # the int8/int4 output (dtype change), so silence the
        # per-compile "not usable" lowering warning.
        import warnings

        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return fn(jnp.asarray(w))

    return quantize


@obs_tracer.spanned_once("boot.quantize")
def quantize_params(
    params: Dict, spec: ModelSpec, consume: bool = False, mode: str = "int8",
    mesh=None,
) -> Dict:
    """Quantize every dense matmul weight of a transformer param pytree.

    Returns a new pytree with each of ``_QUANT_LEAVES`` (per layer) and the
    LM head replaced by ``{"q", "scale"}`` dicts.  Tied-embedding models
    gain an explicit quantized ``lm_head`` (from ``embed.T``) so the logits
    projection is quantized while the bf16 embedding table remains for
    token gathers; ``transformer._logits`` prefers ``lm_head`` when
    present, keeping the tie semantically intact.

    ``consume=True`` drops each bf16 source leaf from ``params`` as it is
    quantized, so peak device memory is the quantized model plus ONE bf16
    weight instead of both full copies — the difference between a large
    model fitting a single v5e chip or not.  Only pass it for a tree
    the caller owns exclusively.  ``mode`` selects int8 (W8A8) or int4
    (grouped W4A16).

    With ``mesh``, each leaf quantizes through a jitted transform whose
    ``out_shardings`` is the leaf's ``param_sharding``
    (:func:`_sharded_quantizer`): with ``consume`` the per-device peak is
    the quantized model SHARD plus one bf16 leaf shard, not per replica.
    """
    if mesh is not None:
        sharded = _sharded_quantizer(mode, spec, mesh)
        quantize = lambda logical, w, consume: sharded(logical, w, consume)  # noqa: E731
    else:
        plain = _quantizer(mode)
        quantize = lambda logical, w, consume: plain(w, consume=consume)  # noqa: E731
    out = dict(params)
    out_layers = []
    for li, layer in enumerate(params["layers"]):
        new_layer = {}
        for k in list(layer):
            v = layer[k]
            if k in _QUANT_LEAVES:
                new_layer[k] = quantize(f"layers.{li}.{k}", v, consume)
                if consume:
                    del layer[k]
                del v  # drop the local bf16 reference immediately
            else:
                new_layer[k] = v
        out_layers.append(new_layer)
    out["layers"] = out_layers
    if "lm_head" in params:
        out["lm_head"] = quantize("lm_head", params["lm_head"], consume)
        if consume:
            del params["lm_head"]
    elif spec.tie_embeddings:
        out["lm_head"] = quantize("lm_head", params["embed"].T, True)
    return out


def quantize_leaf_transform(spec: ModelSpec, mode: str = "int8"):
    """Per-leaf hook for the checkpoint loader: quantize each dense weight
    AS IT LOADS, so the bf16 tensor is freed before the next one arrives
    (streamed quantized loading; see loader.load_checkpoint_params)."""
    quantize = _quantizer(mode)

    def transform(logical: str, tensor):
        leaf = logical.split(".")[-1]
        if leaf in _QUANT_LEAVES or leaf == "lm_head":
            return quantize(tensor, consume=True)
        return tensor

    return transform


def ensure_quantized_head(
    params: Dict, spec: ModelSpec, mode: str = "int8", mesh=None
) -> Dict:
    """Give tied-embedding models their explicit quantized LM head when a
    leaf-transform load (which never sees an ``lm_head`` tensor) built the
    rest of the tree.  With ``mesh`` the head quantizes under its
    ``param_sharding`` like every other leaf (:func:`_sharded_quantizer`)."""
    if "lm_head" not in params and spec.tie_embeddings:
        if mesh is not None:
            params["lm_head"] = _sharded_quantizer(mode, spec, mesh)(
                "lm_head", params["embed"].T, True
            )
        else:
            params["lm_head"] = _quantizer(mode)(params["embed"].T, consume=True)
    return params
