"""Functional decoder-only transformer (RMSNorm / RoPE / GQA / SwiGLU).

TPU-first design choices:

* Parameters are a plain dict pytree; :mod:`bcg_tpu.parallel.sharding`
  assigns ``NamedSharding`` per leaf (heads and the MLP intermediate dim
  partition over the ``tp`` mesh axis — Megatron layout: column-parallel
  in-projections, row-parallel out-projections).
* Static shapes everywhere: prefill is [B, L] with an explicit validity
  mask (left-padded batches), decode is a [B, 1] step against a
  preallocated KV cache updated via ``dynamic_update_slice``.
* Weights and KV cache are bf16; RMSNorm accumulates in f32; attention
  logits/softmax run in f32 for stability.
* The attention inner op is pluggable (``attention_impl``): the stock
  XLA path (einsum softmax einsum — XLA fuses it well on MXU) or the
  Pallas flash kernel in :mod:`bcg_tpu.ops.attention`.
* Every operation is traced under the ``jax.named_scope`` of its part
  (:mod:`bcg_tpu.obs.scopes`): a model call is ``bcg.head``, its layer
  scan ``bcg.layers``, each sublayer ``bcg.attn``, ``bcg.linear_mixer``
  or ``bcg.ffn``; the device trace reads time per part by them.

Two block families share this file (``models/configs.py``): the dense
one above (``_block``; every layer the same) and hybrids whose spec
states ``layer_types`` — a block per layer type from ``_HYBRID_BLOCKS``
(full attention reusing ``_attend``; the gated delta rule of
``ops/gated_delta.py``), two kinds of per-row state in the cache (K/V
for the full layers, recurrent state and conv tail for the linear
ones), and a layer scan over the PERIODS of the pattern.  A hybrid's
mixers are the full attention (with an output gate where the spec has
one), the scalar-gated delta rule, the channel-gated one (KDA) and the
Mamba-1 state-space mixer (``ops/selective_scan.py``); its MLP half is
the SwiGLU or routed experts with a shared one (``ops/moe.py``: the chip
computes the experts it holds).

Replaces the CUDA side of the reference's engine (vLLM internals behind
``vllm_agent.py:100-157``); no reference code exists at this layer.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from bcg_tpu.models.configs import (
    FULL_ATTENTION, KDA, LINEAR_ATTENTION, MAMBA, RECURRENT_TYPES, ModelSpec,
)
from bcg_tpu.models.quantize import dense
from bcg_tpu.obs import scopes
from bcg_tpu.obs import tracer as obs_tracer
from bcg_tpu.ops import HybridImpl, impl_mesh, is_pallas

TransformerParams = Dict  # pytree: see init_params for the layout


# ----------------------------------------------------------------- building

# Leaf kinds that draw from a key (see init_leaf); the rest are constants.
RANDOM_KINDS = ("dense", "attn_out", "gate", "conv", "a_log", "dt_bias",
                "tied_embed", "ssm_dt_bias")
# The matrices a leaf transform (the int8 or int4 quantiser) takes.
MATRIX_KINDS = ("dense", "attn_out")

# A post-norm stack adds a unit-RMS vector to the residual stream per
# sublayer and feeds it to the next layer un-normalised: at 32 layers its
# RMS reaches 8.  The delta-rule layer's two per-head gate projections
# are drawn that much smaller, so that their pre-activations stay of
# order 1 at every depth (see init_leaf).
_GATE_DAMP = 8.0

# Norm vectors of a hybrid that are not ones.  A random delta-rule layer
# reads its state through the current token's query and answers the
# last few tokens; softmax attention over a 2,000-token prompt answers
# their mean; a post-norm stack rescales both answers to one size.  With
# every norm vector at one the prompt's mean carries a fifth of the
# logits' variance over a string's positions (the dense family: nine
# tenths): the next-byte distribution is drawn afresh at every step and
# every seed's model closes its guided strings alike.  The vector on a
# full-attention mixer's output at 4 makes that share four fifths, and
# the final one at 2 sharpens the logits, so that whether a string
# closes is the seed's model's, as in the dense family (PERF.md
# section 6, PR 29).
_FULL_MIXER_NORM = 4.0
_FINAL_NORM = 2.0
# The same lesson in a pre-norm hybrid, whose sublayers add to the
# residual stream un-normalised: softmax attention's answer, the
# prompt's mean, is some twentieth of a delta-rule or expert sublayer's,
# and carried a ninth of the logits' variance over a string's positions
# (PERF.md section 6, Solar-Open2: probed at width 512, three seeds; nine
# tenths in the dense family, 0.85 in the post-norm hybrid as set above).
# A full-attention layer's output projection drawn 16 times larger brings
# that share to 0.87; with the final norm vector at 2, as above.
_ATTN_OUT_GAIN = 16.0
_CONSTANT_KINDS = {"ones": 1.0, "zeros": 0.0, "full_mixer_norm": _FULL_MIXER_NORM,
                   "hybrid_final_norm": _FINAL_NORM}


def param_plan(spec: ModelSpec):
    """Ordered ``(logical_name, init_kind, shape)`` triples for every
    leaf :func:`init_params` creates — ``init_kind`` is ``"dense"``
    (random, scaled by 1/sqrt(fan_in)), a constant of
    ``_CONSTANT_KINDS`` (``"ones"``: norm vectors; ``"zeros"``:
    projection biases; a hybrid's two norm vectors that are not ones)
    or, in a hybrid's delta-rule layers, ``"gate"``, ``"conv"``,
    ``"a_log"`` and ``"dt_bias"``
    (:func:`init_leaf`).

    This is the single source of truth for the parameter layout: the
    eager initializer (:func:`init_params`), the born-sharded
    initializer (``models/loader.py::init_random_params_sharded``) and
    the analytic boot-memory accounting (``loader.boot_peak_report``)
    all iterate it, so creation order, key consumption and shapes
    cannot drift between the materializing and the abstract paths.

    Two key-consumption contracts (:func:`plan_keys`), each restated by
    the benchmark's plain reference of its family:

    * the dense family (no ``layer_types``): dense leaves consume one
      key each, in plan order, from ``jax.random.split(key, 4 +
      num_layers * 7)`` (``benchmark/references/dense_gqa.py``);
    * a hybrid: every random leaf (``RANDOM_KINDS``) consumes one key,
      in plan order, from ``jax.random.split(key, n)`` with ``n`` the
      count of such leaves (``benchmark/references/olmo_hybrid.py``).
    """
    if spec.hybrid:
        return _hybrid_param_plan(spec)
    plan = [
        ("embed", "dense", (spec.vocab_size, spec.hidden_size)),
        ("final_norm", "ones", (spec.hidden_size,)),
    ]
    for li in range(spec.num_layers):
        pre = f"layers.{li}."
        plan += [
            (pre + "attn_norm", "ones", (spec.hidden_size,)),
            (pre + "wq", "dense", (spec.hidden_size, spec.q_size)),
            (pre + "wk", "dense", (spec.hidden_size, spec.kv_size)),
            (pre + "wv", "dense", (spec.hidden_size, spec.kv_size)),
            (pre + "wo", "dense", (spec.q_size, spec.hidden_size)),
            (pre + "mlp_norm", "ones", (spec.hidden_size,)),
            (pre + "w_gate", "dense", (spec.hidden_size, spec.intermediate_size)),
            (pre + "w_up", "dense", (spec.hidden_size, spec.intermediate_size)),
            (pre + "w_down", "dense", (spec.intermediate_size, spec.hidden_size)),
        ]
        if spec.qk_norm:
            plan += [
                (pre + "q_norm", "ones", (spec.head_dim,)),
                (pre + "k_norm", "ones", (spec.head_dim,)),
            ]
        if spec.attn_bias:
            plan += [
                (pre + "bq", "zeros", (spec.q_size,)),
                (pre + "bk", "zeros", (spec.kv_size,)),
                (pre + "bv", "zeros", (spec.kv_size,)),
            ]
    if not spec.tie_embeddings:
        plan.append(("lm_head", "dense", (spec.hidden_size, spec.vocab_size)))
    return plan


def _hybrid_param_plan(spec: ModelSpec):
    """The plan of a spec with ``layer_types``: each layer's leaves by
    its type.  Every kind carries the two sublayer norms and the MLP
    half (the SwiGLU, or the router, the shared expert and the held
    experts' stacks); a linear layer's mixer is the gated delta rule's
    (five projections, the two per-head gates, the depthwise conv's
    taps, ``A_log``, ``dt_bias`` and the per-head output norm), a KDA
    layer's the channel-gated rule's (q, k, v, beta, the low-rank decay
    and output-gate pairs, conv, ``A_log`` per head, ``dt_bias`` per
    channel, the output norm and projection), a Mamba layer's the
    selective scan's (in-projection, conv taps and bias, x-projection,
    the norms on the step size, B and C, the step-size projection and
    its bias, ``A_log`` [channels, state], ``D``, out-projection).  A
    spec with tied embeddings draws its embedding at the head's scale
    (``tied_embed``)."""
    D = spec.hidden_size
    plan = [
        ("embed", "tied_embed" if spec.tie_embeddings else "dense", (spec.vocab_size, D)),
        ("final_norm", "hybrid_final_norm", (D,)),
    ]
    H = spec.linear_num_value_heads
    for li, kind in enumerate(spec.layer_types):
        pre = f"layers.{li}."
        shapes = spec.matmul_shapes(kind)
        dense_leaf = lambda name: (pre + name, "dense", shapes[name])  # noqa: E731
        if kind == LINEAR_ATTENTION:
            plan += [
                dense_leaf("lin_wq"), dense_leaf("lin_wk"), dense_leaf("lin_wv"),
                (pre + "lin_wa", "gate", shapes["lin_wa"]),
                (pre + "lin_wb", "gate", shapes["lin_wb"]),
                (pre + "lin_conv", "conv",
                 (spec.linear_conv_kernel_dim, spec.linear_conv_size)),
                (pre + "lin_a_log", "a_log", (H,)),
                (pre + "lin_dt_bias", "dt_bias", (H,)),
                dense_leaf("lin_wg"),
                (pre + "lin_out_norm", "ones", (spec.linear_value_head_dim,)),
                dense_leaf("lin_wo"),
            ]
        elif kind == KDA:
            plan += [
                dense_leaf("lin_wq"), dense_leaf("lin_wk"), dense_leaf("lin_wv"),
                (pre + "lin_conv", "conv",
                 (spec.linear_conv_kernel_dim, spec.linear_conv_size)),
                dense_leaf("lin_wb"), dense_leaf("kda_fa"), dense_leaf("kda_fb"),
                (pre + "lin_a_log", "a_log", (H,)),
                (pre + "lin_dt_bias", "dt_bias", (spec.linear_key_size,)),
                dense_leaf("kda_ga"), dense_leaf("kda_gb"),
                (pre + "lin_out_norm", "ones", (spec.linear_value_head_dim,)),
                dense_leaf("lin_wo"),
            ]
        elif kind == MAMBA:
            Di, N, R = spec.mamba_inner, spec.mamba_d_state, spec.mamba_dt_rank
            plan += [
                dense_leaf("ssm_in"),
                (pre + "ssm_conv", "conv", (spec.mamba_d_conv, Di)),
                (pre + "ssm_conv_bias", "conv", (Di,)),
                dense_leaf("ssm_x"),
                (pre + "ssm_dt_norm", "ones", (R,)),
                (pre + "ssm_b_norm", "ones", (N,)),
                (pre + "ssm_c_norm", "ones", (N,)),
                dense_leaf("ssm_dt"),
                (pre + "ssm_dt_bias", "ssm_dt_bias", (Di,)),
                (pre + "ssm_a_log", "ssm_a_log", (Di, N)),
                (pre + "ssm_d", "ones", (Di,)),
                dense_leaf("ssm_out"),
            ]
        else:
            plan += [dense_leaf(n) for n in ("wq", "wk", "wv", "wg") if n in shapes]
            plan.append((pre + "wo", "attn_out" if spec.norm_placement == "pre" else "dense",
                         shapes["wo"]))
            if spec.qk_norm:
                full = spec.qk_norm == "full"
                plan += [
                    (pre + "q_norm", "ones", (spec.q_size if full else spec.head_dim,)),
                    (pre + "k_norm", "ones", (spec.kv_size if full else spec.head_dim,)),
                ]
        plan += [
            (pre + "attn_norm",
             "full_mixer_norm" if kind == FULL_ATTENTION and spec.norm_placement == "post"
             else "ones", (D,)),
            (pre + "mlp_norm", "ones", (D,)),
        ]
        plan += [dense_leaf(n) for n in ("moe_router", "w_gate", "w_up", "w_down")
                 if n in shapes]
        plan += [(pre + n, "dense", (spec.experts_held, *io))
                 for n, io in spec.expert_shapes().items()]
    if not spec.tie_embeddings:
        plan.append(("lm_head", "dense", (D, spec.vocab_size)))
    return plan


def plan_keys(spec: ModelSpec, key: jax.Array, plan=None) -> jax.Array:
    """The keys :func:`param_plan`'s random leaves consume, in plan
    order, under the family's contract (see :func:`param_plan`)."""
    if not spec.hybrid:
        return jax.random.split(key, 4 + spec.num_layers * 7)
    plan = param_plan(spec) if plan is None else plan
    return jax.random.split(key, sum(kind in RANDOM_KINDS for _, kind, _ in plan))


def init_leaf(kind: str, shape, key: jax.Array, dtype) -> jax.Array:
    """One leaf of the plan from its key (constants ignore it).

    ``dense``: normal / sqrt(fan_in) (a stack of held experts ``[E, in,
    out]``: each expert's own); ``attn_out`` (a pre-norm hybrid's
    full-attention output projection) 16 times that.  The delta-rule
    layer's own:
    ``gate`` (the two per-head gate projections) normal / (8
    sqrt(fan_in)); ``conv`` taps normal / 2; ``a_log`` = log U(1, 16) and
    ``dt_bias`` the inverse softplus of U(0.001, 0.1).  With the gates'
    pre-activations of order 1, ``softplus(. + dt_bias)`` stays near
    U(0.001, 0.1) times e^(+-1) and a head's decay ``exp(-exp(a_log)
    softplus(.))`` covers long memory (0.9996) and short (0.02), and the
    write strength ``2 sigmoid(.)`` passes 1 about half the time.  Gates
    at the matrices' own scale saturate instead: on a residual stream of
    RMS 8 nearly every decay is 0, and a rounding error of 0.02 in the
    pre-activation moves ``g`` by 16 times that (measured: the W8A8
    model's greedy tokens then lie up to 2.3 below the float32
    reference's best, against 4.4 for int4 weights).

    A Mamba layer's own, Mamba-1's initialisation: ``ssm_a_log`` = log 1,
    ..., N along the state axis in every channel (float32), and
    ``ssm_dt_bias`` the inverse softplus of a step size log-uniform in
    [0.001, 0.1].  ``tied_embed`` (an embedding that is also the head):
    normal / sqrt(hidden size), so that the logits have the untied
    head's scale."""
    if kind == "dense":
        return (
            jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
        ).astype(dtype)
    if kind == "attn_out":
        return (
            jax.random.normal(key, shape, jnp.float32) * _ATTN_OUT_GAIN / math.sqrt(shape[-2])
        ).astype(dtype)
    if kind == "gate":
        return (
            jax.random.normal(key, shape, jnp.float32)
            / (_GATE_DAMP * math.sqrt(shape[0]))
        ).astype(dtype)
    if kind == "conv":
        return (jax.random.normal(key, shape, jnp.float32) / 2.0).astype(dtype)
    if kind == "a_log":
        return jnp.log(
            jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if kind == "dt_bias":
        dt = jax.random.uniform(key, shape, jnp.float32, 0.001, 0.1)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if kind == "tied_embed":
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-1])).astype(dtype)
    if kind == "ssm_dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(0.001), math.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if kind == "ssm_a_log":   # float32 whatever the served dtype
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
    if kind in _CONSTANT_KINDS:
        return jnp.full(shape, _CONSTANT_KINDS[kind], dtype)
    raise ValueError(f"unknown init kind {kind!r}")


def assemble_param_tree(items) -> TransformerParams:
    """``(logical_name, leaf)`` pairs -> the nested param pytree
    (``layers.{i}.{name}`` paths become ``params["layers"][i][name]``)."""
    params: Dict = {}
    for logical, leaf in items:
        parts = logical.split(".")
        if parts[0] == "layers":
            layers = params.setdefault("layers", [])
            li = int(parts[1])
            while len(layers) <= li:
                layers.append({})
            layers[li][parts[2]] = leaf
        else:
            params[logical] = leaf
    return params


def init_params(
    spec: ModelSpec, key: jax.Array, dtype=jnp.bfloat16, leaf_transform=None
) -> TransformerParams:
    """Random-init parameters with the HF-compatible logical layout.

    Layout (per layer l):
      embed            [V, D]
      layers.l.attn_norm [D]
      layers.l.wq      [D, H*Dh]    layers.l.wk/wv [D, Hkv*Dh]
      layers.l.wo      [H*Dh, D]
      layers.l.q_norm/k_norm [Dh]   (qk_norm models only)
      layers.l.bq/bk/bv             (attn_bias models only, e.g. Qwen2)
      layers.l.mlp_norm [D]
      layers.l.w_gate/w_up [D, F]   layers.l.w_down [F, D]
      final_norm       [D]
      lm_head          [D, V]       (absent when tie_embeddings)
    (a hybrid's layers: :func:`_hybrid_param_plan`.)

    ``leaf_transform(logical_name, tensor)`` (same hook as the streamed
    checkpoint loader) is applied to each dense weight AS IT IS CREATED,
    so e.g. int8 quantization never holds the whole bf16 model: an
    8B-class random-weight bench would otherwise OOM a 16 GB chip during
    init alone.

    This EAGER path still creates every leaf replicated on the default
    device with an fp32 intermediate per tensor — for flagship-scale
    specs use ``models/loader.py::init_random_params_sharded``, which
    materializes each leaf of the same :func:`param_plan` (same shapes,
    same key consumption) through a jitted per-leaf initializer under
    its ``param_sharding``, so no leaf ever exists unsharded.  Its
    VALUES intentionally differ bit-wise from this path's (it scopes the
    partitionable RNG for mesh-shape invariance); random weights carry
    no golden-value contract.
    """
    plan = param_plan(spec)
    keys = iter(plan_keys(spec, key, plan))

    def build(logical, kind, shape):
        w = init_leaf(
            kind, shape, next(keys) if kind in RANDOM_KINDS else None, dtype)
        if kind in MATRIX_KINDS and leaf_transform:
            return leaf_transform(logical, w)
        return w

    return assemble_param_tree(
        (logical, build(logical, kind, shape)) for logical, kind, shape in plan
    )


@obs_tracer.spanned_once("boot.stack")
def stack_layer_params(
    params: TransformerParams, consume: bool = False, mesh=None, spec=None
) -> TransformerParams:
    """Convert ``params["layers"]`` from a per-layer list to a STACKED
    pytree (each leaf gains a leading ``[num_layers]`` dim) for
    scan-over-layers execution.

    Why: every per-layer Python iteration unrolls into the HLO, so an
    unrolled 36-layer 8B program is ~36x the module size of its scanned
    equivalent, and compiles about that much slower.  ``lax.scan`` over
    stacked weights emits the block ONCE.

    Stacks leaf-group by leaf-group; with ``consume`` each group's
    per-layer source buffers are dropped as soon as its stack exists, so
    peak device memory is the model plus ONE leaf-group instead of two
    full copies — stacking an 8B int8 model non-consuming OOMs a 16 GB
    chip (measured).  Only pass ``consume`` for a tree the caller owns.

    With ``mesh`` (and ``spec``), each leaf-group stacks through a
    jitted transform whose ``out_shardings`` is the group's stacked
    ``param_sharding`` and whose inputs are DONATED under ``consume`` —
    so a tp/dp-sharded tree stays sharded through the stack and the
    leaf-group transient is per device SHARD, not per replica (a 14B
    tree stacking replicated would re-stage dp×/tp× the bytes the
    born-sharded init just avoided).
    """
    layers = params["layers"]
    if isinstance(layers, dict):
        return params

    stack_group = None
    if mesh is not None:
        if spec is None:
            raise ValueError("stack_layer_params(mesh=...) needs spec= too")
        from bcg_tpu.parallel.sharding import param_sharding

        def _stack(ls):
            if isinstance(ls[0], dict):
                return {k: jnp.stack([lv[k] for lv in ls]) for k in ls[0]}
            return jnp.stack(ls)

        # Memoized per (leaf signature, output shardings): same-shaped
        # groups — wk/wv, w_gate/w_up, the norm vectors — share ONE
        # compiled stack instead of re-lowering identical programs
        # (compiles sit on the boot path this function exists to slim).
        stack_fns: Dict = {}

        def stack_group(name, leaves):
            sample = leaves[0]
            if isinstance(sample, dict):
                outs = {
                    k: param_sharding(
                        f"layers.{name}.{k}", spec, mesh, stacked=True
                    )
                    for k in sample
                }
                sig = tuple(
                    sorted(
                        (k, v.shape, str(v.dtype), outs[k].spec)
                        for k, v in sample.items()
                    )
                )
            else:
                outs = param_sharding(f"layers.{name}", spec, mesh, stacked=True)
                sig = (sample.shape, str(sample.dtype), outs.spec)
            key = (sig, len(leaves))
            fn = stack_fns.get(key)
            if fn is None:
                fn = jax.jit(
                    _stack, out_shardings=outs,
                    donate_argnums=(0,) if consume else (),
                )
                stack_fns[key] = fn
            # Donation here frees each per-layer source as its slice is
            # copied; it can never ALIAS the stacked output (leading dim
            # added), so silence the per-compile "not usable" lowering
            # warning — the free, not the alias, is the point.
            import warnings

            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                return fn(leaves)

    def stack(layers) -> Dict:
        stacked: Dict = {}
        for name in list(layers[0].keys()):
            if consume:
                leaves = [l.pop(name) for l in layers]
            else:
                leaves = [l[name] for l in layers]
            if stack_group is not None:
                stacked[name] = stack_group(name, leaves)
            elif isinstance(leaves[0], dict):  # quantized {"q", "scale"}
                stacked[name] = {
                    k: jnp.stack([lv[k] for lv in leaves]) for k in leaves[0]
                }
            else:
                stacked[name] = jnp.stack(leaves)
            del leaves
        return stacked

    out = dict(params)
    kinds = [layer_kind(l) for l in layers]
    if set(kinds) & set(RECURRENT_TYPES):
        # A hybrid stacks BY TYPE, each type's layers in model order:
        # the scan walks periods and indexes each type's stack.
        if mesh is not None:
            raise ValueError(
                "stack_layer_params: a hybrid tree has no sharded stacking")
        out["layers"] = {
            kind: stack([l for l, k in zip(layers, kinds) if k == kind])
            for kind in dict.fromkeys(kinds)
        }
    else:
        out["layers"] = stack(layers)
    return out


def layer_kind(layer: Dict) -> str:
    """A layer's type, told from its leaves."""
    if "kda_fa" in layer:
        return KDA
    if "ssm_in" in layer:
        return MAMBA
    return LINEAR_ATTENTION if "lin_wq" in layer else FULL_ATTENTION


def layers_stacked(params: TransformerParams) -> bool:
    return isinstance(params["layers"], dict)


def probe_weight(params: TransformerParams):
    """One block matmul weight of the tree (``w_down``: every layer of
    every family has it), list form or stacked: what tells a tree's
    quantization format."""
    layers = params["layers"]
    if not isinstance(layers, dict):
        return layers[0]["w_down"]
    return (layers if "w_down" in layers else next(iter(layers.values())))["w_down"]


# ------------------------------------------------------------------ kernels

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * weight


def rope_table(
    positions: jax.Array, head_dim: int, theta: float, scaling=None
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given positions ([..., P] -> [..., P, Dh/2]).

    ``scaling`` is an optional :class:`~bcg_tpu.models.configs.RopeScaling`
    (Llama-3.1 "llama3" NTK-by-parts): long-wavelength frequencies divide
    by ``factor``, short ones are kept, the band between interpolates.
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None:
        wavelen = 2.0 * math.pi / inv_freq
        low_wl = scaling.original_max_position / scaling.low_freq_factor
        high_wl = scaling.original_max_position / scaling.high_freq_factor
        smooth = (
            scaling.original_max_position / wavelen - scaling.low_freq_factor
        ) / (scaling.high_freq_factor - scaling.low_freq_factor)
        scaled = jnp.where(
            wavelen > low_wl,
            inv_freq / scaling.factor,
            jnp.where(
                wavelen < high_wl,
                inv_freq,
                (1 - smooth) * inv_freq / scaling.factor + smooth * inv_freq,
            ),
        )
        inv_freq = scaled
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def _rope_for(spec: ModelSpec, positions: jax.Array):
    """cos/sin for a spec's rotary embedding, or ``(None, None)`` where
    it has none (``rope_theta=None`` or ``use_rope=False``): the blocks
    then skip :func:`apply_rope`; nothing is fed a stand-in theta."""
    if spec.rope_theta is None or not spec.use_rope:
        return None, None
    return rope_table(positions, spec.head_dim, spec.rope_theta, spec.rope_scaling)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate half (HF convention). x: [B, T, H, Dh]; cos/sin: [B, T, Dh/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _xla_attention(q, k, v, mask, scale):
    """Stock attention: einsum -> masked f32 softmax -> einsum.

    q: [B, T, H, Dh], k/v: [B, S, Hkv, Dh], mask: [B, T, S] bool.
    """
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, T, Hkv, group, Dh)
    logits = jnp.einsum("bthgd,bshd->bhgts", qg, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(B, T, H, Dh)


def attention(q, k, v, mask, scale, impl="xla"):
    """``impl`` is the caller's resolved choice — "xla", "blockwise",
    "pallas" or a mesh-carrying ``ops.PallasTP``; nothing here
    second-guesses it from the backend."""
    if is_pallas(impl):
        from bcg_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, mask, scale, mesh=impl_mesh(impl))
    if impl == "blockwise":
        from bcg_tpu.ops.attention import blockwise_attention

        return blockwise_attention(q, k, v, mask, scale)
    return _xla_attention(q, k, v, mask, scale)


# ------------------------------------------------------------------ forward

class _Layer(NamedTuple):
    """One layer's K/V entry of a STACKED dense cache (the layer scan's
    carry), addressed and never materialised: ``stack`` holds the leaves
    [Lyr, ...], ``li`` says which layer.  :func:`_write_cache` writes
    the fresh tokens into the stack at ``li`` in place, a reader takes
    through :func:`_leaf` one ``dynamic_slice`` bounded to what it reads
    (XLA fuses it into the consumer), and the int8 decode kernel reads
    its layer through an index map (``ops/decode_attention.py``).
    Slicing the entry out of the carry and writing it back copied the
    whole layer four times a layer and decode step: 16% of a round at
    8B and 27% of the hybrid's (PERF.md section 6, PR 30).  The list
    (unrolled) cache has no stacked axis and its entries stay dicts; so
    does a paged entry, whose pool is addressed by its block table."""
    stack: Dict
    li: jax.Array


def _leaves(entry) -> Dict:
    """The dict whose keys and dtypes say what an entry stores (int8 or
    int4 scales, a block table), for either form of entry."""
    return entry.stack if isinstance(entry, _Layer) else entry


def _leaf(entry, name: str, upto: Optional[int] = None) -> jax.Array:
    """Leaf ``name`` of a dense entry as the entry's own array
    ([B, ...]), cut to cache slots [0, upto) when given."""
    leaves = _leaves(entry)
    a = leaves[name]
    # slots: last axis of a scale, S of [.., Hkv, S, Dh] int8 storage or
    # of [.., S, Hkv, Dh] bf16
    axis = a.ndim - (1 if name.endswith("_scale") else 2 if "k_scale" in leaves else 3)
    if not isinstance(entry, _Layer):
        return a if upto is None else jax.lax.slice_in_dim(a, 0, upto, axis=axis)
    sizes = [1, *a.shape[1:]]
    if upto is not None:
        sizes[axis] = upto
    return jax.lax.dynamic_slice(a, [entry.li] + [0] * (a.ndim - 1), sizes)[0]


def kv_is_int4(entry: Dict) -> bool:
    """True for a packed-int4 KV entry.  The marker is the SCALE dtype —
    int4 scales are bf16 where the int8 arm's are f32 (see
    quantize.quantize_kv_int4) — so every layout this repo stores KV in
    (dense slab, paged pool, gathered dense view, per-entry prefix KV)
    carries its own dtype without the caller needing the model head dim
    to disambiguate the packed storage shape."""
    return "k_scale" in entry and entry["k_scale"].dtype == jnp.bfloat16


def _kv_quantizer(entry: Dict):
    """The fresh-KV quantizer a quantized entry needs, so every write
    path (dense scalar, dense per-row, paged) shares one dispatch that
    cannot drift from the allocation.  Both quantizers share the
    ``[B, T, Hkv, Dh] -> (storage values, [B, T, Hkv] scales)``
    signature; only the storage head dim (packed Dh/2 vs Dh) differs."""
    if kv_is_int4(entry):
        from bcg_tpu.models.quantize import quantize_kv_int4

        return quantize_kv_int4
    from bcg_tpu.ops.decode_attention import quantize_kv

    return quantize_kv


def _kv_dequantizer(entry: Dict):
    """The matching ``(values, scale) -> f32`` dequantizer (the XLA
    fallback / gather paths; kernels dequantize in VMEM)."""
    if kv_is_int4(entry):
        from bcg_tpu.models.quantize import dequantize_kv_int4

        return dequantize_kv_int4
    from bcg_tpu.ops.decode_attention import dequantize_kv

    return dequantize_kv


def _write_cache(entry, k, v, pos):
    """Write fresh k/v into the cache entry (quantizing if it is int8
    or packed int4 — the entry's scale dtype selects, see
    :func:`_kv_quantizer`).

    ``pos`` is either a scalar (one shared cache slot for the whole
    batch — prefill chunks, the standard/fast-forward decode loops) or a
    [B] vector of PER-ROW slots (the speculative decode loop, whose rows
    advance by their own accepted-token counts and keep the cache fully
    compacted — no masked gaps streamed by later steps).

    Quantized entries store k/v [B, Hkv, S, Dh] (S-major-of-last-two):
    int8 arrays tile as (32, 128) on the last two dims, so a kernel block
    slicing S x Dh is native — the bf16 layout's [.., S, Hkv, Dh] would
    hand Mosaic (1, 128)-row int8 blocks (measured ~70x slower decode).

    A stacked cache's layer (:class:`_Layer`) takes the same update one
    axis deeper, at ``(li, ...)`` of the stack: T slots of one layer
    written in place in the scan's carry, nothing else moved.

    A PAGED entry (block pool + per-row block table, ``"tbl"`` present —
    :mod:`bcg_tpu.ops.paged_attention`) routes both position forms
    through the block-indexed scatter instead; the logical semantics
    are identical.
    """
    leaves = _leaves(entry)
    if "tbl" in leaves:
        from bcg_tpu.ops.paged_attention import paged_write

        return paged_write(entry, k, v, pos)
    if getattr(pos, "ndim", 0) == 1:
        return _write_cache_rows(entry, k, v, pos)
    if "k_scale" in leaves:
        quantize_kv = _kv_quantizer(leaves)
        kq, ksc = quantize_kv(k)   # kq: [B, T, Hkv, Dh(/2)]; ksc: [B, T, Hkv]
        vq, vsc = quantize_kv(v)
        fresh = {
            "k": (kq.transpose(0, 2, 1, 3), (0, 0, pos, 0)),
            "v": (vq.transpose(0, 2, 1, 3), (0, 0, pos, 0)),
            "k_scale": (ksc.transpose(0, 2, 1), (0, 0, pos)),
            "v_scale": (vsc.transpose(0, 2, 1), (0, 0, pos)),
        }
    else:
        fresh = {
            "k": (k.astype(leaves["k"].dtype), (0, pos, 0, 0)),
            "v": (v.astype(leaves["v"].dtype), (0, pos, 0, 0)),
        }
    stacked = isinstance(entry, _Layer)
    new = dict(leaves)
    for name, (update, at) in fresh.items():
        if stacked:
            update, at = update[None], (entry.li,) + at
        new[name] = jax.lax.dynamic_update_slice(leaves[name], update, at)
    return entry._replace(stack=new) if stacked else new


def _write_cache_rows(entry, k, v, row_pos):
    """Per-row-position variant of :func:`_write_cache`: row ``b``'s
    [T]-token chunk lands at cache slots ``[row_pos[b], row_pos[b]+T)``
    (a scatter instead of ``dynamic_update_slice``; indices are in
    bounds by the caller's slot provisioning)."""
    leaves = _leaves(entry)
    stacked = isinstance(entry, _Layer)
    lead = (entry.li,) if stacked else ()
    new = dict(leaves)
    B, T = k.shape[0], k.shape[1]
    bidx = jnp.arange(B)[:, None]                       # [B, 1]
    sidx = row_pos[:, None] + jnp.arange(T)[None, :]    # [B, T]
    if "k_scale" in leaves:
        quantize_kv = _kv_quantizer(leaves)
        kq, ksc = quantize_kv(k)   # kq: [B, T, Hkv, Dh(/2)]; ksc: [B, T, Hkv]
        vq, vsc = quantize_kv(v)
        # Storage [B, Hkv, S, Dh] / scales [B, Hkv, S]: advanced indices
        # on axes (0, 2) move to the front (with a stack's scalar layer
        # index before them, which broadcasts), so the target region is
        # [B, T, Hkv, Dh] / [B, T, Hkv] — already the fresh-KV layout.
        at = lead + (bidx, slice(None), sidx)
        fresh = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    else:
        at = lead + (bidx, sidx)
        fresh = {"k": k.astype(leaves["k"].dtype), "v": v.astype(leaves["v"].dtype)}
    for name, update in fresh.items():
        new[name] = leaves[name].at[at].set(update)
    return entry._replace(stack=new) if stacked else new


def _sp_args(entry):
    """``k, v`` and the scale keywords of the ``sp`` ring forms
    (``ops/ring_attention.py``): one layer's whole leaves, of which each
    device reads its own S/sp slice."""
    leaves = _leaves(entry)
    assert not kv_is_int4(leaves), (
        "int4 KV does not compose with sp-sharded decode (the ring "
        "kernels dequantize int8 scales) — the engine rejects the "
        "pairing at boot"
    )
    scales = {n: _leaf(entry, n) for n in ("k_scale", "v_scale") if n in leaves}
    return _leaf(entry, "k"), _leaf(entry, "v"), scales


def _int8_kernel_args(entry):
    """``k, v`` and the keyword arguments with which
    ``ops/decode_attention``'s two forms read an int8 entry: a stacked
    layer hands over the whole stack and its index (the kernel's index
    map finds the layer), a dict entry its own leaves."""
    leaves = _leaves(entry)
    kw = {"k_scale": leaves["k_scale"], "v_scale": leaves["v_scale"]}
    if isinstance(entry, _Layer):
        kw["layer"] = entry.li
    return leaves["k"], leaves["v"], kw


def _cache_attention(q, entry, mask, scale, impl: str):
    """Decode-step attention over the (possibly int8) cache.

    q: [B, 1, H, Dh]; mask: [B, S] attendable slots.  A Pallas ``impl``
    (the engine resolves it only for a dense int8 cache on a TPU with a
    lane-aligned head dim) streams the cache once and dequantizes in
    VMEM; any other ``impl`` dequantizes and runs the stock einsum.
    """
    leaves = _leaves(entry)
    if "tbl" in leaves:
        # Paged cache (ops/paged_attention.py): ``impl`` carries the
        # engine-resolved paged marker — "paged_pallas"(+"_it") runs
        # the fused page-gather kernel, anything else the block-table
        # gather + stock masked attention (bit-identical to the dense
        # path given identical block contents).
        from bcg_tpu.ops.paged_attention import paged_decode_attention

        return paged_decode_attention(q, entry, mask, scale, impl=impl)
    if is_pallas(impl):
        from bcg_tpu.ops.decode_attention import decode_attention

        # The dense kernel streams unpacked int8 only.
        assert not kv_is_int4(leaves), "no dense Pallas decode for int4 KV"
        if "k_scale" in leaves:
            k, v, kw = _int8_kernel_args(entry)
        else:   # the bf16 kernel (interpret-mode checks) takes one entry
            k, v, kw = _leaf(entry, "k"), _leaf(entry, "v"), {}
        return decode_attention(
            q[:, 0], k, v, mask, scale, mesh=impl_mesh(impl), **kw)[:, None]
    k, v = _layer_kv(entry, q.dtype)
    return _xla_attention(q, k, v, mask[:, None, :], scale)


def _live_mask(mask, cache, impl, ring):
    """A decode step's (or chunk's) mask as the int8 decode kernels take
    it where they are what attends under it (a dense int8 cache, a
    Pallas ``impl``, no ``sp`` ring): with each row's first and last
    attendable slot beside it (``ops/decode_attention.LiveMask``),
    reduced HERE, once a step, and not once a layer inside the layer
    scan.  Every other path gets the mask as it came."""
    if isinstance(impl, HybridImpl):
        impl = impl.attention
    entry = _kv_entry(cache)
    if (ring is not None or not is_pallas(impl)
            or "k_scale" not in entry or "tbl" in entry):
        return mask
    from bcg_tpu.ops.decode_attention import LiveMask, live_slots

    return LiveMask(mask, live_slots(mask))


def _kv_entry(cache) -> Dict:
    """The leaves of a K/V entry of a cache in any of its forms: a
    list's first attention entry, a stack, a hybrid's ``{kind: stack}``."""
    if isinstance(cache, dict):
        return cache.get(FULL_ATTENTION, cache)
    return next(e for e in cache if "k" in e)


def _cache_len(cache) -> int:
    """Allocated cache length S, across layouts: bf16 k is
    [(Lyr,) B, S, Hkv, Dh]; quantized storage is [(Lyr,) B, Hkv, S, Dh]."""
    entry = _kv_entry(cache)
    return entry["k"].shape[-2 if "k_scale" in entry else -3]


def _dequant_slice(entry, name: str, upto: Optional[int], dtype) -> jax.Array:
    """Cache slots [0, upto) (all of them: None) of k or v as
    [B, upto, Hkv, Dh], dequantized (and transposed out of the
    [B, Hkv, S, Dh] storage) if stored int8.  Of a stacked layer only
    that much of that layer is read.  Paged entries gather only the
    table's first ``upto / bs`` block columns (the caller block-aligns
    the prefix region) to the same dense view first."""
    if "tbl" in _leaves(entry):
        from bcg_tpu.ops.paged_attention import block_size, paged_gather_entry

        bs = block_size(entry)
        assert upto % bs == 0, (
            f"paged history window {upto} not block-aligned (bs={bs})"
        )
        entry = paged_gather_entry(entry, upto_blocks=upto // bs)
    leaves = _leaves(entry)
    scale_name = f"{name}_scale"
    if scale_name not in leaves:
        return _leaf(entry, name, upto).astype(dtype)
    dequantize_kv = _kv_dequantizer(leaves)

    # astype BEFORE the transpose: the transpose is the materialization
    # point, and a bf16 buffer halves its traffic vs transposing in f32.
    return dequantize_kv(
        _leaf(entry, name, upto), _leaf(entry, scale_name, upto)
    ).astype(dtype).transpose(0, 2, 1, 3)


def _layer_kv(entry, dtype):
    """ONE layer's whole K and V in the attention layout
    [B, S, Hkv, Dh], for the XLA fallbacks: a quantized entry's (slow
    path) full dequant to ``dtype``, a bf16 one as stored."""
    if "k_scale" in _leaves(entry):
        return (_dequant_slice(entry, "k", None, dtype),
                _dequant_slice(entry, "v", None, dtype))
    return _leaf(entry, "k"), _leaf(entry, "v")


def _block(
    layer: Dict,
    spec: ModelSpec,
    x: jax.Array,              # [B, T, D]
    cos: jax.Array,
    sin: jax.Array,
    kv_write_pos: jax.Array,   # scalar: where in the cache to write
    cache_entry: Dict,         # {k, v[, k_scale, v_scale]}, [B, S, ...]
    attn_mask: jax.Array,      # prefill: [B, T, hist_len+T] over hist+chunk;
                               # decode (T == 1): [B, S] over the cache
    impl: str,
    hist_len: int = 0,         # static: cache slots [0, hist_len) hold a
                               # reusable prefix (prefix caching)
    ring=None,                 # static (Mesh, axis_name): sequence-parallel
                               # ring attention for the full-prefill branch
    kv_valid=None,             # [B, T] bool, ring mode only (pads False)
) -> Tuple[jax.Array, Dict]:
    B, T, D = x.shape
    with jax.named_scope(scopes.ATTN):
        h = rms_norm(x, layer["attn_norm"], spec.rms_eps)
        q, k, v = dense(h, layer["wq"]), dense(h, layer["wk"]), dense(h, layer["wv"])
        if "bq" in layer:  # Qwen2-style projection biases
            q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
        q = q.reshape(B, T, spec.num_heads, spec.head_dim)
        k = k.reshape(B, T, spec.num_kv_heads, spec.head_dim)
        v = v.reshape(B, T, spec.num_kv_heads, spec.head_dim)
        if spec.qk_norm:
            q = rms_norm(q, layer["q_norm"], spec.rms_eps)
            k = rms_norm(k, layer["k_norm"], spec.rms_eps)
        if cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        attn_out, new_entry = _attend(
            spec, q, k, v, kv_write_pos, cache_entry, attn_mask, impl,
            hist_len=hist_len, ring=ring, kv_valid=kv_valid,
        )
        x = x + dense(attn_out.reshape(B, T, spec.q_size), layer["wo"])

    with jax.named_scope(scopes.FFN):
        x = x + _swiglu(layer, rms_norm(x, layer["mlp_norm"], spec.rms_eps))
    return x, new_entry


def _swiglu(layer: Dict, h: jax.Array) -> jax.Array:
    """The SwiGLU MLP on its (normed) input: every block's second half."""
    gate = jax.nn.silu(dense(h, layer["w_gate"]))
    return dense(gate * dense(h, layer["w_up"]), layer["w_down"])


def _attend(
    spec: ModelSpec,
    q: jax.Array,              # [B, T, H, Dh], positions applied
    k: jax.Array,              # [B, T, Hkv, Dh]
    v: jax.Array,
    kv_write_pos: jax.Array,
    cache_entry: Dict,
    attn_mask: jax.Array,
    impl: str,
    hist_len: int = 0,
    ring=None,
    kv_valid=None,
) -> Tuple[jax.Array, Dict]:
    """Write the fresh K/V into the layer's cache entry and attend: the
    attention of :func:`_block` (arguments as there), shared with the
    hybrid family's full-attention block.  Returns the attention output
    [B, T, H, Dh] and the updated entry."""
    T = q.shape[1]
    new_entry = _write_cache(cache_entry, k, v, kv_write_pos)

    scale = 1.0 / math.sqrt(spec.head_dim)
    if T > 1 and ring is not None:
        # Sequence-parallel full prefill: K/V blocks rotate over the sp
        # ring (ops/ring_attention.py) instead of materializing the
        # [B, T, T] mask and scores on one device.  Causality is by
        # physical position (left-padding preserves order) and pads are
        # masked via kv_valid — exactly prefill()'s mask semantics.
        from bcg_tpu.ops.ring_attention import ring_attention

        assert hist_len == 0, "ring prefill has no cached-prefix path"
        mesh, axis_name = ring
        attn_out = ring_attention(
            q, k, v, mesh, axis_name=axis_name, causal=True, scale=scale,
            kv_valid=kv_valid,
        )
    elif T > 1 and hist_len > 0:
        # Suffix prefill: the chunk attends over the cached prefix KV
        # plus itself.  Prefix slots are read once per call instead of
        # being recomputed — the point of prefix caching.
        hk = _dequant_slice(cache_entry, "k", hist_len, q.dtype)
        hv = _dequant_slice(cache_entry, "v", hist_len, q.dtype)
        attn_out = attention(
            q, jnp.concatenate([hk, k], axis=1),
            jnp.concatenate([hv, v], axis=1), attn_mask, scale, impl,
        )
    elif T > 1:
        # Prefill attends over the FRESH bf16 chunk (nothing earlier is
        # in the cache), so prefill cost is O(L^2) not O(L*S_cache) and
        # is unaffected by cache quantization.
        attn_out = attention(q, k, v, attn_mask, scale, impl)
    elif ring is not None:
        # Sequence-parallel decode: the cache stays sharded over sp and
        # each device attends its slice; partials merge via pmax/psum of
        # O(B*H) stats (ops/ring_attention.sp_decode_attention).  An
        # int8 cache dequantizes only its local S/sp slice inside the
        # shard_map.  Indivisible cache length is a LOUD error, not a
        # silent fallback: the engine aligns its cache allocation to sp
        # (jax_engine._kv_align), so reaching here with S % sp != 0
        # means that guarantee broke — and a silent replicated fallback
        # once made this whole path dead while its feature flag read as
        # active.
        from bcg_tpu.ops.ring_attention import sp_decode_attention

        mesh, axis_name = ring
        ck, cv, scales = _sp_args(new_entry)
        attn_out = sp_decode_attention(
            q[:, 0], ck, cv, attn_mask, mesh,
            axis_name=axis_name, scale=scale, **scales,
        )[:, None]
    else:
        attn_out = _cache_attention(q, new_entry, attn_mask, scale, impl)
    return attn_out, new_entry


# ------------------------------------------------------- the hybrid family

class _Ctx(NamedTuple):
    """What a hybrid block needs of the call beside its own layer and
    its own cache entry."""
    cos: Optional[jax.Array]
    sin: Optional[jax.Array]
    write_pos: jax.Array           # cache slot of the chunk's column 0
    attn_mask: jax.Array           # as :func:`_block` takes it
    hist_len: int
    valid: Optional[jax.Array]     # [B, T] bool, False on pads; None in a
                                   # decode step (every row's token counts)
    impl: HybridImpl               # what each kind of layer runs


def _norm_in(spec: ModelSpec, x, weight):
    """The sublayer's input under the spec's norm placement."""
    return x if spec.norm_placement == "post" else rms_norm(x, weight, spec.rms_eps)


def _norm_out(spec: ModelSpec, y, weight):
    """The sublayer's output under the spec's norm placement."""
    return rms_norm(y, weight, spec.rms_eps) if spec.norm_placement == "post" else y


@jax.named_scope(scopes.FFN)
def _mlp_sublayer(layer: Dict, spec: ModelSpec, x: jax.Array, ctx: _Ctx):
    """The SwiGLU half of a hybrid layer.  Returns the new residual and
    the held experts the step hit (none: it has no experts)."""
    y = _swiglu(layer, _norm_in(spec, x, layer["mlp_norm"]))
    return x + _norm_out(spec, y, layer["mlp_norm"]), jnp.int32(0)


@jax.named_scope(scopes.FFN)
def _moe_sublayer(layer: Dict, spec: ModelSpec, x: jax.Array, ctx: _Ctx):
    """Routed experts with a shared one (``ops/moe.py``): the router
    spans all ``num_experts``, the chip adds the part of the experts it
    holds (``0 .. experts_held - 1``) and the shared expert's.  Pad
    positions take no expert's work.  Returns the new residual and the
    expert work's count: in a decode step the distinct held experts its
    rows hit (what the decode kernel streams), in a prefill the (token,
    held expert) assignments of its real tokens (what the grouped matmul
    computes)."""
    from bcg_tpu.ops import moe

    B, T, D = x.shape
    h = _norm_in(spec, x, layer["mlp_norm"])
    flat = h.reshape(B * T, D)
    routing = moe.route(flat, layer["moe_router"], spec.experts_per_token,
                        spec.norm_topk_prob, spec.routed_scaling_factor)
    decode = ctx.valid is None
    experts = [layer[n] for n in ("moe_w_gate", "moe_w_up", "moe_w_down")]
    li = experts[0].li if isinstance(experts[0], _Layer) else None
    y = moe.routed_experts(
        flat, routing, *(e.stack if isinstance(e, _Layer) else e for e in experts),
        valid=None if decode else ctx.valid.reshape(-1), decode=decode,
        impl=ctx.impl.moe, layer=li)
    if spec.shared_experts:
        y = y + _swiglu(layer, flat).astype(jnp.float32)
    y = y.astype(x.dtype).reshape(B, T, D)
    if decode:
        count = moe.experts_hit(routing, 0, spec.experts_held)
    else:
        chosen = moe.held_weights(routing, 0, spec.experts_held)[1]
        count = jnp.sum(chosen & ctx.valid.reshape(-1, 1)).astype(jnp.int32)
    return x + _norm_out(spec, y, layer["mlp_norm"]), count


@jax.named_scope(scopes.ATTN)
def _block_full(layer: Dict, spec: ModelSpec, x: jax.Array, entry: Dict,
                ctx: _Ctx) -> Tuple[jax.Array, Dict]:
    """A hybrid's full-attention mixer: the dense family's attention
    (:func:`_attend`: same cache entry, same kernels) under the spec's
    norm placement, q/k norm form and rotary setting, its output gated
    elementwise by ``sigmoid(x W_g)`` where the layer has ``wg``."""
    B, T, _ = x.shape
    h = _norm_in(spec, x, layer["attn_norm"])
    q, k, v = dense(h, layer["wq"]), dense(h, layer["wk"]), dense(h, layer["wv"])
    if spec.qk_norm == "full":   # over the whole projection, before the split
        q = rms_norm(q, layer["q_norm"], spec.rms_eps)
        k = rms_norm(k, layer["k_norm"], spec.rms_eps)
    q = q.reshape(B, T, spec.num_heads, spec.head_dim)
    k = k.reshape(B, T, spec.num_kv_heads, spec.head_dim)
    v = v.reshape(B, T, spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm is True:     # per head
        q = rms_norm(q, layer["q_norm"], spec.rms_eps)
        k = rms_norm(k, layer["k_norm"], spec.rms_eps)
    if ctx.cos is not None:
        q = apply_rope(q, ctx.cos, ctx.sin)
        k = apply_rope(k, ctx.cos, ctx.sin)
    attn_out, new_entry = _attend(
        spec, q, k, v, ctx.write_pos, entry, ctx.attn_mask, ctx.impl.attention,
        hist_len=ctx.hist_len,
    )
    attn_out = attn_out.reshape(B, T, spec.q_size)
    if "wg" in layer:
        attn_out = attn_out * jax.nn.sigmoid(dense(h, layer["wg"]))
    y = dense(attn_out, layer["wo"])
    return x + _norm_out(spec, y, layer["attn_norm"]), new_entry


def _causal_conv(u: jax.Array, taps: jax.Array, tail: jax.Array,
                 valid: Optional[jax.Array], bias: Optional[jax.Array] = None):
    """The depthwise causal conv of a recurrent mixer, then silu: channel
    ``c`` of position ``t`` is ``silu(sum_i taps[i, c] u[t - K + 1 + i, c]
    (+ bias[c]))`` over its ``K`` taps, the positions before the chunk
    read from ``tail`` [B, K-1, C] (the last K - 1 inputs so far, zeros
    before a sequence starts).  A pad position's input is zero.  Returns
    the output [B, T, C] float32 and the new tail, in the tail's dtype."""
    T, f32 = u.shape[1], jnp.float32
    if valid is not None:
        u = jnp.where(valid[..., None], u, 0)
    window = jnp.concatenate([tail, u.astype(tail.dtype)], axis=1)
    taps = taps.astype(f32)                                        # [K, C]
    y = sum(taps[i] * window[:, i:i + T].astype(f32) for i in range(taps.shape[0]))
    if bias is not None:
        y = y + bias.astype(f32)
    return jax.nn.silu(y), window[:, T:]


def _short_conv(layer: Dict, spec: ModelSpec, h: jax.Array, entry: Dict, ctx: _Ctx):
    """A delta-rule mixer's q, k, v: the three projections through the
    depthwise causal conv (:func:`_causal_conv`, no bias), q and k
    L2-normalised per head and q scaled by ``dk^-1/2``.  Returns q, k
    [B, T, H, dk], v [B, T, H, dv] (float32) and the conv's new tail."""
    B, T, _ = h.shape
    H = spec.linear_num_value_heads
    dk, dv = spec.linear_key_head_dim, spec.linear_value_head_dim
    u = jnp.concatenate(
        [dense(h, layer["lin_wq"]), dense(h, layer["lin_wk"]),
         dense(h, layer["lin_wv"])], axis=-1)                     # [B, T, C]
    y, tail = _causal_conv(u, layer["lin_conv"], entry["conv"], ctx.valid)
    q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
    q, k = q.reshape(B, T, H, dk), k.reshape(B, T, H, dk)
    v = v.reshape(B, T, H, dv)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    return unit(q) * dk ** -0.5, unit(k), v, tail


@jax.named_scope(scopes.LINEAR_MIXER)
def _block_gated_delta(layer: Dict, spec: ModelSpec, x: jax.Array, entry: Dict,
                       ctx: _Ctx) -> Tuple[jax.Array, Dict]:
    """A hybrid's linear-attention mixer: the gated delta rule
    (``ops/gated_delta.py``).  Its cache entry is the recurrent state
    ``S`` [B, H, dv, dk] float32 and the conv tail ``conv`` [B, K-1,
    channels] (the last K-1 inputs of the depthwise causal conv).

    A pad position (``ctx.valid`` False) leaves both as they were: its
    conv input is zero (what the conv sees before a sequence starts,
    and pads stand only before a row's tokens), its decay is 0 and its
    write strength 0.  So a row's state is that of the row alone, and
    a chunk of pads maps zeros to zeros."""
    from bcg_tpu.ops.gated_delta import gated_delta_prefill, gated_delta_step

    B, T, _ = x.shape
    H, dv = spec.linear_num_value_heads, spec.linear_value_head_dim
    f32 = jnp.float32
    h = _norm_in(spec, x, layer["attn_norm"])
    q, k, v, tail = _short_conv(layer, spec, h, entry, ctx)

    gate = lambda w: jnp.dot(h, w, preferred_element_type=f32)    # noqa: E731  [B, T, H]
    beta = jax.nn.sigmoid(gate(layer["lin_wb"]))
    if spec.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(layer["lin_a_log"].astype(f32)) * jax.nn.softplus(
        gate(layer["lin_wa"]) + layer["lin_dt_bias"].astype(f32))
    if ctx.valid is None:       # one decoded token: the recurrence itself
        o, S = gated_delta_step(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], entry["S"])
        o = o[:, None]
    else:
        g = jnp.where(ctx.valid[..., None], g, 0.0)
        beta = jnp.where(ctx.valid[..., None], beta, 0.0)
        o, S = gated_delta_prefill(
            q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype), g, beta,
            entry["S"], impl=ctx.impl.delta)
    new_entry = {"S": S, "conv": tail}

    o = rms_norm(o.astype(x.dtype), layer["lin_out_norm"], spec.rms_eps)
    o = o * jax.nn.silu(dense(h, layer["lin_wg"])).reshape(B, T, H, dv)
    y = dense(o.reshape(B, T, H * dv), layer["lin_wo"])
    return x + _norm_out(spec, y, layer["attn_norm"]), new_entry


@jax.named_scope(scopes.LINEAR_MIXER)
def _block_kda(layer: Dict, spec: ModelSpec, x: jax.Array, entry: Dict,
               ctx: _Ctx) -> Tuple[jax.Array, Dict]:
    """A hybrid's KDA mixer: the channel-gated delta rule
    (``ops/gated_delta.kda_prefill`` / ``kda_step``), its cache entry
    and its pad handling as :func:`_block_gated_delta`'s.  The decay is a
    vector over the key channels, ``g = -exp(A_log) softplus(f_b(f_a(x))
    + dt_bias)``, the write strength ``beta = 2 sigmoid(x W_b)``, and the
    output ``W_o(rmsnorm_head(o) * sigmoid(g_b(g_a(x))))``."""
    from bcg_tpu.ops.gated_delta import kda_prefill, kda_step

    B, T, _ = x.shape
    H, dk, dv = (spec.linear_num_value_heads, spec.linear_key_head_dim,
                 spec.linear_value_head_dim)
    f32 = jnp.float32
    h = _norm_in(spec, x, layer["attn_norm"])
    q, k, v, tail = _short_conv(layer, spec, h, entry, ctx)

    def low_rank(a, b):       # [B, T, out] float32 through the rank-r pair
        return jnp.dot(jnp.dot(h, a, preferred_element_type=f32), b.astype(f32))

    beta = jax.nn.sigmoid(jnp.dot(h, layer["lin_wb"], preferred_element_type=f32))
    if spec.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = jax.nn.softplus(low_rank(layer["kda_fa"], layer["kda_fb"])
                        + layer["lin_dt_bias"].astype(f32)).reshape(B, T, H, dk)
    g = -jnp.exp(layer["lin_a_log"].astype(f32))[:, None] * g
    if ctx.valid is None:       # one decoded token: the recurrence itself
        o, S = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], entry["S"])
        o = o[:, None]
    else:
        g = jnp.where(ctx.valid[..., None, None], g, 0.0)
        beta = jnp.where(ctx.valid[..., None], beta, 0.0)
        o, S = kda_prefill(
            q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype), g, beta,
            entry["S"], impl=ctx.impl.delta)
    new_entry = {"S": S, "conv": tail}

    o = rms_norm(o.astype(x.dtype), layer["lin_out_norm"], spec.rms_eps)
    gate = jax.nn.sigmoid(low_rank(layer["kda_ga"], layer["kda_gb"]))
    o = o * gate.astype(x.dtype).reshape(B, T, H, dv)
    y = dense(o.reshape(B, T, H * dv), layer["lin_wo"])
    return x + _norm_out(spec, y, layer["attn_norm"]), new_entry


@jax.named_scope(scopes.LINEAR_MIXER)
def _block_mamba(layer: Dict, spec: ModelSpec, x: jax.Array, entry: Dict,
                 ctx: _Ctx) -> Tuple[jax.Array, Dict]:
    """A hybrid's Mamba-1 mixer (``ops/selective_scan.py``).  Its cache
    entry is the scan state ``S`` [B, N, channels] float32 and the conv
    tail ``conv`` [B, K-1, channels].  ``[u, z] = h W_in``; ``u`` through
    the conv with its bias and silu; ``[d, B, C] = u W_x``, each RMS-normed
    by its own vector; the step size ``delta = softplus(d W_dt + b_dt)``;
    the scan's output ``y`` gated by ``silu(z)`` and projected by
    ``W_out``.  The discretisation and the scan run under the free
    sub-scope ``scan``.  A pad position leaves the state and the tail as
    they were: its conv input is zero and its step size 0."""
    from bcg_tpu.ops.selective_scan import mamba_prefill, mamba_step

    R, N = spec.mamba_dt_rank, spec.mamba_d_state
    f32 = jnp.float32
    h = _norm_in(spec, x, layer["attn_norm"])
    u, z = jnp.split(dense(h, layer["ssm_in"]), 2, axis=-1)
    u, tail = _causal_conv(u, layer["ssm_conv"], entry["conv"], ctx.valid,
                           layer["ssm_conv_bias"])
    with jax.named_scope("scan"):
        dbc = jnp.dot(u.astype(x.dtype), layer["ssm_x"], preferred_element_type=f32)
        d, Bm, Cm = jnp.split(dbc, [R, R + N], axis=-1)
        d = rms_norm(d, layer["ssm_dt_norm"], spec.rms_eps)
        Bm = rms_norm(Bm, layer["ssm_b_norm"], spec.rms_eps)
        Cm = rms_norm(Cm, layer["ssm_c_norm"], spec.rms_eps)
        delta = jax.nn.softplus(
            jnp.dot(d.astype(x.dtype), layer["ssm_dt"], preferred_element_type=f32)
            + layer["ssm_dt_bias"].astype(f32))
        A = -jnp.exp(layer["ssm_a_log"].astype(f32))                # [channels, N]
        if ctx.valid is None:       # one decoded token: the recurrence itself
            y, S = mamba_step(u[:, 0], delta[:, 0], Bm[:, 0], Cm[:, 0], A,
                              layer["ssm_d"], entry["S"])
            y = y[:, None]
        else:
            delta = jnp.where(ctx.valid[..., None], delta, 0.0)
            y, S = mamba_prefill(u, delta, Bm, Cm, A, layer["ssm_d"], entry["S"],
                                 impl=ctx.impl.ssm)
    y = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype)
    out = dense(y, layer["ssm_out"])
    return x + _norm_out(spec, out, layer["attn_norm"]), {"S": S, "conv": tail}


# Layer type -> its mixer, and the MLP half -> its sublayer.  A further
# mechanism adds a function and an entry.
_HYBRID_BLOCKS = {
    FULL_ATTENTION: _block_full,
    LINEAR_ATTENTION: _block_gated_delta,
    KDA: _block_kda,
    MAMBA: _block_mamba,
    "swiglu": _mlp_sublayer,
    "moe": _moe_sublayer,
}


def _hybrid_layer(kind: str, layer: Dict, spec: ModelSpec, x, entry, ctx: _Ctx):
    """One hybrid layer: its mixer, then its MLP half.  Returns the
    residual, the mixer's new entry and the experts the step hit."""
    x, entry = _HYBRID_BLOCKS[kind](layer, spec, x, entry, ctx)
    x, hits = _HYBRID_BLOCKS["moe" if spec.moe else "swiglu"](layer, spec, x, ctx)
    return x, entry, hits


def _carried_entry(stack: Dict, li):
    """Layer ``li``'s entry of one stacked state in the layer scan's
    carry.  Dense K/V is addressed in place (:class:`_Layer`): a step
    adds T slots to it.  A paged pool's entry and a delta-rule layer's
    ``S`` / ``conv`` are sliced out and written back whole: the first is
    addressed by its block table, the second rewritten whole each step
    by design (XLA updates it in place already)."""
    if "k" in stack and "tbl" not in stack:
        return _Layer(stack, li)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False), stack)


def _carry_with(stack: Dict, li, entry) -> Dict:
    """The stacked state after layer ``li``'s block returned ``entry``."""
    if isinstance(entry, _Layer):
        return entry.stack
    return jax.tree.map(
        lambda a, e: jax.lax.dynamic_update_index_in_dim(a, e, li, 0),
        stack, entry)


def _layer_weights(stack: Dict, li) -> Dict:
    """Layer ``li``'s weights from one type's stacks, for the scan body:
    each leaf sliced where it is used (XLA fuses the slice into its
    consumer), but a held-expert stack handed on whole with its index
    (:class:`_Layer`): the expert kernels read their layer through their
    index maps, where a slice would be copied out for them (630 MB a
    layer and decode step at published widths)."""
    take = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)  # noqa: E731
    return {name: _Layer(leaf, li) if name.startswith("moe_w_") else jax.tree.map(take, leaf)
            for name, leaf in stack.items()}


def _run_layers_hybrid(params: TransformerParams, spec: ModelSpec,
                       x: jax.Array, cache, ctx: _Ctx):
    """:func:`_run_layers` for a spec with ``layer_types``.  List form:
    a Python loop over the layers, each with its own cache entry.
    Stacked form (``stack_layer_params``: one stack per type): ONE
    ``lax.scan`` over the PERIODS of the pattern, whose body applies the
    period's layers in order, so the program is O(1) in depth; both
    kinds of state ride the carry, indexed per type
    (:func:`_carried_entry`: K/V in place, recurrent state whole).
    Returns the residual, the new cache and the expert work's count
    (:func:`_moe_sublayer`), summed over layers (0 without experts; only
    a spec with experts carries it through the scan)."""
    layers = params["layers"]
    hits = jnp.int32(0)
    if not isinstance(layers, dict):
        new_cache = []
        for layer, kind, entry in zip(layers, spec.layer_types, cache):
            x, entry, hit = _hybrid_layer(kind, layer, spec, x, entry, ctx)
            hits = hits + hit
            new_cache.append(entry)
        return x, new_cache, hits

    period = spec.layer_period
    per = {kind: period.count(kind) for kind in dict.fromkeys(period)}

    def body(carry, p):
        h, c, *hits = carry
        seen = dict.fromkeys(per, 0)
        for kind in period:
            # the period's j-th layer of this kind is the type's stack's
            # (p * per-period + j)-th: ONE layer's weights are sliced where
            # they are used (a period's slab as the scan's xs is copied
            # whole every iteration: 5 GB a decode step at 7B, measured)
            li = p * per[kind] + seen[kind]
            seen[kind] += 1
            # A recurrent layer's state leaves the carry and goes back
            # whole: its mixer's traffic (the one-token update fuses into
            # the write back), so its mixer's part.
            part = scopes.LINEAR_MIXER if kind in RECURRENT_TYPES else scopes.LAYERS
            with jax.named_scope(part):
                entry = _carried_entry(c[kind], li)
            h, entry, hit = _hybrid_layer(
                kind, _layer_weights(layers[kind], li), spec, h, entry, ctx)
            with jax.named_scope(part):
                c = {**c, kind: _carry_with(c[kind], li, entry)}
            hits = [n + hit for n in hits]
        return (h, c, *hits), None

    (x, new_cache, *counted), _ = jax.lax.scan(
        body, (x, cache, *([hits] if spec.moe else [])),
        jnp.arange(spec.num_layers // len(period)))
    return x, new_cache, (counted or [hits])[0]


@jax.named_scope(scopes.LAYERS)
def _run_layers(
    params: TransformerParams,
    spec: ModelSpec,
    x: jax.Array,
    cos, sin,
    write_pos: jax.Array,
    cache,
    attn_mask: jax.Array,
    impl: str,
    hist_len: int = 0,
    chunk: bool = False,
    ring=None,
    kv_valid=None,
    valid=None,
    tally: bool = False,
):
    """Apply every decoder block: a Python loop for list-form params
    (each layer unrolled into the HLO — best when the program already
    compiles), or ONE ``lax.scan`` over stacked params + stacked cache
    (program size O(1) in depth — the 8B-unblocking path; see
    ``stack_layer_params``).  The scanned cache rides the scan CARRY —
    riding xs/ys would materialize a second full cache, which OOMs at
    8B — keeps the same [Lyr, ...] layout, and is updated in it in
    place: a layer's dense K/V entry is addressed as (stack, layer
    index) and never sliced out (:class:`_Layer`, :func:`_carried_entry`).

    A spec with ``layer_types`` goes through :func:`_run_layers_hybrid`
    (a block per layer type, two kinds of state); ``valid`` ([B, T],
    False on pads) is what its recurrent layers need of a prefill, and
    the call forms it is not built for raise here, at trace time.  With
    ``tally`` a hybrid's call returns a third value, the count of its
    expert work (:func:`_run_layers_hybrid`)."""
    if spec.hybrid:
        if chunk or ring is not None or (valid is None and x.shape[1] > 1):
            raise NotImplementedError(
                f"{spec.name}: a spec with layer_types runs prefill, "
                "prefill_chunk_at and decode_step only (no decode chunk, "
                "ring, cached-prefix or paged form carries recurrent state)")
        if not isinstance(impl, HybridImpl):
            # a plain name (a decode loop's): the delta rule's one-token
            # step is XLA's, the expert kernels follow the attention's
            impl = HybridImpl(impl, "xla", "pallas" if is_pallas(impl) else "xla")
        x, cache, hits = _run_layers_hybrid(params, spec, x, cache, _Ctx(
            cos, sin, write_pos, attn_mask, hist_len, valid, impl))
        return (x, cache, hits) if tally else (x, cache)
    layers = params["layers"]
    if isinstance(layers, dict):
        # The cache rides the scan CARRY, not xs/ys: ys would be a second
        # full-cache allocation (XLA could not alias the donated input
        # through scan — measured OOM at 8B where cache ~6.8 GB), while
        # carry buffers update in place inside the underlying while loop.
        num_layers = jax.tree.leaves(cache)[0].shape[0]

        def body(carry, per_layer):
            h, c = carry
            li, lp = per_layer
            ce = _carried_entry(c, li)
            if chunk:
                h, entry = _block_chunk(
                    lp, spec, h, cos, sin, write_pos, ce, attn_mask, impl,
                    ring=ring,
                )
            else:
                h, entry = _block(
                    lp, spec, h, cos, sin, write_pos, ce, attn_mask, impl,
                    hist_len=hist_len, ring=ring, kv_valid=kv_valid,
                )
            return (h, _carry_with(c, li, entry)), None

        (x, new_cache), _ = jax.lax.scan(
            body, (x, cache), (jnp.arange(num_layers), layers)
        )
        return x, new_cache
    new_cache = []
    for li, layer in enumerate(layers):
        if chunk:
            x, entry = _block_chunk(
                layer, spec, x, cos, sin, write_pos, cache[li], attn_mask,
                impl, ring=ring,
            )
        else:
            x, entry = _block(
                layer, spec, x, cos, sin, write_pos, cache[li], attn_mask,
                impl, hist_len=hist_len, ring=ring, kv_valid=kv_valid,
            )
        new_cache.append(entry)
    return x, new_cache


def _logits(params: TransformerParams, spec: ModelSpec, x: jax.Array) -> jax.Array:
    h = rms_norm(x, params["final_norm"], spec.rms_eps)
    # Quantized tied-embedding models carry an explicit quantized lm_head
    # (see quantize.quantize_params), so prefer it when present; an untied
    # model without one is a loader bug that must stay loud.
    if "lm_head" in params:
        return dense(h, params["lm_head"], out_dtype=jnp.float32)
    if not spec.tie_embeddings:
        raise KeyError(f"params for untied model {spec.name!r} lack 'lm_head'")
    return jnp.dot(h, params["embed"].T, preferred_element_type=jnp.float32)


def init_kv_cache(
    spec: ModelSpec, batch: int, max_len: int, dtype=jnp.bfloat16,
    quantized=False, stacked: bool = False,
):
    """Per-layer list of {k, v[, k_scale, v_scale]} leaves, or — with
    ``stacked`` — ONE dict whose leaves carry a leading [num_layers] dim
    (the scan-over-layers cache; must match ``stack_layer_params``).

    k/v are [B, S, Hkv, Dh]; with ``quantized`` (True or ``"int8"``)
    they are int8 stored [B, Hkv, S, Dh] — int8 tiles as (32, 128) over
    the last two dims, so an S x Dh kernel block is Mosaic-native (the
    bf16 axis order would hand it (1, 128)-row int8 blocks) — with f32
    per-(position, kv-head) absmax scales stored [B, Hkv, S] (S minor,
    lane-aligned).  Halves the HBM traffic of the bandwidth-bound decode
    step; the kernels dequantize in VMEM (see ops/decode_attention.py).

    ``quantized="int4"`` packs the head dim two values per byte on the
    same axes ([B, Hkv, S, Dh/2] storage) with BF16 scales — the scale
    dtype is the layout marker (:func:`kv_is_int4`) — halving KV bytes
    again vs int8: the capacity knob that roughly doubles admissible
    batch at a fixed HBM budget (see models/quantize.py's int4-KV
    contract).

    A spec with ``layer_types`` gets K/V entries for its
    full-attention layers only and, for each linear layer, ``{"S":
    [B, H, dv, dk] float32, "conv": [B, K-1, channels]}`` (zeros: the
    state before a sequence starts); a Mamba layer's ``S`` is [B, N,
    channels].  Stacked, its cache is ``{kind:
    leaves [layers of that kind, ...]}``; :func:`cache_bytes` counts
    either form by kind of state.

    The list form keeps separate pytree leaves so the
    ``dynamic_update_slice`` in each decode step is a pure per-buffer
    update XLA can alias in-place inside ``lax.while_loop``.  The stacked
    form has one buffer per leaf, updated in place at (layer, slots) in
    the scan's carry, for an O(1)-in-depth program — the 8B compile
    unblocking."""
    if quantized == "int4":
        from bcg_tpu.models.quantize import kv_int4_layout

        dh_store, scale_dtype = kv_int4_layout(spec.head_dim)
    else:
        dh_store, scale_dtype = spec.head_dim, jnp.float32
    shape = (batch, max_len, spec.num_kv_heads, spec.head_dim)
    qshape = (batch, spec.num_kv_heads, max_len, dh_store)
    scale_shape = (batch, spec.num_kv_heads, max_len)

    def entry(lead=()):
        if quantized:
            return {
                "k": jnp.zeros(lead + qshape, jnp.int8),
                "v": jnp.zeros(lead + qshape, jnp.int8),
                "k_scale": jnp.ones(lead + scale_shape, scale_dtype),
                "v_scale": jnp.ones(lead + scale_shape, scale_dtype),
            }
        return {
            "k": jnp.zeros(lead + shape, dtype),
            "v": jnp.zeros(lead + shape, dtype),
        }

    if spec.hybrid:
        # Two kinds of state: K/V for the full-attention layers only,
        # and for each delta-rule layer its recurrent state (float32)
        # and the conv's last K-1 inputs.
        def linear(lead=()):
            return {
                "S": jnp.zeros(
                    lead + (batch, spec.linear_num_value_heads,
                            spec.linear_value_head_dim, spec.linear_key_head_dim),
                    jnp.float32),
                "conv": jnp.zeros(
                    lead + (batch, spec.linear_conv_kernel_dim - 1,
                            spec.linear_conv_size), dtype),
            }

        def mamba(lead=()):
            return {
                "S": jnp.zeros(lead + (batch, spec.mamba_d_state, spec.mamba_inner),
                               jnp.float32),
                "conv": jnp.zeros(
                    lead + (batch, spec.mamba_d_conv - 1, spec.mamba_inner), dtype),
            }

        make = {FULL_ATTENTION: entry, LINEAR_ATTENTION: linear, KDA: linear,
                MAMBA: mamba}
        if stacked:
            return {kind: make[kind](lead=(spec.layers_of(kind),))
                    for kind in dict.fromkeys(spec.layer_period)}
        return [make[kind]() for kind in spec.layer_types]
    if stacked:
        return entry(lead=(spec.num_layers,))
    return [entry() for _ in range(spec.num_layers)]


def cache_bytes(spec: ModelSpec, batch: int, max_len: int, **kw) -> Dict[str, int]:
    """Bytes of what :func:`init_kv_cache` allocates for these
    arguments, by kind of state: ``kv`` (keys, values, their scales) and
    ``linear_state`` (recurrent state and conv tail; 0 for a spec with
    no linear layer).  Read off the allocation's own shapes, so a count
    made from it cannot drift from what is allocated."""
    shapes = jax.eval_shape(partial(init_kv_cache, spec, batch, max_len, **kw))
    out = {"kv": 0, "linear_state": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        name = path[-1].key
        kind = "linear_state" if name in ("S", "conv") else "kv"
        out[kind] += math.prod(leaf.shape) * leaf.dtype.itemsize
    return out


@jax.named_scope(scopes.HEAD)
def prefill(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,        # [B, L] left-padded
    valid: jax.Array,         # [B, L] bool, False on pads
    cache: Dict,              # from init_kv_cache, written at [0, L)
    impl: str = "xla",
) -> Tuple[jax.Array, Dict]:
    """Process the full prompt; returns last-position logits and the cache.

    Left-padding: positions count only valid tokens, so RoPE sees each
    sequence starting at 0; pads are masked out of attention entirely.
    """
    B, L = tokens.shape
    positions = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = _rope_for(spec, positions)

    causal = jnp.tril(jnp.ones((L, L), bool))
    # Prefill attends over the fresh [B, L] chunk only — nothing beyond L
    # is in the cache yet, so no padded-cache slots are ever touched.
    attn_mask = causal[None] & valid[:, None, :] & valid[:, :, None]  # [B, L, L]

    x = params["embed"][tokens]
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, jnp.int32(0), cache, attn_mask, impl,
        valid=valid,
    )
    logits = _logits(params, spec, x[:, -1:, :])[:, 0, :]  # [B, V]
    return logits, new_cache


@jax.named_scope(scopes.HEAD)
def prefill_sp(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,        # [B, L] left-padded, L divisible by sp
    valid: jax.Array,         # [B, L] bool, False on pads
    cache: Dict,
    mesh,                     # jax.sharding.Mesh with an `axis_name` axis
    axis_name: str = "sp",
    impl: str = "xla",
) -> Tuple[jax.Array, Dict]:
    """Sequence-parallel full-prompt prefill: ring attention over ``sp``.

    Long-context serving (SURVEY.md §5.7 stretch goal made first-class):
    the token dimension is sharded over the ``sp`` mesh axis, so per-chip
    prefill activation memory is O(L/sp) and attention never materializes
    the [B, L, L] score matrix on one device — K/V blocks rotate around
    the ICI ring (ops/ring_attention.py).  Per-token work (norms, matmuls,
    RoPE) partitions over the same axis via the sharding constraint; XLA
    SPMD inserts the collectives.  Results match :func:`prefill` (same
    causal-by-physical-position + validity mask semantics; left-padding
    preserves order).  The reference has no long-context machinery at
    all — it compresses context instead (truncation ladders,
    bcg_agents.py:632, a2a_sim.py:69-73).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, L = tokens.shape
    sp = mesh.shape[axis_name]
    if L % sp:
        raise ValueError(f"prompt length {L} not divisible by sp={sp}")
    positions = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = _rope_for(spec, positions)

    x = params["embed"][tokens]
    x = jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, axis_name, None))
    )
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, jnp.int32(0), cache, None, impl,
        ring=(mesh, axis_name), kv_valid=valid,
    )
    logits = _logits(params, spec, x[:, -1:, :])[:, 0, :]
    return logits, new_cache


@jax.named_scope(scopes.HEAD)
def prefill_with_prefix(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,         # [B, Ls] left-padded suffix tokens
    valid: jax.Array,          # [B, Ls] bool, False on pads
    cache: Dict,               # slots [0, P) already hold prefix KV
    prefix_valid: jax.Array,   # [B, P] attendable prefix slots
    prefix_lens: jax.Array,    # [B] valid prefix token counts (RoPE offset)
    impl: str = "xla",
) -> Tuple[jax.Array, Dict]:
    """Prefill the per-call suffix against a cached prompt prefix.

    Prefix caching: the static system-prompt segment is prefilled once per
    run (slots [0, P) of the cache) and only the round-specific suffix is
    processed here, with RoPE positions continuing where each row's prefix
    ended.  The suffix chunk KV is written at slots [P, P+Ls).
    """
    B, Ls = tokens.shape
    P = prefix_valid.shape[1]
    positions = prefix_lens[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = _rope_for(spec, positions)

    causal = jnp.tril(jnp.ones((Ls, Ls), bool))
    chunk_mask = causal[None] & valid[:, None, :] & valid[:, :, None]   # [B, Ls, Ls]
    hist_mask = prefix_valid[:, None, :] & valid[:, :, None]            # [B, Ls, P]
    attn_mask = jnp.concatenate([hist_mask, chunk_mask], axis=2)        # [B, Ls, P+Ls]

    x = params["embed"][tokens]
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, jnp.int32(P), cache, attn_mask, impl,
        hist_len=P,
    )
    logits = _logits(params, spec, x[:, -1:, :])[:, 0, :]
    return logits, new_cache


@jax.named_scope(scopes.HEAD)
def prefill_paged(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,         # [B, Ls] RIGHT-padded (left-aligned) tokens
    valid: jax.Array,          # [B, Ls] bool, False on trailing pads
    cache: Dict,               # paged entries; logical slots [0, P) hold
                               # radix-shared prefix blocks
    prefix_valid: jax.Array,   # [B, P] attendable prefix slots (P may be 0)
    prefix_lens: jax.Array,    # [B] valid prefix token counts (RoPE offset)
    impl: str = "xla",
) -> Tuple[jax.Array, Dict]:
    """Prefill into a PAGED cache: the per-call chunk (full prompt when
    ``P == 0``, or the suffix past the radix-resident prefix) is written
    at logical slots ``[P, P+Ls)`` through each row's block table.

    Differs from :func:`prefill_with_prefix` in exactly two ways, both
    forced by block paging: tokens arrive LEFT-aligned (so full
    real-token blocks are radix-insertable — a left-pad would interleave
    pad KV into shareable blocks), and logits are taken at each row's
    last VALID position instead of the last physical one (with trailing
    pads those differ).  Attention math is unchanged: causality is by
    physical position, pads are masked, RoPE counts only valid tokens.
    """
    B, Ls = tokens.shape
    P = prefix_valid.shape[1]
    positions = prefix_lens[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = _rope_for(spec, positions)

    causal = jnp.tril(jnp.ones((Ls, Ls), bool))
    chunk_mask = causal[None] & valid[:, None, :] & valid[:, :, None]   # [B, Ls, Ls]
    hist_mask = prefix_valid[:, None, :] & valid[:, :, None]            # [B, Ls, P]
    attn_mask = jnp.concatenate([hist_mask, chunk_mask], axis=2)        # [B, Ls, P+Ls]

    x = params["embed"][tokens]
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, jnp.int32(P), cache, attn_mask, impl,
        hist_len=P,
    )
    last = jnp.sum(valid.astype(jnp.int32), axis=1) - 1                 # [B]
    last = jnp.maximum(last, 0)
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)        # [B, 1, D]
    logits = _logits(params, spec, h_last)[:, 0, :]
    return logits, new_cache


@jax.named_scope(scopes.HEAD)
def prefill_paged_chunk_at(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,         # [B, C] one RIGHT-padded prefill chunk
    valid: jax.Array,          # [B, C] bool, False on trailing pads
    cache: Dict,               # paged entries; slots [0, H) may hold
                               # prior context (prefix + earlier chunks)
    hist_valid: jax.Array,     # [B, H] attendable prior slots (False at
                               # and past the chunk's own write region)
    pos_offset: jax.Array,     # [B] RoPE position of each row's first
                               # valid chunk token
    write_pos: jax.Array,      # scalar int32: cache slot of chunk col 0
    carry_logits: jax.Array,   # [B, V] f32: last-valid logits so far
    impl: str = "xla",
) -> Tuple[jax.Array, Dict]:
    """One chunk of a PAGED chunked prefill — :func:`prefill_chunk_at`'s
    block-pool sibling: the history window is a fixed ``[B, H]`` mask
    and the write slot a traced scalar, so every full-width chunk of
    every offset shares one compiled program per ``(B, C, H)``, with
    two paged differences.  Chunks arrive RIGHT-padded (left-aligned,
    the radix-insertable orientation — see :func:`prefill_paged`), so a
    row's last valid token may sit mid-chunk; and because rows END in
    different chunks, the final logits thread through ``carry_logits``:
    each call takes logits at the row's last valid position *within
    this chunk* and keeps the carry for rows with no valid tokens here.
    Right-padding makes valid tokens contiguous from column 0, so after
    the final chunk the carry holds every row's true last-valid logits.
    The chunk's KV lands at logical slots ``[write_pos, write_pos+C)``
    through each row's block table; ``H`` must be block-aligned (the
    history gather reads whole table columns — the engine aligns its
    chunk size to the pool's block size)."""
    B, C = tokens.shape
    positions = pos_offset[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = _rope_for(spec, positions)

    H = hist_valid.shape[1]
    causal = jnp.tril(jnp.ones((C, C), bool))
    chunk_mask = causal[None] & valid[:, None, :] & valid[:, :, None]   # [B, C, C]
    hist_mask = hist_valid[:, None, :] & valid[:, :, None]              # [B, C, H]
    attn_mask = jnp.concatenate([hist_mask, chunk_mask], axis=2)        # [B, C, H+C]

    x = params["embed"][tokens]
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, write_pos, cache, attn_mask, impl,
        hist_len=H,
    )
    nvalid = jnp.sum(valid.astype(jnp.int32), axis=1)                   # [B]
    last = jnp.maximum(nvalid - 1, 0)
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)        # [B, 1, D]
    logits = _logits(params, spec, h_last)[:, 0, :]
    logits = jnp.where((nvalid > 0)[:, None], logits, carry_logits)
    return logits, new_cache


@jax.named_scope(scopes.HEAD)
def prefill_chunk_at(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,         # [B, C] one prefill chunk, left-aligned pads ok
    valid: jax.Array,          # [B, C] bool
    cache: Dict,               # slots [0, H) may hold prior context
    hist_valid: jax.Array,     # [B, H] attendable prior slots (False past
                               # the chunk's own write region)
    pos_offset: jax.Array,     # [B] RoPE position of each row's first
                               # valid chunk token
    write_pos: jax.Array,      # scalar int32: cache slot of chunk col 0
    impl: str = "xla",
    ring=None,                 # static (Mesh, axis_name): sp-sharded-cache
                               # chunked prefill (sp_chunk_decode_attention)
    tally: bool = False,       # static: also return the chunk's (token,
                               # held expert) assignments over the layers
):
    """One chunk of a chunked prefill with a DYNAMIC write position.

    Unlike :func:`prefill_with_prefix` (whose history width — and hence
    compiled shape — grows with every chunk offset), the history window
    here is a fixed ``[B, H]`` mask and the chunk's cache slot arrives as
    a traced scalar, so EVERY chunk of every offset shares one compiled
    program per (B, C, H): an 8B boot's L/C prefill compiles become
    one.

    With ``ring`` the chunk instead attends the WHOLE sp-sharded cache
    (its own slots written first) through the decode loops' chunk path —
    this matters most for the LARGE size class, whose default config is
    exactly chunked prefill, so an 8B+ long-context sp deployment would
    otherwise never engage sequence parallelism at prefill.
    """
    B, C = tokens.shape
    positions = pos_offset[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = _rope_for(spec, positions)

    H = hist_valid.shape[1]
    causal = jnp.tril(jnp.ones((C, C), bool))
    chunk_mask = causal[None] & valid[:, None, :] & valid[:, :, None]   # [B, C, C]
    hist_mask = hist_valid[:, None, :] & valid[:, :, None]              # [B, C, H]

    x = params["embed"][tokens]
    if ring is not None:
        # [B, C, S] whole-cache mask: history slots in [0, H) (hist_valid
        # is already False at and past the chunk's write region), the
        # chunk's own causally-visible slots at [write_pos, write_pos+C).
        # _block_chunk writes the chunk KV before attending, so the key
        # set matches the hist-concat form exactly; only the (sharded)
        # storage it reads from differs.
        S = _cache_len(cache)
        full_mask = jnp.zeros((B, C, S), bool)
        full_mask = full_mask.at[:, :, :H].set(hist_mask)
        full_mask = jax.lax.dynamic_update_slice(
            full_mask, chunk_mask, (0, 0, write_pos)
        )
        x, new_cache, *count = _run_layers(
            params, spec, x, cos, sin, write_pos, cache, full_mask, impl,
            chunk=True, ring=ring,
        )
    else:
        attn_mask = jnp.concatenate([hist_mask, chunk_mask], axis=2)
        x, new_cache, *count = _run_layers(
            params, spec, x, cos, sin, write_pos, cache, attn_mask, impl,
            hist_len=H, valid=valid, tally=tally,
        )
    logits = _logits(params, spec, x[:, -1:, :])[:, 0, :]
    return (logits, new_cache, *count)


@jax.named_scope(scopes.HEAD)
def decode_step(
    params: TransformerParams,
    spec: ModelSpec,
    token: jax.Array,          # [B] current tokens
    write_pos: jax.Array,      # scalar int32: cache slot to write
    seq_positions: jax.Array,  # [B] RoPE positions of these tokens
    cache: Dict,
    valid_mask: jax.Array,     # [B, S] which cache slots are attendable
    impl: str = "xla",
    ring=None,                 # static (Mesh, axis_name): sp-sharded-cache
                               # decode (ops/ring_attention.sp_decode_attention)
    tally: bool = False,       # static: also return the distinct held
                               # experts the step's rows hit, over the layers
):
    """One autoregressive step for the whole batch: ``(logits, cache)``,
    and with ``tally`` (a spec with routed experts) the step's count of
    held experts hit as a third value."""
    B = token.shape[0]
    cos, sin = _rope_for(spec, seq_positions[:, None])
    x = params["embed"][token][:, None, :]  # [B, 1, D]

    x, new_cache, *hits = _run_layers(
        params, spec, x, cos, sin, write_pos, cache,
        _live_mask(valid_mask, cache, impl, ring), impl, ring=ring, tally=tally,
    )
    logits = _logits(params, spec, x)[:, 0, :]
    return (logits, new_cache, *hits)


@jax.named_scope(scopes.HEAD)
def decode_chunk(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,         # [B, K] chunk: sampled token + forced chain
    chunk_valid: jax.Array,    # [B, K] bool; position 0 always valid
    write_pos: jax.Array,      # scalar int32: cache slot of chunk position 0
    positions: jax.Array,      # [B, K] RoPE positions (per-row real counts)
    cache: Dict,
    cache_valid: jax.Array,    # [B, S] attendable cache slots BEFORE chunk
    impl: str = "xla",
    ring=None,                 # static (Mesh, axis_name): sp-sharded-cache
                               # chunk decode (sp_chunk_decode_attention)
) -> Tuple[jax.Array, Dict]:
    """One fast-forward step: process a [B, K] token chunk against the
    cache (forced-chain fast-forward — the sampled token plus up to K-1
    DFA-forced JSON-skeleton tokens per row in a single weight pass).

    The chunk is written at cache slots [write_pos, write_pos+K); rows
    whose chain is shorter leave trailing slots invalid (gaps — masked
    from all later attention by ``cache_valid``).  Returns logits at each
    row's LAST VALID chunk position and the updated cache.
    """
    B, K = tokens.shape
    cos, sin = _rope_for(spec, positions)

    # Mask: chunk queries attend to valid prior cache slots plus the
    # causally-visible valid part of the chunk itself.
    S = cache_valid.shape[1]
    base = jnp.repeat(cache_valid[:, None, :], K, axis=1)          # [B, K, S]
    causal = jnp.tril(jnp.ones((K, K), bool))
    chunk_mask = causal[None] & chunk_valid[:, None, :] & chunk_valid[:, :, None]
    attn_mask = jax.lax.dynamic_update_slice(base, chunk_mask, (0, 0, write_pos))

    x = params["embed"][tokens]
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, write_pos, cache,
        _live_mask(attn_mask, cache, impl, ring), impl,
        chunk=True, ring=ring,
    )
    # Per-row last valid chunk position -> one LM-head application.
    last = jnp.sum(chunk_valid.astype(jnp.int32), axis=1) - 1      # [B]
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)   # [B, 1, D]
    logits = _logits(params, spec, h_last)[:, 0, :]
    return logits, new_cache


@jax.named_scope(scopes.HEAD)
def decode_chunk_spec(
    params: TransformerParams,
    spec: ModelSpec,
    tokens: jax.Array,         # [B, K1] chunk: sampled token + draft
    chunk_valid: jax.Array,    # [B, K1] bool; position 0 always valid
    row_write_pos: jax.Array,  # [B] int32: PER-ROW cache slot of chunk col 0
    positions: jax.Array,      # [B, K1] RoPE positions (per-row real counts)
    cache: Dict,
    cache_valid: jax.Array,    # [B, S] attendable cache slots BEFORE chunk
    impl: str = "xla",
    ring=None,                 # static (Mesh, axis_name): sp-sharded-cache
                               # chunk decode (sp_chunk_decode_attention)
) -> Tuple[jax.Array, Dict]:
    """One speculative-decoding verify step: process a [B, K1] chunk
    (the sampled token at position 0 plus up to K1-1 drafted tokens)
    against the cache, with PER-ROW write positions (each row's cache
    stays fully compacted at its own accepted-token count) and logits
    returned at EVERY chunk position — position j's logits are the
    model's distribution for position j+1, which is what the acceptance
    test compares each draft token against.

    Differs from :func:`decode_chunk` in exactly two ways: the KV write
    is a per-row scatter (``_write_cache`` [B]-pos form) and the LM head
    applies to all K1 positions instead of the last valid one.  The
    attention itself is mask-driven and shared.
    """
    B, K1 = tokens.shape
    cos, sin = _rope_for(spec, positions)

    # Mask: chunk queries attend valid prior cache slots plus the
    # causally-visible valid chunk prefix, scattered at per-row columns.
    S = cache_valid.shape[1]
    base = jnp.repeat(cache_valid[:, None, :], K1, axis=1)         # [B, K1, S]
    causal = jnp.tril(jnp.ones((K1, K1), bool))
    chunk_mask = causal[None] & chunk_valid[:, None, :] & chunk_valid[:, :, None]
    bidx = jnp.arange(B)[:, None, None]
    qidx = jnp.arange(K1)[None, :, None]
    sidx = row_write_pos[:, None, None] + jnp.arange(K1)[None, None, :]
    attn_mask = base.at[bidx, qidx, sidx].set(chunk_mask)

    x = params["embed"][tokens]
    x, new_cache = _run_layers(
        params, spec, x, cos, sin, row_write_pos, cache,
        _live_mask(attn_mask, cache, impl, ring), impl,
        chunk=True, ring=ring,
    )
    logits = _logits(params, spec, x)                              # [B, K1, V]
    return logits, new_cache


def _block_chunk(
    layer: Dict,
    spec: ModelSpec,
    x: jax.Array,              # [B, K, D]
    cos, sin,
    write_pos: jax.Array,
    cache_entry: Dict,
    attn_mask: jax.Array,      # [B, K, S]
    impl: str,
    ring=None,                 # static (Mesh, axis_name): sp-sharded-cache
                               # chunk decode (sp_chunk_decode_attention)
) -> Tuple[jax.Array, Dict]:
    """Chunk decode block: write the fresh K positions into the cache,
    then attend over the WHOLE cache (prior context + the chunk itself,
    all selected by ``attn_mask``)."""
    x, new_entry = _chunk_attention(layer, spec, x, cos, sin, write_pos,
                                    cache_entry, attn_mask, impl, ring)
    with jax.named_scope(scopes.FFN):
        x = x + _swiglu(layer, rms_norm(x, layer["mlp_norm"], spec.rms_eps))
    return x, new_entry


@jax.named_scope(scopes.ATTN)
def _chunk_attention(layer, spec, x, cos, sin, write_pos, cache_entry,
                     attn_mask, impl, ring):
    """:func:`_block_chunk`'s attention half (arguments as there):
    the new residual and the updated entry."""
    B, K, D = x.shape
    h = rms_norm(x, layer["attn_norm"], spec.rms_eps)
    q, k, v = dense(h, layer["wq"]), dense(h, layer["wk"]), dense(h, layer["wv"])
    if "bq" in layer:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    q = q.reshape(B, K, spec.num_heads, spec.head_dim)
    k = k.reshape(B, K, spec.num_kv_heads, spec.head_dim)
    v = v.reshape(B, K, spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = rms_norm(q, layer["q_norm"], spec.rms_eps)
        k = rms_norm(k, layer["k_norm"], spec.rms_eps)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_entry = _write_cache(cache_entry, k, v, write_pos)

    # Attend over the full cache including the just-written chunk.
    scale = 1.0 / math.sqrt(spec.head_dim)
    leaves = _leaves(new_entry)
    quantized = "k_scale" in leaves
    if "tbl" in leaves:
        # Paged cache (chunk form — the fast-forward / speculative-
        # verify decode windows; paged chunked PREFILL attends via
        # ``_block``'s cached-prefix path instead): ``impl`` carries
        # the engine-resolved paged marker — the fused kernel, or
        # "xla" = gather to the dense layout and attend.  Either way
        # the PAGED entry returns for the carry; see
        # ops/paged_attention.py.
        from bcg_tpu.ops.paged_attention import paged_chunk_attention

        attn_out = paged_chunk_attention(
            q, new_entry, attn_mask, scale, impl=impl
        )
    elif ring is not None:
        # Sequence-parallel chunk decode: cache stays sharded over sp,
        # partials merge via pmax/psum (same loud-on-indivisible policy
        # as the single-token path — the engine sp-aligns its caches).
        # Takes precedence over the single-device Pallas kernel: with
        # sp>1 the replicated full-cache kernel would defeat the
        # sharding.  An int8 cache dequantizes its local slice only.
        from bcg_tpu.ops.ring_attention import sp_chunk_decode_attention

        mesh, axis_name = ring
        ck, cv, scales = _sp_args(new_entry)
        attn_out = sp_chunk_decode_attention(
            q, ck, cv, attn_mask, mesh,
            axis_name=axis_name, scale=scale, **scales,
        )
    elif quantized and is_pallas(impl):
        # int8 cache: stream once, dequantize in VMEM (K*group query rows
        # per program — the prefill flash kernel would pad K chunk rows
        # to a 128-row block).  The engine resolves a Pallas chunk impl
        # only for a dense int8 cache (_resolved_loop_impl).
        from bcg_tpu.ops.decode_attention import chunk_decode_attention

        assert not kv_is_int4(leaves), "no dense Pallas decode for int4 KV"
        ck, cv, kw = _int8_kernel_args(new_entry)
        attn_out = chunk_decode_attention(
            q, ck, cv, attn_mask, scale, mesh=impl_mesh(impl), **kw)
    else:
        ck, cv = _layer_kv(new_entry, q.dtype)
        attn_out = attention(
            q, ck, cv, attn_mask, scale, "xla" if quantized else impl
        )
    return x + dense(attn_out.reshape(B, K, spec.q_size), layer["wo"]), new_entry


def param_count(params: TransformerParams) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
