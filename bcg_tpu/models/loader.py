"""HF checkpoint loading: safetensors -> transformer param pytree.

Replaces the weight-loading half of the reference's engine boot
(``vllm_agent.py:100-157``).  Weights stream tensor-by-tensor from
safetensors shards into bf16 device arrays — optionally placed under a
``NamedSharding`` per leaf while loading, so a TP-sharded 32B model never
materializes unsharded on one host.

This build environment has no network egress, so checkpoints must exist
on local disk (HF cache layout or a flat directory of ``*.safetensors``).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bcg_tpu.models.configs import ModelSpec
from bcg_tpu.obs import tracer as obs_tracer
from bcg_tpu.runtime.envflags import get_str

# HF parameter name templates for the Qwen/Llama/Mistral family.
_LAYER_MAP = {
    "attn_norm": "model.layers.{i}.input_layernorm.weight",
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wk": "model.layers.{i}.self_attn.k_proj.weight",
    "wv": "model.layers.{i}.self_attn.v_proj.weight",
    "bq": "model.layers.{i}.self_attn.q_proj.bias",
    "bk": "model.layers.{i}.self_attn.k_proj.bias",
    "bv": "model.layers.{i}.self_attn.v_proj.bias",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "q_norm": "model.layers.{i}.self_attn.q_norm.weight",
    "k_norm": "model.layers.{i}.self_attn.k_norm.weight",
    "mlp_norm": "model.layers.{i}.post_attention_layernorm.weight",
    "w_gate": "model.layers.{i}.mlp.gate_proj.weight",
    "w_up": "model.layers.{i}.mlp.up_proj.weight",
    "w_down": "model.layers.{i}.mlp.down_proj.weight",
}
_TOP_MAP = {
    "embed": "model.embed_tokens.weight",
    "final_norm": "model.norm.weight",
    "lm_head": "lm_head.weight",
}
# HF stores projections as [out, in]; our layout is [in, out].
_TRANSPOSED = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"}


def find_checkpoint_dir(model_name: str) -> Optional[str]:
    """Locate a local checkpoint: explicit dir, HF cache, or env override."""
    candidates = []
    env = get_str("BCG_TPU_CHECKPOINT_DIR")
    if env:
        candidates.append(os.path.join(env, model_name.replace("/", "--")))
        candidates.append(env)
    candidates.append(model_name)  # model_name may itself be a path
    # Repo-local checkpoints (e.g. the hermetic bcg-hf/* artifact sets
    # built by models/hf_fixture.py).
    candidates.append(os.path.join("checkpoints", model_name.replace("/", "--")))
    hf_home = os.environ.get("HF_HOME", os.path.expanduser("~/.cache/huggingface"))
    snap_root = os.path.join(
        hf_home, "hub", f"models--{model_name.replace('/', '--')}", "snapshots"
    )
    if os.path.isdir(snap_root):
        for snap in sorted(os.listdir(snap_root)):
            candidates.append(os.path.join(snap_root, snap))
    for c in candidates:
        if c and os.path.isdir(c) and any(
            f.endswith(".safetensors") for f in os.listdir(c)
        ):
            return c
    return None


def load_checkpoint_params(
    spec: ModelSpec,
    model_name: str,
    mesh=None,
    dtype=jnp.bfloat16,
    leaf_transform=None,
    ckpt_dir: Optional[str] = None,
) -> Dict:
    """Load and (optionally) shard all parameters for ``spec``.

    ``leaf_transform(logical_name, tensor) -> leaf`` is applied to each
    tensor right after device placement — e.g. streamed int8 quantization
    (models/quantize.py:quantize_leaf_transform), which keeps peak device
    memory at the final model size instead of bf16 + quantized copies.
    ``ckpt_dir``: a pre-resolved checkpoint directory (skips the
    candidate walk a caller already did via :func:`find_checkpoint_dir`).
    """
    if spec.hybrid:
        raise ValueError(
            f"{spec.name}: no checkpoint loader is built for a spec with "
            "layer_types (the name map below is the dense family's); serve "
            "its shapes with random weights through a bcg-tpu/* preset")
    if ckpt_dir is None:
        ckpt_dir = find_checkpoint_dir(model_name)
    if ckpt_dir is None:
        raise FileNotFoundError(
            f"No local safetensors checkpoint found for {model_name!r} "
            "(zero-egress environment: download is not possible; set "
            "BCG_TPU_CHECKPOINT_DIR or use a bcg-tpu/* random-weight preset)"
        )
    from safetensors import safe_open

    # Index every tensor name to its shard file.
    shard_files = sorted(
        os.path.join(ckpt_dir, f)
        for f in os.listdir(ckpt_dir)
        if f.endswith(".safetensors")
    )
    name_to_file: Dict[str, str] = {}
    for path in shard_files:
        with safe_open(path, framework="numpy") as f:
            for name in f.keys():
                name_to_file[name] = path

    sharding_for = None
    if mesh is not None:
        from bcg_tpu.parallel.sharding import param_sharding

        sharding_for = lambda logical: param_sharding(logical, spec, mesh)  # noqa: E731

    open_files: Dict[str, object] = {}

    def fetch(hf_name: str, logical: str):
        path = name_to_file[hf_name]
        if path not in open_files:
            open_files[path] = safe_open(path, framework="numpy")
        arr = open_files[path].get_tensor(hf_name)
        return _convert(arr, logical)

    def _convert(arr, logical: str):
        # bf16 bit-pattern view, transpose, and dtype cast all happen on
        # the HOST ndarray, so the FIRST device placement is already the
        # sharded one — `jnp.asarray` first would stage the full tensor
        # unsharded on the default device, exactly the transient the
        # per-leaf sharded load exists to avoid.
        if arr.dtype == np.uint16:  # raw bf16 storage
            arr = arr.view(ml_dtypes.bfloat16)
        if logical.split(".")[-1] in _TRANSPOSED:
            arr = arr.T
        arr = arr.astype(np.dtype(dtype), copy=False)
        if sharding_for is not None:
            tensor = jax.device_put(arr, sharding_for(logical))
        else:
            tensor = jnp.asarray(arr)
        if leaf_transform is not None:
            tensor = leaf_transform(logical, tensor)
        return tensor

    params: Dict = {"layers": []}
    try:
        for logical, hf_name in _TOP_MAP.items():
            if logical == "lm_head" and spec.tie_embeddings:
                continue
            if hf_name not in name_to_file:
                if logical == "lm_head":
                    continue  # tied embeddings checkpoint
                raise KeyError(f"{hf_name} missing from checkpoint {ckpt_dir}")
            params[logical] = fetch(hf_name, logical)
        for i in range(spec.num_layers):
            layer = {}
            for logical, template in _LAYER_MAP.items():
                if logical in ("q_norm", "k_norm") and not spec.qk_norm:
                    continue
                if logical in ("bq", "bk", "bv") and not spec.attn_bias:
                    continue
                hf_name = template.format(i=i)
                layer[logical] = fetch(hf_name, f"layers.{i}.{logical}")
            params["layers"].append(layer)
    finally:
        # Release shard handles/mmaps deterministically.  safe_open
        # handles expose the context-manager protocol; some versions also
        # have .close() — prefer it, else call __exit__ with its three
        # required args.
        for handle in open_files.values():
            try:
                close = getattr(handle, "close", None)
                if close is not None:
                    close()
                else:
                    exit_ = getattr(handle, "__exit__", None)
                    if exit_ is not None:
                        exit_(None, None, None)
            except Exception:
                pass
        open_files.clear()
    return params


# -------------------------------------------------- born-sharded random init

@obs_tracer.spanned_once("boot.init_params")
def init_random_params_sharded(
    spec: ModelSpec,
    key: jax.Array,
    mesh=None,
    dtype=jnp.bfloat16,
    leaf_transform=None,
) -> Dict:
    """Born-sharded, born-quantized random init — the flagship-scale
    boot path (hermetic ``bcg-tpu/*`` presets and benches).

    ``transformer.init_params`` creates every leaf eagerly on the
    default device: an fp32 intermediate per tensor, unsharded — a 14B
    bf16 tree peaks far past one chip's HBM during init even when the
    mesh has room (the round-5 ``bench_14b`` RESOURCE_EXHAUSTED, twice).
    This materializes the SAME ``param_plan`` (same key consumption,
    bit-identical values) leaf by leaf through a jitted initializer with
    ``out_shardings=param_sharding(...)`` and the quantize
    ``leaf_transform`` INSIDE the jit, so:

    * no full-precision leaf ever exists unsharded — the fp32 source and
      its bf16/int8 product are computed per device shard;
    * peak device memory is the transformed tree so far plus ONE leaf's
      shard-sized transient (see ``boot_peak_report`` for the analytic
      accounting).

    ``leaf_transform`` must depend only on the LAST component of the
    logical name (true of ``quantize_leaf_transform``): per-leaf jits
    are reused across layers of the same shape, so a transform keyed on
    the layer index would silently apply layer 0's behaviour everywhere.

    With ``mesh=None`` the per-leaf jit still fuses the fp32
    intermediate away (single-device peak = tree + one leaf), matching
    the streamed-checkpoint discipline this replaces.

    Values are MESH-SHAPE-INVARIANT: the partitionable threefry RNG is
    enabled for the scope of this call, so the same seed yields the same
    weights at tp=1 and tp=8 (the legacy counter scheme re-derives
    per-shard streams under ``out_shardings`` — a tp=2 and a tp=4 bench
    would otherwise serve different random models).  They intentionally
    differ bit-wise from ``transformer.init_params``'s legacy-RNG
    output; no golden-value contract exists for random weights.
    """
    from bcg_tpu.models.transformer import (
        MATRIX_KINDS, RANDOM_KINDS, assemble_param_tree, init_leaf, param_plan, plan_keys,
    )

    sharding_for = None
    if mesh is not None:
        from bcg_tpu.parallel.sharding import param_sharding

        sharding_for = lambda logical: param_sharding(logical, spec, mesh)  # noqa: E731

    plan = param_plan(spec)
    keys = plan_keys(spec, key, plan)
    spare_key = keys[-1]  # feeds the constant leaves' jits, which ignore it
    ki = 0
    fns: Dict = {}
    items = []
    prev_partitionable = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        for logical, kind, shape in plan:
            leaf = logical.split(".")[-1]
            if kind in RANDOM_KINDS:
                k = keys[ki]
                ki += 1
            else:
                k = spare_key

            cache_key = (leaf, kind, shape)
            fn = fns.get(cache_key)
            if fn is None:

                def _init(k, _kind=kind, _shape=shape, _logical=logical):
                    w = init_leaf(_kind, _shape, k, dtype)
                    # Dense leaves only, like init_params and
                    # boot_peak_report — the three param_plan
                    # consumers must agree on what transforms.
                    if _kind in MATRIX_KINDS and leaf_transform is not None:
                        w = leaf_transform(_logical, w)
                    return w

                out_shardings = None
                if sharding_for is not None:
                    out_struct = jax.eval_shape(_init, k)
                    if isinstance(out_struct, dict):  # quantized {"q","scale"}
                        out_shardings = {
                            sub: sharding_for(f"{logical}.{sub}")
                            for sub in out_struct
                        }
                    else:
                        out_shardings = sharding_for(logical)
                    fn = jax.jit(_init, out_shardings=out_shardings)
                else:
                    fn = jax.jit(_init)
                fns[cache_key] = fn
            items.append((logical, fn(k)))
    finally:
        jax.config.update("jax_threefry_partitionable", prev_partitionable)
    return assemble_param_tree(items)


def _shard_bytes(struct, sharding) -> int:
    """Per-device bytes of a ShapeDtypeStruct under a NamedSharding
    (full bytes when ``sharding`` is None) — the shared computation in
    ``parallel/sharding.shard_bytes``, so this analytic report and the
    engine's HBM budget cannot drift apart."""
    from bcg_tpu.parallel.sharding import shard_bytes

    return shard_bytes(struct.shape, struct.dtype, sharding)


def boot_peak_report(
    spec: ModelSpec,
    mesh=None,
    quantization: Optional[str] = None,
    dtype=jnp.bfloat16,
    scan_layers: bool = True,
) -> Dict:
    """Analytic per-device boot-memory accounting for the born-sharded
    init path — pure ``eval_shape`` + ``param_sharding``, NO weights
    materialized (safe for 14B/32B specs on a laptop CPU).

    Models the engine boot phase by phase:

    * per-leaf init: the already-materialized (transformed) tree so far,
      plus the current leaf's fp32 source and its transformed output —
      all at SHARD size, because ``init_random_params_sharded``'s
      ``out_shardings`` partition the whole per-leaf computation;
    * consume-stacking (``scan_layers``): the full transformed tree plus
      one leaf-group's stacked copy (``stack_layer_params(consume=True)``
      frees each group's per-layer sources as its stack appears).

    Returns a dict of byte counts; the headline invariant — boot peak
    per device <= final tree + one leaf-group (where "one leaf-group"
    is the larger of the biggest stacking group and the biggest single-
    leaf init transient) — holds by construction and is asserted by
    ``tests/test_born_sharded.py`` and ``scripts/boot_smoke.py`` against
    the components reported here.
    """
    from bcg_tpu.models.transformer import MATRIX_KINDS, RANDOM_KINDS, param_plan

    transform = None
    if quantization is not None:
        from bcg_tpu.models.quantize import quantize_leaf_transform

        transform = quantize_leaf_transform(spec, quantization)

    sharding_for = None
    if mesh is not None:
        from bcg_tpu.parallel.sharding import param_sharding

        sharding_for = lambda logical: param_sharding(logical, spec, mesh)  # noqa: E731

    done = 0
    init_peak = 0
    max_transient = 0
    max_transient_leaf = None
    group_bytes: Dict[str, int] = {}
    for logical, kind, shape in param_plan(spec):
        # every random leaf is drawn in float32, then cast
        src_dtype = jnp.float32 if kind in RANDOM_KINDS else dtype

        def _make(w, _logical=logical, _kind=kind):
            w = w.astype(dtype)
            if transform is not None and _kind in MATRIX_KINDS:
                return transform(_logical, w)
            return w

        src = jax.ShapeDtypeStruct(shape, src_dtype)
        out_struct = jax.eval_shape(_make, src)
        if isinstance(out_struct, dict):
            out_b = sum(
                _shard_bytes(
                    sub,
                    sharding_for(f"{logical}.{name}") if sharding_for else None,
                )
                for name, sub in out_struct.items()
            )
        else:
            out_b = _shard_bytes(
                out_struct, sharding_for(logical) if sharding_for else None
            )
        # The fp32 source transient is sharded like the parent weight
        # (out_shardings propagate back through the elementwise chain).
        transient = (
            _shard_bytes(src, sharding_for(logical) if sharding_for else None)
            if kind in RANDOM_KINDS
            else 0
        )
        init_peak = max(init_peak, done + transient + out_b)
        if transient + out_b > max_transient:
            max_transient = transient + out_b
            max_transient_leaf = logical
        done += out_b
        parts = logical.split(".")
        if parts[0] == "layers":
            group_bytes[parts[2]] = group_bytes.get(parts[2], 0) + out_b

    max_group = max(group_bytes.values()) if group_bytes else 0
    stack_peak = done + max_group if scan_layers else done
    return {
        "final_bytes_per_device": done,
        "init_peak_bytes_per_device": init_peak,
        "stack_peak_bytes_per_device": stack_peak,
        "peak_bytes_per_device": max(init_peak, stack_peak),
        "max_init_transient_bytes": max_transient,
        "max_init_transient_leaf": max_transient_leaf,
        "max_leaf_group_bytes": max_group,
        "devices": 1 if mesh is None else mesh.size,
        "quantization": quantization,
    }
