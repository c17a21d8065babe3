#!/usr/bin/env python
"""Abstract boot smoke: eval_shape-boot EVERY model preset — 14B/32B
included — through the born-sharded init plan and the HBM accounting,
failing on any sharding/budget inconsistency WITHOUT materializing a
single weight.

Tier-1-safe (CPU, seconds): the round-5 14B hardware failure was a boot
problem that no CPU test could see because every boot-path check
materialized weights at test scale only.  This smoke runs the exact
abstract machinery the real boot uses — ``transformer.param_plan`` +
``param_sharding`` + ``loader.boot_peak_report`` +
``sharding.kv_cache_bytes_per_device`` — at FLAGSHIP shapes, so a spec
or layout change that would brick a 14B boot fails here first.

Checks, per (preset, mesh, quantization) combination:

1.  every plan leaf (and quantized sub-leaf) has a placeable sharding —
    ``shard_shape`` raises on a sharded dim that doesn't divide its
    mesh axis, which is exactly what the real per-leaf jit would hit;
2.  the analytic boot peak obeys the born-sharded contract:
    peak-per-device <= final tree + one leaf-group (the larger of the
    biggest stacking group and the biggest single-leaf init transient);
3.  under a multi-device mesh, large 2-D dense leaves actually shard
    (no silent full-precision replica of embed/wq/w_gate at init);
4.  the KV capacity accounting is self-consistent: summing
    ``kv_cache_bytes_per_device`` over the mesh equals the global cache
    bytes times the replication factor of the axes that did NOT engage
    (divisibility guards), for engaged, dp-bypass, and
    guard-failing shapes.

Run standalone (``python scripts/boot_smoke.py``) or through
``tests/test_boot_smoke.py``.
"""

from __future__ import annotations

import os
import sys


def _ensure_cpu_mesh() -> None:
    """Ask for an 8-virtual-device CPU backend BEFORE jax initializes
    (same environment settings as tests/conftest.py)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def check_preset(name: str, mesh, quantization) -> list:
    """All boot-path inconsistencies for one (preset, mesh, quant)
    combination — empty list means the abstract boot is sound."""
    import jax
    import jax.numpy as jnp

    from bcg_tpu.models.configs import MODEL_SPECS
    from bcg_tpu.models.loader import boot_peak_report
    from bcg_tpu.models.quantize import quantize_leaf_transform
    from bcg_tpu.models.transformer import init_kv_cache, param_plan
    from bcg_tpu.parallel.sharding import (
        kv_cache_bytes_per_device,
        kv_cache_tree_sharding,
        param_sharding,
    )

    spec = MODEL_SPECS[name]
    problems = []
    transform = (
        quantize_leaf_transform(spec, quantization) if quantization else None
    )

    # --- 1. every leaf (incl. quantized sub-leaves) places cleanly ----
    for logical, kind, shape in param_plan(spec):
        src = jax.ShapeDtypeStruct(
            shape, jnp.float32 if kind == "dense" else jnp.bfloat16
        )

        def _make(w, _logical=logical, _kind=kind):
            w = w.astype(jnp.bfloat16)
            if transform is not None and _kind == "dense":
                return transform(_logical, w)
            return w

        out = jax.eval_shape(_make, src)
        subleaves = (
            {f"{logical}.{sub}": s for sub, s in out.items()}
            if isinstance(out, dict)
            else {logical: out}
        )
        for sub_logical, struct in subleaves.items():
            if mesh is None:
                continue
            sh = param_sharding(sub_logical, spec, mesh)
            try:
                sh.shard_shape(struct.shape)
            except Exception as e:
                problems.append(
                    f"{name}: {sub_logical} {struct.shape} does not place "
                    f"under {sh.spec}: {e}"
                )

    if problems:
        # Unplaceable leaves would make the accounting below raise the
        # same divisibility error less legibly — report them as is.
        return problems

    # --- 2. + 3. analytic boot peak obeys the born-sharded contract ---
    report = boot_peak_report(spec, mesh=mesh, quantization=quantization)
    headroom = max(
        report["max_leaf_group_bytes"], report["max_init_transient_bytes"]
    )
    if report["peak_bytes_per_device"] > (
        report["final_bytes_per_device"] + headroom
    ):
        problems.append(
            f"{name}: boot peak {report['peak_bytes_per_device']} exceeds "
            f"final tree + one leaf-group "
            f"({report['final_bytes_per_device']} + {headroom})"
        )
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        # Weights shard over tp only (dp/sp replicate them by design),
        # so the no-unsharded-full-precision-leaf contract is checkable
        # exactly when tp engages: the biggest init transient must be a
        # SHARD, not the whole fp32 embed.
        full_embed_fp32 = spec.vocab_size * spec.hidden_size * 4
        if report["max_init_transient_bytes"] >= full_embed_fp32:
            problems.append(
                f"{name}: init transient "
                f"{report['max_init_transient_bytes']} is a full "
                f"unsharded fp32 leaf ({report['max_init_transient_leaf']})"
                " — born-sharded contract broken"
            )

    # --- 4. KV capacity accounting self-consistency --------------------
    if mesh is not None:
        for B, S, quant_kv in ((8, 1024, False), (3, 1024, False),
                               (8, 1021, True)):
            shapes = jax.eval_shape(
                lambda: init_kv_cache(spec, B, S, quantized=quant_kv)
            )
            per_dev = kv_cache_bytes_per_device(
                mesh, shapes, quantized=quant_kv
            )
            shardings = kv_cache_tree_sharding(
                mesh, shapes, quantized=quant_kv
            )
            expected = 0
            for leaf, sh in zip(
                jax.tree.leaves(shapes),
                jax.tree.leaves(
                    shardings, is_leaf=lambda s: hasattr(s, "shard_shape")
                ),
            ):
                engaged = 1
                for ax in sh.spec:
                    if ax is not None:
                        engaged *= mesh.shape[ax]
                expected += (
                    leaf.size * leaf.dtype.itemsize
                ) // engaged
            if per_dev != expected:
                problems.append(
                    f"{name}: kv_cache_bytes_per_device(B={B}, S={S}, "
                    f"int8={quant_kv}) = {per_dev}, engaged-axes "
                    f"expectation {expected}"
                )
    return problems


def run_all(verbose: bool = True) -> list:
    """Smoke every preset under representative mesh/quantization
    combinations; returns the accumulated problem list."""
    import jax

    from bcg_tpu.models.configs import (
        LARGE_MODEL_PARAMS, MODEL_SPECS, XL_MODEL_PARAMS,
    )
    from bcg_tpu.parallel.mesh import build_mesh

    n_dev = len(jax.devices())
    meshes = [("single", None)]
    if n_dev >= 8:
        meshes += [
            ("tp8", build_mesh(dp=1, tp=8, sp=1)),
            ("dp8", build_mesh(dp=8, tp=1, sp=1)),
            ("dp2tp2sp2", build_mesh(dp=2, tp=2, sp=2)),
        ]
    problems = []
    for name, spec in sorted(MODEL_SPECS.items()):
        # Quantization per the bench's size-class gates, plus bf16 so
        # both materialization formats stay abstract-bootable.
        if spec.param_count >= XL_MODEL_PARAMS:
            quants = ["int4", "int8"]
        elif spec.param_count >= LARGE_MODEL_PARAMS:
            quants = ["int8", None]
        else:
            quants = [None, "int8"]
        for mesh_name, mesh in meshes:
            if spec.hybrid and mesh is not None:
                # a spec with layer_types has no sharded form (recurrent
                # state): the engine refuses a mesh at boot
                continue
            for quant in quants:
                got = check_preset(name, mesh, quant)
                problems += got
                if verbose:
                    status = "FAIL" if got else "ok"
                    print(
                        f"boot_smoke: {name:45s} mesh={mesh_name:10s} "
                        f"quant={str(quant):5s} {status}"
                    )
    return problems


def main(argv=None) -> int:
    _ensure_cpu_mesh()
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    problems = run_all()
    if problems:
        print(f"\nboot_smoke: {len(problems)} problem(s):")
        for p in problems:
            print(f"  - {p}")
        return 1
    print("boot_smoke: all presets abstract-boot cleanly")
    return 0


if __name__ == "__main__":
    sys.exit(main())
