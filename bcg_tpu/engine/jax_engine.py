"""JAX/XLA inference engine — the TPU replacement for the reference's
CUDA vLLM singleton (``vllm_agent.py:58-551``).

Serving design (lockstep game, no continuous batching needed —
SURVEY.md §7 hard part 2):

* One padded batch per game phase; prompts are LEFT-padded into a
  length bucket (multiple of ``_LEN_BUCKET``) so only a handful of
  prefill shapes ever compile.
* Prefill runs once per call; decode is a single ``lax.while_loop``
  entirely on device — no host round-trip per token.  Guided decoding
  rides along as per-sequence DFA states + two gathers per step
  (:mod:`bcg_tpu.guided`), so heterogeneous schemas (honest + Byzantine
  in one batch) stay batched.
* Weights/KV bf16; logits f32; EOS is forced exactly when a sequence's
  DFA reaches an accepting state with no tokens allowed.
"""

from __future__ import annotations

import json
import os
import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bcg_tpu.engine.chat_template import (
    format_chat_parts,
    format_chat_parts3,
    format_chat_prompt,
    prefix_split_safe,
)
from bcg_tpu.engine.interface import InferenceEngine, per_row_settings as _per_row
from bcg_tpu.engine.speculative import (
    build_spec_loop,
    make_masked_sampler as _make_masked_sampler_impl,
    spec_decode_slots as _spec_decode_slots,
)
from bcg_tpu.engine.tokenizer import Tokenizer, tokenizer_for_model
from bcg_tpu.guided.processor import GuidedBatch, compile_schema
from bcg_tpu.ops import PallasTP, is_pallas
from bcg_tpu.ops.guided_sampler import (
    PALLAS as _GS_PALLAS,
    PALLAS_INTERPRET as _GS_PALLAS_INTERPRET,
)
from bcg_tpu.config import env_flag
from bcg_tpu.obs import (
    compile as obs_compile,
    counters as obs_counters,
    hlo as obs_hlo,
    hostsync as obs_hostsync,
    ledger as obs_ledger,
    scopes as obs_scopes,
    tracer as obs_tracer,
)
from bcg_tpu.models.configs import (
    FULL_ATTENTION,
    LARGE_MODEL_PARAMS,
    RECURRENT_TYPES,
    ModelSpec,
    spec_for_model,
)
from bcg_tpu.runtime import resilience
from bcg_tpu.models.transformer import (
    cache_bytes,
    decode_chunk,
    decode_step,
    init_kv_cache,
    layers_stacked,
    prefill,
    prefill_chunk_at,
    prefill_with_prefix,
    probe_weight,
    stack_layer_params,
)

# Coarse prompt-length ladder.  Every distinct (B, L) pair compiles its
# own prefill + decode loop, seconds to minutes at 8B widths, so shapes
# must stabilize after the first round even
# though prompts keep growing with game history.  A fine-grained bucket
# (the first design used 128) recompiled nearly every round.
_LEN_BUCKETS = (512, 1024, 2048, 4096, 6144, 8192)
# With the system prompt served from the prefix cache, the remaining
# per-call suffix (round prompt) is much shorter — give it a finer ladder.
# Decode streams every ALLOCATED slot each step, so pad in the suffix
# bucket is decode wall-clock: the measured vote suffixes (~2000-2900
# byte-tokenizer, ~1000-1500 trained-BPE) land just past a rung and pay
# up to 40% pad traffic on the coarse ladder.  The FINE ladder adds the
# 1536/3072 rungs — opt-in per engine (EngineConfig.fine_suffix_buckets,
# or env BCG_TPU_FINE_SUFFIX=1 as the bench/sweep override) until the
# extra compile signatures are A/B-measured on hardware against the
# pad-traffic saving.
_SUFFIX_BUCKETS = (256, 512, 1024, 2048, 4096, 8192)
_SUFFIX_BUCKETS_FINE = (256, 512, 1024, 1536, 2048, 3072, 4096, 8192)
# Prefix entries are per-run static (one compile each), so an even finer
# ladder is cheap — and a tight prefix bucket matters doubly, because pad
# slots in [0, P) are streamed by EVERY subsequent decode step (the BCG
# system prompts measure ~550-770 and ~1580-1620 tokens, hence the 768
# and 1792 rungs).
_PREFIX_BUCKETS = (128, 256, 512, 768, 1024, 1536, 1792, 2048, 4096, 6144, 8192)


class BudgetError(ValueError):
    """A request whose token budget cannot fit the context window.

    The ONLY generation-time error class the engine converts into
    per-row ``{"error": ...}`` results; anything else (XLA/Pallas
    compile failures, runtime errors) propagates — see
    batch_generate_json.
    """


# Where compiled programs persist when the environment names no place:
# one fixed path inside the checkout (.gitignore lists it).  The path is
# part of JAX's cache key, so it must never move — not under ~, a temp
# name, a pid or the time.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compilation_cache_dir() -> str:
    """The persistent compile cache's directory under the one rule:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    the fixed in-checkout path."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def _enable_compilation_cache() -> None:
    """Persist compiled XLA executables across processes: an 8B boot
    compiles for minutes, and a fresh process repays all of it.

    With ``JAX_COMPILATION_CACHE_DIR`` set the cache is JAX's own
    business and no directory is set in code.  Unset, the TPU backend
    gets the fixed in-checkout directory; the CPU backend gets none (CPU
    AOT artifacts are keyed to the exact host feature set, and the tiny
    test models compile fast anyway).  A directory that cannot be
    created or written is an error, not a silently cold boot."""
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not from_env and jax.default_backend() != "tpu":
        return
    cache_dir = compilation_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    if not os.access(cache_dir, os.W_OK):
        raise PermissionError(f"compile cache {cache_dir!r} is not writable")
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)


def _ff_decode_slots(max_new: int) -> int:
    """Cache tail allocation for the fast-forward loop's compacted writes.

    The write position advances by 1 + max(chain) per iteration, with the
    in-loop capacity guard falling back to single-token advances whenever
    the worst-case remainder (1 slot per remaining iteration plus a final
    K-window) would no longer fit — so 1.5x the token budget plus two
    chunk windows always suffices, vs. the K * max_new a fixed stride
    needs.  Fewer allocated slots = fewer slots streamed by every decode
    step of the KV-bandwidth-bound loop.
    """
    from bcg_tpu.guided.processor import FF_CHUNK

    return (3 * max_new) // 2 + 2 * FF_CHUNK


def _named(name: str, fn, **bound):
    """``partial(fn, **bound)`` under a name: jax names a jitted program
    after its function's ``__name__``, and a bare partial has none (the
    profiler then shows ``jit__unknown`` for every such program)."""
    named = partial(fn, **bound)
    named.__name__ = name
    return named


def _pad_batch(real_B: int) -> int:
    """Batch-size bucketing: small (retry) batches round up to a power of
    two to reuse compiled loops; full-size game batches stay exact."""
    return real_B if real_B >= 8 else 1 << (real_B - 1).bit_length()


def _aligned_pad_batch(n: int, multiple: int) -> int:
    """Final padded batch size: power-of-two bucketing (_pad_batch) then
    alignment up to the dp ``multiple``."""
    B = _pad_batch(n)
    return B + (-B) % multiple


def _chunk_size(cap: int, multiple: int = 1) -> int:
    """Largest chunk whose PADDED batch (:func:`_aligned_pad_batch`)
    stays within ``cap`` — max_num_seqs / the HBM provisioner bound
    allocated KV rows, so neither power-of-two padding (cap 5 would pad
    to 8) nor dp alignment (cap 12 at dp 8 would pad to 16) may
    re-inflate a chunk past them.  Requires ``multiple <= cap`` (the
    caller drops dp alignment otherwise)."""
    return max(
        s for s in range(1, cap + 1) if _aligned_pad_batch(s, multiple) <= cap
    )


def _pad_rows(*lists, multiple: int = 1):
    """Pad parallel per-sequence lists to the bucketed batch size by
    repeating row 0 (results for padding rows are discarded).  Small
    batches (retry sub-batches, sequential fallbacks) pad to a power of
    two so they share compiled decode loops instead of each paying a
    fresh compile; the main game batch (all agents, a
    stable size every round) runs exact — decode is KV-bandwidth-bound,
    so padding IT would cost real HBM traffic.  ``multiple`` (the
    engine's dp degree) further aligns the padded size so the batch axis
    shards evenly over the mesh's ``dp`` axis: sharding N padding rows
    over dp devices costs LESS per-device traffic than replicating the
    unpadded batch to all of them.  Returns (real_B, B, *padded_lists)."""
    real_B = len(lists[0])
    B = _aligned_pad_batch(real_B, multiple)
    return (real_B, B) + tuple(l + [l[0]] * (B - real_B) for l in lists)


def _kernel_fallback_warn(family: str, knob: str, detail: str,
                          consequence: str) -> None:
    """ONE warning shape for every kernel-family fallback (the int8 GQA
    decode kernel, the fused guided sampler, future arms): names the
    kernel family, the CONFIG KNOB that caused the fallback (an env
    kill-switch, a geometry guard, a backend condition — cause
    attribution is the caller's job: when an operator-set env flag and a
    geometry guard both apply, the stated cause must be the flag the
    operator actually set), and the operational consequence.  Hand-
    rolled per-family warning text drifted — each family named its
    cause differently or not at all."""
    import warnings

    warnings.warn(
        f"{family} disabled — falling back to the XLA path ({knob}: "
        f"{detail}); {consequence}",
        stacklevel=3,
    )


class JaxEngine(InferenceEngine):
    def _refuse_unbuilt_for_hybrid(self, config, mesh) -> None:
        """A spec with ``layer_types`` keeps two kinds of per-row state
        (K/V in its full-attention layers, recurrent state and conv tail
        in its delta-rule, KDA or state-space layers).  What is built
        for that is the plain path: full or chunked prefill into a dense
        cache and the one-token decode loop on one device.  Every option
        that would need a state snapshot, a state rollback, a paged or a
        sharded form of the second kind raises here, by name; nothing
        falls back."""
        from bcg_tpu.runtime.envflags import get_str

        kv = (get_str("BCG_TPU_KV_DTYPE") or str(config.kv_cache_dtype)).strip().lower()
        unbuilt = [why for on, why in (
            (getattr(config, "prefix_caching", True),
             "prefix_caching (a cached prefix's boundary would need a "
             "snapshot of the recurrent state)"),
            (getattr(config, "decode_fast_forward", False),
             "decode_fast_forward (a decode chunk has no recurrent form)"),
            (getattr(config, "spec_decode", False) or env_flag("BCG_TPU_SPEC"),
             "spec_decode (a rejected draft would need the state rolled back)"),
            (getattr(config, "paged_kv", False) or env_flag("BCG_TPU_PAGED_KV"),
             "paged_kv (the block pool holds K/V only)"),
            (kv == "int4", "kv_cache_dtype='int4'"),
            (config.quantization == "int4", "quantization='int4'"),
            (mesh is not None and mesh.size > 1,
             "a multi-device mesh (tensor, sequence or data parallel: the "
             "recurrent state has no sharded form)"),
        ) if on]
        if unbuilt:
            kinds = sorted({k for k in self.spec.layer_types if k in RECURRENT_TYPES})
            raise ValueError(
                f"{self.spec.name} is a hybrid (layer_types) spec with "
                f"recurrent layers ({', '.join(kinds)}); not built "
                "for it: " + "; ".join(unbuilt))

    def __init__(self, config, mesh=None, params=None, spec: Optional[ModelSpec] = None):
        _enable_compilation_cache()
        self.config = config
        # Boot-phase memory/timing breakdown (runtime/metrics.py):
        # created FIRST so this boot owns metrics.LAST_BOOT_PHASES from
        # its first instant — a boot that dies even before its first
        # recorded phase (config validation, tokenizer) must not leave a
        # previous attempt's breakdown to be misattributed.  Each phase
        # records wall time + allocator readings, survives a mid-phase
        # OOM (recorded `failed`), and is a `boot.<phase>` span of the
        # tracer / attached to bench JSON — so the next 14B boot failure
        # names its phase instead of dying as a bare RESOURCE_EXHAUSTED.
        from bcg_tpu.runtime.metrics import BootPhaseRecorder

        self._boot = BootPhaseRecorder()
        self.boot_phases = self._boot.phases
        self._first_call_recorded = False
        self.spec = spec or spec_for_model(config.model_name)
        if self.spec is None:
            raise ValueError(
                f"No architecture spec for model {config.model_name!r}; "
                f"known: {sorted(__import__('bcg_tpu.models.configs', fromlist=['MODEL_SPECS']).MODEL_SPECS)}"
            )
        self.tokenizer: Tokenizer = tokenizer_for_model(config.model_name)
        self.mesh = mesh
        if self.spec.hybrid:
            self._refuse_unbuilt_for_hybrid(config, mesh)
        # Kernel eligibility, decided ONCE here from what the engine can
        # observe — backend, head dim, mesh — and never again at trace
        # time: the ops run the kernel they are handed or raise.  Under a
        # mesh every Pallas call is shard_map'd over tp (ops.PallasTP —
        # Mosaic kernels have no SPMD partitioning rule), which needs
        # whole GQA groups per device: H and Hkv both divisible by tp.
        _tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        # None = the Pallas kernels can run; else the first reason not.
        self._kernel_blocker: Optional[str] = next(
            (why for ok, why in (
                (jax.default_backend() == "tpu",
                 f"backend is {jax.default_backend()!r}, not 'tpu'"),
                (self.spec.head_dim % 128 == 0,
                 f"head_dim {self.spec.head_dim} is not a multiple of 128"),
                (self.spec.num_heads % _tp == 0
                 and self.spec.num_kv_heads % _tp == 0,
                 f"heads {self.spec.num_heads}/{self.spec.num_kv_heads} "
                 f"do not divide tp={_tp}"),
            ) if not ok),
            None,
        )
        # Prefill is the memory-critical path: the stock XLA einsum
        # attention materializes B*H*T*S f32 scores, which OOMs a single
        # v5e chip at game batch sizes — flash (Pallas) is the default
        # wherever the kernel can run.  Decode is T=1, where the einsum
        # path is already a cheap fused GEMV; flash's 128-row query
        # padding would waste MXU work.
        if config.attention_impl == "auto":
            self.attention_impl = "xla" if self._kernel_blocker else "pallas"
        else:
            self.attention_impl = config.attention_impl
        if self.attention_impl == "pallas" and self._kernel_blocker:
            raise ValueError(
                "attention_impl='pallas' cannot run here: "
                f"{self._kernel_blocker} (use 'auto', 'xla' or "
                "'blockwise')"
            )
        # The mesh every Pallas call is shard_map'd over (None on one
        # device, where the bare pallas_call is the whole program).
        self._kernel_mesh = (
            mesh if mesh is not None and mesh.size > 1 else None
        )
        # KV-cache dtype: config field, overridden by BCG_TPU_KV_DTYPE
        # (bench/sweep A/B knob; "bf16" and "bfloat16" are the same
        # spelling, "int8" keeps its historical meaning as an alias of
        # itself in the generalized {bf16,int8,int4} switch).
        from bcg_tpu.runtime.envflags import get_str as _get_str0

        _kv_raw = (
            (_get_str0("BCG_TPU_KV_DTYPE") or "").strip().lower()
            or str(config.kv_cache_dtype).lower()
        )
        _kv_raw = {"bf16": "bfloat16"}.get(_kv_raw, _kv_raw)
        if _kv_raw not in ("bfloat16", "int8", "int4"):
            raise ValueError(
                f"kv_cache_dtype={_kv_raw!r}: expected 'bfloat16'/'bf16', "
                "'int8' or 'int4'"
            )
        if _kv_raw == "int4":
            from bcg_tpu.models.quantize import kv_int4_layout

            kv_int4_layout(self.spec.head_dim)  # even-head-dim boot check
        self.kv_dtype = _kv_raw
        if config.quantization not in (None, "int8", "int4"):
            raise ValueError(
                f"quantization={config.quantization!r}: expected None, "
                "'int8' or 'int4'"
            )
        # The activation/weight compute dtype is bf16 by design on TPU
        # (MXU-native; f32 would halve matmul throughput and double HBM
        # traffic; lower precision goes through `quantization`).  The
        # knob exists for serving-config interface parity — reject
        # rather than silently ignore other values.
        if getattr(config, "dtype", "bfloat16") not in ("bfloat16", "bf16"):
            raise ValueError(
                f"dtype={config.dtype!r}: TPU serving computes in "
                "bfloat16; use quantization='int8'/'int4' for lower-"
                "precision weights"
            )
        # False | "int8" | "int4" — truthy for any quantized layout (the
        # [B, Hkv, S, *] axes and scale leaves are shared), passed
        # verbatim as the ``quantized=`` argument of every cache
        # init/sharding helper so the packed int4 shapes materialize
        # where they must; int8-KERNEL eligibility checks compare
        # against "int8" explicitly (the dense Pallas decode kernels
        # stream unpacked int8 only — int4 serves through the dequant
        # fallback dense, and through the paged kernel's in-VMEM nibble
        # unpack when paged).
        self.kv_quantized = False if _kv_raw == "bfloat16" else _kv_raw
        # Decode impl: the bf16 einsum path is a well-fused GEMV; the
        # Pallas cache-streaming kernel exists for the int8 cache's
        # in-VMEM dequant and is int8-ONLY — its bf16-layout K/V
        # BlockSpec (1, block_s, 1, Dh) breaks the TPU lowering's
        # last-two-dims rule whenever Hkv > 1 (jax 0.9.0 refuses it with
        # a ValueError before Mosaic, PR 22), so bf16 decode always
        # takes the einsum path.
        # Operational kill-switch: serve int8 KV through the dequant
        # path (slower, warned below) without a code change.
        kill_switch = env_flag("BCG_TPU_DISABLE_INT8_DECODE_KERNEL")
        # GQA group-width guard: non-power-of-two groups (the 14B
        # preset's group 5: H=40, Hkv=8) take the XLA dequant path
        # unless BCG_TPU_ALLOW_PADDED_GROUP_KERNEL is set, in which case
        # the wrappers pad the group to pow2_rows
        # (ops/decode_attention.py).  The guard dates from a compiler
        # that is gone; the installed one (jaxlib 0.9.0 / libtpu 0.0.34)
        # compiles group 5 for a described v5e both padded and unpadded
        # (PR 22, compile only) — dropping the guard waits for its A/B
        # on the chip (ROADMAP S6/D8).
        from bcg_tpu.ops.decode_attention import pow2_rows

        group = self.spec.num_heads // max(self.spec.num_kv_heads, 1)
        group_ok = pow2_rows(group) == group
        if env_flag("BCG_TPU_ALLOW_PADDED_GROUP_KERNEL"):
            group_ok = True
        int8_kernel_off = kill_switch or not group_ok
        if (self.kv_dtype == "int8" and self._kernel_blocker is None
                and not int8_kernel_off):
            self.decode_attention_impl = "pallas"
        else:
            self.decode_attention_impl = (
                "xla" if self.attention_impl == "pallas" else self.attention_impl
            )
        if self.kv_dtype == "int8" and self.decode_attention_impl != "pallas":
            # Cause attribution: the env kill-switch is checked FIRST —
            # when both it and the group guard apply, the operator set
            # the switch and the stated cause must be the actual cause.
            knob, detail = (
                ("env kill-switch", "BCG_TPU_DISABLE_INT8_DECODE_KERNEL is set")
                if kill_switch
                else ("geometry guard",
                      f"GQA group width {group} is not a power of two "
                      "(BCG_TPU_ALLOW_PADDED_GROUP_KERNEL pads it)")
                if not group_ok
                else ("kernel eligibility", self._kernel_blocker)
            )
            _kernel_fallback_warn(
                "int8 KV cache Pallas decode kernel", knob, detail,
                "the fallback dequantizes the whole cache per step, "
                "which is SLOWER than bfloat16",
            )
        elif self.kv_dtype == "int8" and self.spec.param_count < LARGE_MODEL_PARAMS:
            import warnings

            # VERDICT round-2 weak #5: the losing configuration must not
            # be silent on the Pallas path either.  Measured on v5e
            # (BENCH_NOTES round 3): 4.06 dec/s int8 KV vs 6.91 bf16 at
            # 1.4B, even after cache-length alignment + block tuning.
            warnings.warn(
                "int8 KV cache measured SLOWER than bfloat16 at sub-6B "
                "model scales on TPU; use it where the bf16 cache does "
                "not fit (8B-class on a 16 GB chip), not as a speed knob",
                stacklevel=2,
            )
        # Decode-cache length alignment.  The Pallas decode kernels
        # stream the cache in BLOCK_S-sized S blocks and jnp.pad a
        # misaligned cache — a full copy of every k/v/scale array per
        # layer per step, measured as int8 KV losing ~4x to bf16
        # (BENCH_NOTES rounds 1-2).  Allocating the cache pre-aligned
        # makes that pad a no-op; the extra masked slots cost their
        # memory and a grid step of nothing per block.
        # Sequence-parallel decode shards the cache over sp, so the
        # allocated length must divide by sp — the length-bucket ladders
        # are all even but S = bucket + max_new + 1 is odd, which would
        # otherwise quietly disqualify EVERY engine cache from the ring
        # decode path (caught by review, round 4).  Under sp>1 the ring
        # path preempts the Pallas decode kernels entirely, so ALIGN_S
        # would only waste cache HBM + per-step streaming there.
        _sp = mesh.shape.get("sp", 1) if mesh is not None else 1
        if _sp > 1:
            self._kv_align = _sp
        elif self.decode_attention_impl == "pallas":
            from bcg_tpu.ops.decode_attention import ALIGN_S

            # A multiple of the kernels' block whatever block they
            # pick (ops/decode_attention.BLOCK_S): the slots it adds
            # past a row's last token are never attended, and the
            # kernel skips their blocks.
            self._kv_align = ALIGN_S
        else:
            self._kv_align = 1
        # Bytes per (position, layer) cache slot — the unit shared by the
        # perf accounting, the KV budget guard, and the provisioner.
        # bf16: k+v at 2 bytes; int8: k+v at 1 byte + two f32 scales;
        # int4: k+v PACKED at Dh/2 bytes each + two bf16 scales — which
        # is exactly half the int8 slot (2(Dh+4) vs Dh+4 per kv head),
        # the arithmetic behind the >= 1.8x admission-cap gain the perf
        # gate pins.
        if self.kv_dtype == "int4":
            self._kv_slot_bytes = self.spec.num_kv_heads * (self.spec.head_dim + 4)
        elif self.kv_dtype == "int8":
            self._kv_slot_bytes = self.spec.num_kv_heads * (2 * self.spec.head_dim + 8)
        else:
            self._kv_slot_bytes = self.spec.num_kv_heads * self.spec.head_dim * 4
        # Layers that hold K/V: all of them, or a hybrid's full-attention
        # ones (its other layers' state is counted by cache_bytes).
        self._kv_layers = self.spec.layers_of(FULL_ATTENTION)
        self.max_model_len = config.max_model_len
        # Forced-chain fast-forward (guided/processor.py FF_CHUNK): each
        # decode step carries the sampled token plus its DFA-forced
        # continuation (JSON skeleton) in one weight pass.  Composes with
        # the int8 KV cache via the chunk decode kernel (in-VMEM dequant,
        # ops/decode_attention.py chunk_decode_attention); off-TPU the
        # fallback dequantizes the whole cache per step — correct, slow.
        self.fast_forward = bool(getattr(config, "decode_fast_forward", False))
        # Prompt-lookup speculative decoding (engine/speculative.py):
        # n-gram drafts against the row's own token history, DFA-walked
        # at draft time and verified in one K+1-position forward pass.
        # Supersedes forced-chain fast-forward when both are configured
        # (the drafter subsumes forced chains as its fallback source).
        # Env flags override the config fields so bench/sweep A/Bs need
        # no code change.
        from bcg_tpu.runtime.envflags import get_int as _get_int, is_set as _is_set

        self.spec_decode = (
            bool(getattr(config, "spec_decode", False))
            or env_flag("BCG_TPU_SPEC")
        )
        self.spec_k = (
            _get_int("BCG_TPU_SPEC_K") if _is_set("BCG_TPU_SPEC_K")
            else int(getattr(config, "spec_k", 4))
        )
        self.spec_ngram = (
            _get_int("BCG_TPU_SPEC_NGRAM") if _is_set("BCG_TPU_SPEC_NGRAM")
            else int(getattr(config, "spec_ngram", 3))
        )
        if self.spec_decode and (self.spec_k < 1 or self.spec_ngram < 1):
            raise ValueError(
                f"spec_k={self.spec_k} / spec_ngram={self.spec_ngram}: "
                "speculative decoding needs both >= 1"
            )
        if (config.quantization == "int8" and not self.fast_forward
                and not self.spec_decode):
            import warnings

            # Measured on v5e (BENCH_NOTES.md): W8A8 loses to bf16 in the
            # single-token decode loop (2.27 vs 3.00 dec/s) and only wins
            # under the [B*K, D] chunk shapes of fast-forward (and of the
            # speculative verify pass).  Configuring the losing pairing
            # should not be silent.
            warnings.warn(
                "quantization='int8' without decode_fast_forward: int8 "
                "weights are SLOWER than bfloat16 in the single-token "
                "decode loop on TPU; enable decode_fast_forward "
                "(--fast-forward) to make int8 pay off",
                stacklevel=2,
            )
        self.prefill_chunk = int(getattr(config, "prefill_chunk", 0) or 0)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk}: expected 0 (disabled) "
                "or a positive token count"
            )
        # Block-paged KV cache (engine/paged_kv.py + ops/paged_attention):
        # per-row block tables over one preallocated pool; prompt
        # prefixes shared across rows/rounds are radix-matched by token
        # content, stored once, referenced N times.  Env flag as the
        # bench/sweep override; the pool itself is allocated after the
        # weights (its auto-sizing needs the weight bytes + mem limit).
        self.paged_kv = (
            bool(getattr(config, "paged_kv", False))
            or env_flag("BCG_TPU_PAGED_KV")
        )
        self._paged = None
        self._paged_call_private: List[int] = []
        self._paged_dirty = False
        self._paged_toks_memo: Dict[str, np.ndarray] = {}
        if self.kv_dtype == "int4" and not self.paged_kv:
            import warnings

            # The losing configuration must not be silent (same
            # principle as the int8 sub-6B warning): the dense int4
            # slab has no streaming kernel — every decode step
            # dequantizes the whole packed cache, which is SLOWER than
            # bfloat16.  The capacity win int4 exists for needs the
            # paged pool (in-VMEM nibble unpack in the fused kernel).
            warnings.warn(
                "kv_cache_dtype='int4' without paged_kv: the dense "
                "packed cache serves through the full-dequant-per-step "
                "fallback, which is SLOWER than bfloat16 — enable "
                "BCG_TPU_PAGED_KV=1 (the paged Pallas kernel unpacks "
                "nibbles in VMEM) to get the capacity win without the "
                "dequant tax",
                stacklevel=2,
            )

        # Fused guided-sampling kernel (ops/guided_sampler.py): the
        # whole [B, V] masked-sampler pipeline — DFA allowed-mask,
        # EOS gate, temperature, top-p threshold scan, draw — as ONE
        # Pallas program per row, shared by all three decode-loop
        # families through _make_masked_sampler exactly like
        # _resolved_loop_impl shares the attention kernel.  Env wins
        # over the config field; "auto" = pallas where the kernel's
        # whole-row-in-VMEM design fits (TPU, vocab under the geometry
        # guard), xla elsewhere.  An EXPLICIT pallas off-TPU runs the
        # kernel in interpret mode (the parity-test path); the XLA
        # sampler (engine/speculative.make_masked_sampler) stays the
        # conformance oracle.
        from bcg_tpu.ops import guided_sampler as _gs

        raw_fs = (
            (_get_str0("BCG_TPU_FUSED_SAMPLER") or "").strip().lower()
            or str(getattr(config, "fused_sampler", "auto") or "auto").lower()
        )
        if raw_fs not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"fused_sampler={raw_fs!r}: expected 'auto', 'xla' or "
                "'pallas'"
            )
        _on_tpu = jax.default_backend() == "tpu"
        _vp, _ = _gs.vocab_rows(self.spec.vocab_size)
        _vocab_ok = _vp <= _gs.MAX_VOCAB
        resolved_fs = (
            ("pallas" if _on_tpu and _vocab_ok else "xla")
            if raw_fs == "auto" else raw_fs
        )
        if resolved_fs == "pallas" and not _vocab_ok:
            # EXPLICIT pallas only (auto never selects a guarded
            # geometry, so default boots cannot warn about a choice
            # nobody made).
            _kernel_fallback_warn(
                "fused guided-sampling kernel", "geometry guard",
                f"padded vocab {_vp} exceeds the whole-row-in-VMEM cap "
                f"({_gs.MAX_VOCAB})",
                "the sampler pipeline lowers as separate XLA ops with "
                "[B, V] intermediates per decode step",
            )
            resolved_fs = "xla"
        self.fused_sampler = resolved_fs  # "xla" | "pallas" (stats/bench)
        # The marker loop builders key compiles on and pass to
        # _make_masked_sampler (interpret mode off-TPU = parity tests).
        self._sampler_loop_impl = (
            "xla" if resolved_fs == "xla"
            else _gs.PALLAS if _on_tpu
            else _gs.PALLAS_INTERPRET
        )
        self._sampler_fused_calls = 0

        quant_mode = config.quantization  # None | "int8" | "int4"
        quantize = quant_mode is not None
        owns_params = params is None
        with self._boot.phase("init_params"):
            if params is not None:
                self.params = params
            elif config.model_name.startswith("bcg-tpu/"):
                # Hermetic presets: BORN-SHARDED random weights (no
                # checkpoint needed) — every leaf materializes through a
                # jitted per-leaf initializer under its param_sharding
                # with the quantize transform INSIDE the jit
                # (models/loader.py init_random_params_sharded), so no
                # full-precision leaf ever exists unsharded and a
                # 14B-class bench boots within one chip's share of HBM.
                from bcg_tpu.models.loader import init_random_params_sharded
                from bcg_tpu.models.quantize import quantize_leaf_transform

                self.params = init_random_params_sharded(
                    self.spec, jax.random.PRNGKey(0), mesh=mesh,
                    leaf_transform=quantize_leaf_transform(self.spec, quant_mode) if quantize else None,
                )
            else:
                from bcg_tpu.models import artifact
                from bcg_tpu.models.loader import (
                    find_checkpoint_dir, load_checkpoint_params,
                )
                from bcg_tpu.models.quantize import quantize_leaf_transform

                ckpt_dir = find_checkpoint_dir(config.model_name)
                if artifact.artifact_mode(ckpt_dir) is not None:
                    # Pre-quantized artifact (models/artifact.py): boot
                    # skips both the bf16 shard streaming and the
                    # quantization pass; the load raises on any
                    # mode/shape mismatch.
                    self.params = artifact.load_quantized_artifact(
                        self.spec, ckpt_dir, quant_mode, mesh=mesh
                    )
                else:
                    # Streamed quantized loading: each weight is
                    # quantized as it arrives so the bf16 model never
                    # exists whole on device.
                    self.params = load_checkpoint_params(
                        self.spec, config.model_name, mesh=mesh,
                        leaf_transform=quantize_leaf_transform(self.spec, quant_mode) if quantize else None,
                        ckpt_dir=ckpt_dir,
                    )

        if not owns_params:
            # Constructor-shared tree (weight sharing between engines):
            # a pre-quantized tree's format must match this engine's
            # configured mode — silently serving int8 under
            # quantization="int4", or quantized weights under
            # quantization=None, would break the capacity math
            # quantization exists for.  (A shared *bf16* unstacked tree
            # under a quantized config is fine: it is quantized below
            # like an owned one, without consuming the donor's copy.)
            from bcg_tpu.models.quantize import is_int4, is_quantized

            probe = probe_weight(self.params)
            tree_mode = (
                ("int4" if is_int4(probe) else "int8")
                if is_quantized(probe) else None
            )
            mismatch = tree_mode != quant_mode and not (
                tree_mode is None and not layers_stacked(self.params)
            )
            if mismatch:
                raise ValueError(
                    f"constructor params are {tree_mode or 'bf16'}-format "
                    f"but config.quantization={quant_mode!r}; share "
                    "weights only between engines of the same mode"
                )

        if quantize and not layers_stacked(self.params):
            from bcg_tpu.models.quantize import (
                ensure_quantized_head, is_quantized, quantize_params,
            )

            # Quantize BEFORE sharding so the int8/int4 tensors (not the
            # bf16 originals) are what gets laid out over the mesh.
            # With a mesh each leaf quantizes through a donation-aware
            # jit under its param_sharding, so the transient is one bf16
            # leaf SHARD per device, not per replica.  Constructor-
            # supplied params may already be quantized (weight sharing
            # between engines, mode-checked above) — don't quantize
            # twice, and only consume (free-as-we-go) a tree this engine
            # created itself.
            with self._boot.phase("quantize"):
                if not is_quantized(probe_weight(self.params)):
                    self.params = quantize_params(
                        self.params, self.spec, consume=owns_params,
                        mode=quant_mode, mesh=mesh,
                    )
                ensure_quantized_head(
                    self.params, self.spec, mode=quant_mode, mesh=mesh
                )

        # Per-engine suffix ladder (config field; env var as the
        # bench/sweep override) — see _SUFFIX_BUCKETS_FINE.
        self._suffix_buckets = (
            _SUFFIX_BUCKETS_FINE
            if (getattr(config, "fine_suffix_buckets", False)
                or env_flag("BCG_TPU_FINE_SUFFIX"))
            else _SUFFIX_BUCKETS
        )

        self.scan_layers = bool(getattr(config, "scan_layers", False))
        if self.scan_layers and not layers_stacked(self.params):
            # Scan-over-layers: program size O(1) in depth (see
            # EngineConfig.scan_layers).  Stacking after quantization so
            # the int8 leaves (not bf16) are what stacks; consuming an
            # owned tree keeps the peak at model + one leaf-group — with
            # a mesh, per device SHARD (jitted donate + out_shardings,
            # transformer.stack_layer_params).
            with self._boot.phase("stack"):
                self.params = stack_layer_params(
                    self.params, consume=owns_params,
                    mesh=mesh, spec=self.spec,
                )
        elif layers_stacked(self.params):
            # Constructor-supplied stacked params (weight sharing from a
            # scan-mode engine, mode-checked above) force scan mode here
            # too.
            self.scan_layers = True

        if mesh is not None:
            from bcg_tpu.parallel.sharding import shard_params

            # Leaves born under their param_sharding re-place as a
            # no-op; this pass exists for constructor-shared trees and
            # any path that still materializes replicated.
            with self._boot.phase("shard"):
                self.params = shard_params(self.params, self.spec, mesh)

        self._key = jax.random.PRNGKey(config.fake_seed if hasattr(config, "fake_seed") else 0)
        # Cumulative observability counters (bench.py's no-decode /
        # failure-fraction guards read the deltas over a measured window;
        # last_decode_steps alone only witnesses the final call).
        self.last_decode_steps = 0
        self.total_decode_steps = 0
        self.total_rows = 0
        self.failed_rows = 0
        # Perf accounting for achieved-bandwidth/MFU reporting
        # (VERDICT round-1 weak #5: perf observability stopped at
        # decisions/sec).  prefill_tokens counts PADDED positions (pads
        # cost real FLOPs); decode_kv_bytes is the estimated cache
        # traffic of the decode loop (see _decode_batch).
        self.prefill_tokens = 0
        self.prefill_seconds = 0.0
        self.decode_seconds = 0.0
        self.decode_kv_bytes = 0
        self.decode_weight_passes = 0
        # Calls where prefix caching was configured but the batch fell
        # back to full-prompt prefill (prefix unfittable/unbucketable).
        # Silent disengagement once hid a disabled cache for a whole
        # round (VERDICT round-2 weak #3) — counted and warned-once now.
        self.prefix_fallbacks = 0
        self._prefix_fallback_warned = False
        # Calls that fell back from a configured sequence-parallel path.
        # Every serving path shards under sp (one-pass, chunked, and
        # cached-prefix prefill incl. entry builds; plain and
        # fast-forward decode; bf16 and int8 caches) for every ladder
        # shape — the only reachable fallbacks are off-ladder clamp
        # shapes whose length doesn't divide sp, counted + warned-once
        # (_note_sp_bypass).  Tests and the dryrun assert zero on ladder
        # shapes: silent disengagement of a configured optimization hid
        # a disabled cache for a whole round once.
        self.sp_bypasses = 0
        self._sp_bypass_warned = False
        # Calls that fell back from configured data-parallel (dp) batch
        # sharding — reachable when the concurrent-row cap
        # (max_num_seqs / the HBM provisioner) is tighter than dp itself
        # (_dp_mult drops the alignment; the batch runs replicated).  A
        # config conflict worth surfacing, so it is counted + warned
        # once like sp.  dp_batches counts batches that ran dp-sharded.
        self.dp_bypasses = 0
        self._dp_bypass_warned = False
        self.dp_batches = 0
        # True once a decode loop was built with the sp-sharded-cache
        # attention (set in _get_decode_loop).  Truthful by construction:
        # cache allocation is sp-aligned (_kv_align) and an indivisible
        # cache length raises inside sp_decode_attention instead of
        # silently replicating, so an active flag cannot coexist with a
        # disengaged path.
        self._decode_ring_active = False
        # Calls whose batch the hbm_utilization provisioner chunked.
        self.provision_chunk_events = 0
        # Compile/retrace accounting (bcg_tpu.obs.counters): per jit
        # entry point, the set of shape signatures seen — a host-side
        # mirror of jax.jit's trace cache.  First signature per entry =
        # expected compile; every FURTHER one increments
        # engine.retrace.<entry> — a retrace in the steady-state decode
        # loop is the single most expensive silent regression this
        # engine has.
        self._jit_shapes: Dict[str, Dict] = {}
        # Pad the token-byte table to the MODEL vocab (embedding tables are
        # padded past the tokenizer vocab, e.g. Qwen3 151669 -> 151936);
        # padding entries are b'' = forbidden, so logits and masks agree.
        self._token_bytes = self.tokenizer.token_bytes()
        if len(self._token_bytes) < self.spec.vocab_size:
            self._token_bytes += [b""] * (self.spec.vocab_size - len(self._token_bytes))
        elif len(self._token_bytes) > self.spec.vocab_size:
            raise ValueError(
                f"tokenizer vocab {len(self._token_bytes)} exceeds model vocab "
                f"{self.spec.vocab_size}"
            )

        # jit entry points (shape-polymorphic via jax.jit's trace cache).
        self._prefill_impl = self._kernel_impl(self.attention_impl)
        if self.spec.hybrid:
            from bcg_tpu.ops import HybridImpl, gated_delta, moe, selective_scan

            # One switch for the Pallas kernels: where flash prefill
            # runs, the chunkwise delta-rule kernel, the grouped expert
            # matmul and the selective-scan kernel run; elsewhere their
            # XLA twins (the same chunk mathematics as a scan; every held
            # expert, weighted; the scan over positions).
            kernels = self.attention_impl == "pallas"
            self._prefill_impl = HybridImpl(
                self._prefill_impl,
                gated_delta.PALLAS if kernels else gated_delta.XLA,
                moe.PALLAS if kernels else moe.XLA,
                selective_scan.PALLAS if kernels else selective_scan.XLA,
            )
        # Named after their obs_hlo census entries: every program of
        # the prefill family carries "prefill" in the profiler's trace.
        self._prefill = jax.jit(
            _named("prefill", prefill, spec=self.spec,
                   impl=self._prefill_impl),
            donate_argnames=("cache",),
        )
        self._prefill_suffix = jax.jit(
            _named("prefill_suffix", prefill_with_prefix, spec=self.spec,
                   impl=self._prefill_impl),
            donate_argnames=("cache",),
        )
        # Sequence-parallel full-prompt prefill (ring attention over the
        # mesh's `sp` axis, transformer.prefill_sp): selected per call by
        # _prefill_possibly_chunked for single-pass full prefills.
        # Chunked prefill AND the cached-prefix suffix shard through the
        # chunk jit's ring path instead (the suffix is one chunk against
        # the cached prefix).  Long-context counterpart to the
        # reference's context COMPRESSION (SURVEY.md §5.7) — prefill
        # activations shard O(L/sp) per chip.
        self._prefill_sp = None
        self._sp_devices = mesh.shape.get("sp", 1) if mesh is not None else 1
        # Data parallelism (agent parallelism): batch rows shard over the
        # mesh's `dp` axis — one agent per device slice when the game's
        # agent count equals dp (BASELINE config 4's one-agent-per-chip
        # scale sweep).  Weights replicate over dp (parallel/sharding.py);
        # batch arrays and the KV cache are placed with a "dp"-first
        # NamedSharding (_put_batch/_put_cache) so XLA partitions every
        # prefill/decode along the batch axis; the ring/sp shard_maps
        # already carry dp in their in_specs (ops/ring_attention.py).
        self._dp_devices = mesh.shape.get("dp", 1) if mesh is not None else 1
        if self.kv_dtype == "int4" and self._sp_devices > 1:
            raise ValueError(
                "kv_cache_dtype='int4' does not compose with sequence "
                f"parallelism (sp={self._sp_devices}): the sp ring decode "
                "kernels dequantize unpacked int8 scales only"
            )
        if self._sp_devices > 1:
            from bcg_tpu.models.transformer import prefill_sp

            self._prefill_sp = jax.jit(
                _named("prefill_sp", prefill_sp, spec=self.spec, mesh=mesh,
                       impl=self._prefill_impl),
                donate_argnames=("cache",),
            )
        self._prefill_chunk_at = jax.jit(
            _named(
                "prefill_chunk", prefill_chunk_at, spec=self.spec,
                impl=self._prefill_impl,
                # Chunked prefill is the LARGE size class's default; under
                # sp it must shard, not bypass (transformer.prefill_chunk_at
                # ring branch — the chunk attends the whole sharded cache).
                ring=((mesh, "sp") if self._sp_devices > 1 else None),
                # a spec with routed experts: each chunk also counts its
                # (token, held expert) assignments, read back with the
                # decode loop's steps (no sync of their own)
                **({"tally": True} if self.spec.moe else {}),
            ),
            donate_argnames=("cache",),
        )
        self._decode_loops: Dict[Tuple, Any] = {}
        # (B, S) -> jitted sharded-zero cache initializer (see
        # _init_cache_sharded; memoized so each batch shape compiles once).
        self._cache_init_jits: Dict[Tuple[int, int], Any] = {}
        _assemble_fn = (
            self._assemble_cache_stacked_fn
            if self.scan_layers
            else self._assemble_cache_fn
        )
        if mesh is not None and mesh.size > 1:
            # Constrain the assembled cache to the mesh layout AT TRACE
            # TIME so GSPMD produces it directly sharded — assembling
            # replicated and resharding after would stage the full
            # unsharded cache on one device first, the same transient
            # spike _init_cache_sharded's out_shardings avoid for fresh
            # caches.
            from bcg_tpu.parallel.sharding import kv_cache_tree_sharding

            _base_assemble = _assemble_fn

            def _assemble_fn(entry_kvs, gid, tail):
                cache = _base_assemble(entry_kvs, gid, tail=tail)
                return jax.tree.map(
                    jax.lax.with_sharding_constraint,
                    cache,
                    kv_cache_tree_sharding(
                        mesh, cache, quantized=self.kv_quantized,
                        stacked=self.scan_layers,
                    ),
                )

        self._assemble_cache = jax.jit(
            _assemble_fn, static_argnames=("tail",)
        )
        # Prefix caching: the per-role system-prompt segment is static for
        # a whole run, so its KV is prefilled once and reused by every
        # round's decision/vote call (the reference caches the system
        # prompt STRING for the same reason, bcg_agents.py:174-177; with
        # an owned engine we can cache the actual KV).  Safe only when the
        # template family ends the prefix at a special-token boundary so
        # BPE merges cannot straddle the split.
        self.prefix_caching = getattr(config, "prefix_caching", True)
        self._prefix_safe = prefix_split_safe(config.model_name)
        from collections import OrderedDict

        # Keyed (prefix, bucket): see _get_prefix_entry.
        self._prefix_cache: "OrderedDict[Tuple[str, int], Dict[str, Any]]" = (
            OrderedDict()
        )
        self._prefix_lens_memo: Dict[str, int] = {}
        self._prefix_bytes = 0
        # Per-DEVICE counterpart (shard sizes via tree_bytes_per_device):
        # what the HBM ledger's prefix_cache account is charged with —
        # global nbytes would overstate it by the shard factor on
        # tp/sp-sharded meshes.
        self._prefix_bytes_dev = 0
        self._prefix_active: set = set()
        self._prefix_over_budget_warned = False
        # Prefix-KV budget: a fraction of device memory when known (the
        # weights/decode-cache OOM guard covers the rest), else a fixed
        # allowance ample for CPU tests.
        self._prefix_budget = 4 << 30
        # One-time constants for the hbm_utilization OOM guard.  Leaf
        # .nbytes is the GLOBAL size while bytes_limit is ONE device's.
        # Per-device weight bytes come from the leaves' ACTUAL shardings
        # (tree_bytes_per_device — a leaf the head-divisibility guards
        # replicate counts whole); per-device KV bytes come from the
        # axes kv_cache_tree_sharding actually engages for the given
        # B/S/Hkv (_kv_bytes_per_device), NOT a flat mesh.size divisor —
        # the dp-bypass path replicates the batch axis, so dividing by
        # the full mesh overcommitted per-device HBM by up to dp×
        # (ADVICE round-5 medium).
        self._kv_budget_warned = False
        self._mesh_devices = mesh.size if mesh is not None else 1
        self._kv_bytes_memo: Dict[Tuple[int, int], int] = {}
        self._cache_bytes_memo: Dict[Tuple[int, int], Dict[str, int]] = {}
        # a spec with routed experts: the chunk programs' counts of (token,
        # held expert) assignments, on the device until the call's readback
        self._moe_prefill_counts: list = []
        self._param_bytes = sum(
            getattr(p, "nbytes", 0) for p in jax.tree.leaves(self.params)
        )
        if mesh is not None:
            from bcg_tpu.parallel.sharding import tree_bytes_per_device

            self._param_bytes_per_device = tree_bytes_per_device(self.params)
        else:
            self._param_bytes_per_device = self._param_bytes
        try:
            stats = jax.devices()[0].memory_stats() or {}
            self._mem_limit = stats.get("bytes_limit")
        except (IndexError, AttributeError, NotImplementedError, RuntimeError):
            # Backend exposes no allocator stats (CPU) — size-adaptive
            # prefix budgeting simply stays off.
            self._mem_limit = None
        if self._mem_limit:
            # Weight-aware: the prefix cache may only use a slice of what
            # the model leaves free (an 8B int8 model on a 16 GB chip
            # leaves ~7 GB for KV + prefixes + workspace).
            free = self._mem_limit - self._param_bytes_per_device
            self._prefix_budget = min(
                4 << 30, max(256 << 20, int(free * 0.25))
            )
        # HBM ledger (bcg_tpu/obs/ledger.py): declare this device's
        # capacity and charge the weight tree — per-device bytes from the
        # leaves' ACTUAL shardings, the same tree_bytes_per_device the
        # budget math uses, so the ledger and admission cannot disagree.
        # Keyed by engine identity: weight-sharing engines each charge
        # their own (shared-tree) share exactly once, and shutdown
        # credits exactly what this instance charged.
        obs_ledger.set_limit(self._mem_limit)
        obs_ledger.charge("params", id(self), self._param_bytes_per_device)
        if self.paged_kv:
            if self._sp_devices > 1:
                raise ValueError(
                    "paged_kv does not compose with sequence parallelism "
                    f"(sp={self._sp_devices}) yet: pool blocks are shared "
                    "across rows so the sequence dim cannot shard"
                )
            from bcg_tpu.engine.paged_kv import PagedKV
            from bcg_tpu.models.transformer import prefill_paged

            bs_blk = (
                _get_int("BCG_TPU_KV_BLOCK_SIZE")
                or int(getattr(config, "kv_block_size", 16) or 16)
            )
            pool_blocks = (
                _get_int("BCG_TPU_KV_POOL_BLOCKS")
                or int(getattr(config, "kv_pool_blocks", 0) or 0)
            )
            if pool_blocks <= 0:
                pool_blocks = self._auto_pool_blocks(bs_blk)
            self._paged = PagedKV(
                self.spec, pool_blocks, bs_blk,
                quantized=self.kv_quantized, stacked=self.scan_layers,
                mesh=mesh,
            )
            # The radix-resident working set is the paged successor of
            # the dense prefix cache — same ledger account, same
            # engine-keyed idempotent charge, credited by shutdown().
            self._paged.set_ledger_key(id(self))
            # Paged decode-attention impl: the fused Pallas page-gather
            # kernel vs the XLA block-gather reference (the oracle).
            # Env wins over the config field; "auto" = pallas where the
            # kernel can lower natively (TPU, lane-aligned head dim),
            # xla elsewhere.  An EXPLICIT pallas off-TPU runs the
            # kernel in interpret mode (the parity-test path).
            from bcg_tpu.runtime.envflags import get_str as _get_str

            raw_impl = (
                (_get_str("BCG_TPU_PAGED_KV_IMPL") or "").strip().lower()
                or str(getattr(config, "paged_kv_impl", "auto") or "auto").lower()
            )
            if raw_impl not in ("auto", "xla", "pallas"):
                raise ValueError(
                    f"paged_kv_impl={raw_impl!r}: expected 'auto', 'xla' "
                    "or 'pallas'"
                )
            on_tpu = jax.default_backend() == "tpu"
            lane_ok = self.spec.head_dim % 128 == 0
            # The paged kernel is not shard_map'd yet: under a mesh it
            # would meet GSPMD bare and fail to compile.
            meshed = mesh is not None and mesh.size > 1
            if raw_impl == "auto":
                # "where the kernel can lower natively": a head dim
                # Mosaic cannot tile silently stays on the reference —
                # default boots must not warn about a choice nobody made.
                resolved = (
                    "pallas" if on_tpu and lane_ok and not meshed else "xla"
                )
            else:
                resolved = raw_impl
            if resolved == "pallas" and meshed:
                raise ValueError(
                    "paged_kv_impl='pallas' under a multi-device mesh: the "
                    "paged kernel has no shard_map wrapper yet (ROADMAP "
                    "S7); use 'auto' or 'xla'"
                )
            if resolved == "pallas" and on_tpu and not lane_ok:
                import warnings

                # EXPLICIT pallas only: same lane-alignment guard as the
                # dense decode kernel, falling back LOUDLY.
                warnings.warn(
                    f"paged_kv_impl='pallas' with head_dim "
                    f"{self.spec.head_dim} not a multiple of 128: the "
                    "kernel cannot lower on TPU — using the XLA gather "
                    "reference",
                    stacklevel=2,
                )
                resolved = "xla"
            self.paged_kv_impl = resolved  # "xla" | "pallas" (stats/bench)
            from bcg_tpu.ops.paged_attention import (
                PALLAS as _PAGED_PALLAS,
                PALLAS_INTERPRET as _PAGED_PALLAS_IT,
            )

            # The marker the decode loops pass through transformer's
            # ``impl`` parameter (models/transformer._cache_attention /
            # _block_chunk dispatch on it for "tbl" entries).
            self._paged_loop_impl = (
                "xla" if resolved == "xla"
                else _PAGED_PALLAS if on_tpu
                else _PAGED_PALLAS_IT
            )
            if self.prefill_chunk:
                # Paged chunked prefill gathers each chunk's history at
                # BLOCK granularity (whole table columns), so the chunk
                # size aligns UP to the pool's block size — at most
                # bs-1 extra tokens of activation per chunk.
                self.prefill_chunk += (-self.prefill_chunk) % bs_blk
            # Worst-case transient blocks of one radix entry build (the
            # bucketed scratch tail) — carved out of the admission math
            # so an admitted batch cannot hit PoolExhausted mid-prefill
            # (see _paged_scratch_blocks).
            self._paged_scratch_blocks = self._paged_build_scratch_blocks()
            self._prefill_paged = jax.jit(
                _named("prefill_paged", prefill_paged, spec=self.spec,
                       impl=self._prefill_impl),
                donate_argnames=("cache",),
            )
            from bcg_tpu.models.transformer import prefill_paged_chunk_at

            self._prefill_paged_chunk_at = jax.jit(
                _named("prefill_paged_chunk", prefill_paged_chunk_at,
                       spec=self.spec, impl=self._prefill_impl),
                donate_argnames=("cache",),
            )
        # Telemetry endpoint (BCG_TPU_METRICS_PORT) + fleet metric-shard
        # flusher (BCG_TPU_METRICS_SHARD_DIR): idempotent, off by
        # default — a scraped deployment gets engine.hlo.* / hbm.* /
        # serve.* without further wiring, and a multi-process run gets
        # its per-rank shard stream from engine boot onward.
        from bcg_tpu.obs import export as obs_export, fleet as obs_fleet

        obs_export.maybe_start_http_server()
        obs_fleet.maybe_start_shard_writer()
        # Sampler/KV-dtype self-description for bench JSON — published
        # at BOOT (not just per call) so a run that dies before its
        # first decode still reports which configuration it booted
        # (runtime.metrics idiom, same as LAST_BOOT_PHASES).
        from bcg_tpu.runtime import metrics as _boot_metrics

        _boot_metrics.publish_sampler(self.sampler_stats())
    # ------------------------------------------------------------- tokenizing

    def _encode_leftpad(
        self, texts: List[str], limits: List[int],
        bucket_ladder: Tuple[int, ...],
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Tokenize (keeping the LAST ``limits[i]`` tokens PER ROW) and
        LEFT-pad into a bucketed [B, L] batch.  Row limits differ when
        per-row token budgets differ — each row reserves only ITS OWN
        decode budget, so merging a small-budget call with a large-budget
        one never tightens the small call's prompt window.  The ladder
        extends by doubling past its static tail so a raised max_model_len
        still lands on stable buckets; anything beyond the last bucket
        uses the largest row limit (one stable shape, not ragged)."""
        token_lists = [
            self.tokenizer.encode(t)[-lim:] for t, lim in zip(texts, limits)
        ]
        max_len = max(len(t) for t in token_lists)
        max_limit = max(limits)
        buckets = list(bucket_ladder)
        while buckets[-1] < max_limit:
            buckets.append(buckets[-1] * 2)
        L = next((b for b in buckets if b >= max_len), max_limit)
        L = max(min(L, max_limit), max_len)
        # Sequence-parallel prefill shards the token dim over sp: align
        # the window up so near-cap prompts (clamped to max_limit, an
        # arbitrary value like 8095) still divide.  The extra slots are
        # left-pads — masked, position-free — so the model-len cap on
        # real tokens (the [-lim:] truncation above) is unaffected.
        if self._sp_devices > 1:
            L += (-L) % self._sp_devices
        B = len(token_lists)
        tokens = np.full((B, L), self.tokenizer.pad_id, dtype=np.int32)
        valid = np.zeros((B, L), dtype=bool)
        for i, toks in enumerate(token_lists):
            tokens[i, L - len(toks):] = toks
            valid[i, L - len(toks):] = True
        return tokens, valid, L

    def _prepare_batch(
        self, full_prompts: List[str], budgets: List[int]
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Tokenize + LEFT-pad into a bucketed [B, L] batch, reserving
        each row's own decode budget: prompt + output always fit
        max_model_len (bucket rounding is capped so it can never eat the
        decode budget)."""
        limits = [self.max_model_len - b - 1 for b in budgets]
        if min(limits) < 1:
            raise BudgetError(
                f"max_tokens={max(budgets)} leaves no room for a prompt "
                f"within max_model_len={self.max_model_len}"
            )
        return self._encode_leftpad(full_prompts, limits, _LEN_BUCKETS)

    # --------------------------------------------------------- prefix caching

    def _entry_bytes_per_device(self, kv, global_bytes: int) -> int:
        """ONE device's share of a prefix entry's KV (shard sizes via
        tree_bytes_per_device) — the unit the HBM ledger accounts in.
        ``global_bytes`` (the nbytes sum the LRU budget uses) is the
        single-device answer, so skip the leaf walk without a mesh."""
        if self.mesh is None or self._mesh_devices <= 1:
            return global_bytes
        from bcg_tpu.parallel.sharding import tree_bytes_per_device

        return tree_bytes_per_device(kv)

    def _prefix_len(self, prefix: str) -> int:
        """Token count of a prefix (memoized — called every batch)."""
        n = self._prefix_lens_memo.get(prefix)
        if n is None:
            n = len(self.tokenizer.encode(prefix))
            self._prefix_lens_memo[prefix] = n
        return n

    def _prune_prefix_memo(self, cap: int = 512) -> None:
        """Bound the token-length memo: keyed by full multi-KB prefix
        strings, a long-lived multi-run process would otherwise retain
        every system prompt ever seen.  Entries whose prefix still has a
        live KV entry stay (they are the hot set); the rest go once the
        memo outgrows ``cap``."""
        if len(self._prefix_lens_memo) <= cap:
            return
        # Composite core keys are "prefix\x1ecore" strings: a system
        # prefix whose only surviving entries are composite is still hot
        # (every _get_core_entry call re-reads its length), so its prefix
        # component must count as live too.
        live = set()
        for p, _b in self._prefix_cache:
            live.add(p)
            if "\x1e" in p:
                live.add(p.split("\x1e", 1)[0])
        self._prefix_lens_memo = {
            p: n for p, n in self._prefix_lens_memo.items() if p in live
        }

    def _get_prefix_entry(
        self, prefix: str, limit: int, bucket: int
    ) -> Optional[Dict[str, Any]]:
        """Prefill (once) and cache the KV for a static prompt prefix at
        the given bucket size.

        The caller picks ONE bucket for every prefix in the batch (the
        smallest rung covering the longest prefix): uniform entry shapes
        keep the cache-assembly jit signature stable — per-entry buckets
        minted a fresh (shape-pattern, order) retrace+compile every time
        the hidden role assignment reshuffled between games.

        Returns ``None`` when the prefix cannot fit — the caller then
        falls back to full-prompt prefill.
        """
        key = (prefix, bucket)
        entry = self._prefix_cache.get(key)
        if entry is None:
            # Same prefix cached at a LARGER bucket (batch compositions
            # alternating between phases pick different rungs): reuse it
            # instead of prefilling a duplicate — the assembly pads every
            # entry to the batch max anyway.  Bounded to 2x the requested
            # bucket: pad slots in [0, P) are streamed by every decode
            # step, so an arbitrarily large reused entry would trade a
            # one-time prefill for a per-step bandwidth tax.
            for (p2, b2), e2 in self._prefix_cache.items():
                if p2 == prefix and bucket < b2 <= min(limit, 2 * bucket):
                    key, entry = (p2, b2), e2
                    break
        if entry is not None:
            self._prefix_cache.move_to_end(key)  # LRU touch
            self._prefix_active.add(key)
            return entry
        toks = self.tokenizer.encode(prefix)
        if not toks or len(toks) > limit - 64:
            return None
        Pb = bucket
        if Pb > limit or len(toks) > Pb:
            return None
        tokens = np.full((1, Pb), self.tokenizer.pad_id, dtype=np.int32)
        valid = np.zeros((1, Pb), dtype=bool)
        tokens[0, Pb - len(toks):] = toks
        valid[0, Pb - len(toks):] = True
        cache = init_kv_cache(
            self.spec, 1, Pb, quantized=self.kv_quantized,
            stacked=self.scan_layers,
        )
        # _prefill_possibly_chunked owns the sp-ring-vs-replicated
        # dispatch (counted fallback for unaligned clamp rungs) — one
        # copy of that logic for batches, entry builds, and core-extend.
        _, kv = self._prefill_possibly_chunked(tokens, valid, Pb, cache)
        # Entry prefills run inside _decode_batch's t0->t1 window, so
        # their (padded) positions must count toward prefill_tokens or
        # miss-heavy windows understate MFU (advisor round-2).
        self.prefill_tokens += Pb
        obs_counters.inc("engine.prefill.positions_padded", Pb)
        obs_counters.inc("engine.prefill.positions_real", len(toks))
        # "toks" rides along for the speculative drafter's history
        # buffer (prompt-lookup matches against the FULL prompt, and the
        # prefix tokens are otherwise only present as cached KV).
        entry = {
            "kv": kv, "valid": valid[0], "len": len(toks), "bucket": Pb,
            "toks": np.asarray(toks, dtype=np.int32),
        }
        # Size-aware LRU.  System prompts embed the agent id ("You are
        # agent_3 ..."), so a 10-agent run holds ~20 DISTINCT prefixes
        # (per agent x per phase) — a small fixed cap would thrash and
        # re-prefill ~B entries every call.  Evict by BYTES, not count:
        # the working set (a few GB at 1-2K-token buckets) must fit
        # alongside weights and the decode cache.
        entry_bytes = sum(
            getattr(a, "nbytes", 0) for a in jax.tree.leaves(kv)
        )
        self._prefix_bytes += entry_bytes
        entry["bytes"] = entry_bytes
        entry["bytes_dev"] = self._entry_bytes_per_device(kv, entry_bytes)
        self._prefix_bytes_dev += entry["bytes_dev"]
        self._prefix_cache[key] = entry
        self._prefix_active.add(key)
        # A larger entry supersedes smaller-bucket duplicates of the same
        # prefix (the reuse scan above prefers the larger one from now
        # on) — evict them so the same KV is never held twice.
        for k2 in [
            k for k in self._prefix_cache
            if k[0] == prefix and k[1] < Pb and k not in self._prefix_active
        ]:
            old = self._prefix_cache.pop(k2)
            self._prefix_bytes -= old["bytes"]
            self._prefix_bytes_dev -= old["bytes_dev"]
        # Evict LRU-first, but never a key of the batch being assembled
        # (_prefix_active): evicting mid-batch would re-prefill the whole
        # working set on EVERY call — the thrash this cache exists to
        # prevent.  If the active set alone exceeds the budget the cache
        # runs over it for the call (the HBM spike is inherent to the
        # batch); warn once so the operator can shrink it.
        self._evict_prefix_over_budget()
        return entry

    @staticmethod
    def _assemble_cache_fn(entry_kvs, gid, tail: int):
        """Gather per-row prefix KV from the cached entries and append the
        suffix+decode tail, for every layer, in one traced computation.

        ``entry_kvs``: tuple (one per unique prefix) of per-layer kv lists,
        each array [1, Pb, Hkv, Dh] (int8 layout [1, Hkv, Pb, Dh]; scales
        [1, Hkv, Pb]); ``gid`` [B] maps rows to entries.  Shapes are
        static under jit, so the pad widths and the target P = max(Pb)
        specialize at trace time.
        """
        s_axis = 2 if "k_scale" in entry_kvs[0][0] else 1
        P = max(e[0]["k"].shape[s_axis] for e in entry_kvs)

        def stack(name, pad_axis, pad_value, li):
            arrs = []
            for e in entry_kvs:
                a = e[li][name]
                pad = P - a.shape[pad_axis]
                if pad:
                    widths = [(0, 0)] * a.ndim
                    widths[pad_axis] = (0, pad)
                    a = jnp.pad(a, widths, constant_values=pad_value)
                arrs.append(a)
            g = jnp.concatenate(arrs, axis=0)[gid]  # [B, ...]
            tail_shape = list(g.shape)
            tail_shape[pad_axis] = tail
            tail_arr = (jnp.ones if pad_value == 1 else jnp.zeros)(
                tuple(tail_shape), g.dtype
            )
            return jnp.concatenate([g, tail_arr], axis=pad_axis)

        cache = []
        for li in range(len(entry_kvs[0])):
            quantized = "k_scale" in entry_kvs[0][li]
            kv_axis = 2 if quantized else 1  # int8 layout is [B, Hkv, S, Dh]
            layer = {
                "k": stack("k", kv_axis, 0, li),
                "v": stack("v", kv_axis, 0, li),
            }
            if quantized:
                layer["k_scale"] = stack("k_scale", 2, 1, li)
                layer["v_scale"] = stack("v_scale", 2, 1, li)
            cache.append(layer)
        return cache

    @staticmethod
    def _assemble_cache_stacked_fn(entry_kvs, gid, tail: int):
        """Scan-over-layers variant of :meth:`_assemble_cache_fn`: entries
        are stacked dicts whose leaves carry a leading [num_layers] dim
        (bf16 k/v [Lyr, 1, Pb, Hkv, Dh]; int8 [Lyr, 1, Hkv, Pb, Dh] with
        scales [Lyr, 1, Hkv, Pb]), and the assembled cache keeps that
        layout — every sequence axis shifts one right of the per-layer
        form."""
        quantized = "k_scale" in entry_kvs[0]
        s_axis = 3 if quantized else 2

        def stack(name, pad_axis, pad_value):
            arrs = []
            for e in entry_kvs:
                a = e[name]
                pad = (
                    max(x[name].shape[pad_axis] for x in entry_kvs)
                    - a.shape[pad_axis]
                )
                if pad:
                    widths = [(0, 0)] * a.ndim
                    widths[pad_axis] = (0, pad)
                    a = jnp.pad(a, widths, constant_values=pad_value)
                arrs.append(a)
            g = jnp.concatenate(arrs, axis=1)[:, gid]  # [Lyr, B, ...]
            tail_shape = list(g.shape)
            tail_shape[pad_axis] = tail
            tail_arr = (jnp.ones if pad_value == 1 else jnp.zeros)(
                tuple(tail_shape), g.dtype
            )
            return jnp.concatenate([g, tail_arr], axis=pad_axis)

        out = {"k": stack("k", s_axis, 0), "v": stack("v", s_axis, 0)}
        if quantized:
            out["k_scale"] = stack("k_scale", 3, 1)
            out["v_scale"] = stack("v_scale", 3, 1)
        return out

    def _get_core_entry(
        self, prefix: str, core: str, limit: int
    ) -> Optional[Dict[str, Any]]:
        """Two-level prefix entry: the (per-role) system ``prefix`` KV
        extended by a shared per-round ``core`` (vote-phase proposals +
        history block).  Cached under a composite key so every agent of
        the role reuses ONE core prefill per round instead of re-prefilling
        2000+ tokens per row (VERDICT round-1 item #3).

        The record-separator composite key cannot collide with plain
        prefix strings, so both entry kinds share the LRU byte budget —
        stale cores from previous rounds age out naturally.
        """
        composite = prefix + "\x1e" + core
        for (p2, b2), e2 in self._prefix_cache.items():
            if p2 == composite and b2 <= limit:
                self._prefix_cache.move_to_end((p2, b2))
                self._prefix_active.add((p2, b2))
                return e2
        core_toks = self.tokenizer.encode(core)
        if not core_toks:
            return None
        Cb = next(
            (b for b in self._suffix_buckets if b >= len(core_toks)),
            len(core_toks),
        )
        if self._sp_devices > 1:
            # sp-align the off-ladder fallback UP (ladder rungs already
            # divide): the combined entry cache (P1b + Cb) must divide
            # sp for the ring core-extend; extra slots are left-pads.
            Cb += (-Cb) % self._sp_devices
        # Level 1: the system prefix at its own natural rung — bounded so
        # the combined entry (P1b + Cb) still leaves suffix room below.
        p1_len = self._prefix_len(prefix)
        p1_limit = limit - 64 - Cb
        P1_rung = next(
            (b for b in _PREFIX_BUCKETS if b >= p1_len and b <= p1_limit),
            # Ladder overshoot with a prefix that itself fits: clamp to
            # the limit (same rationale as _prepare_prefixed_batch).
            p1_limit if 0 < p1_len <= p1_limit else None,
        )
        if P1_rung is not None and self._sp_devices > 1:
            # sp-align clamp rungs by construction: down when the prefix
            # still fits, else UP to the next sp multiple (same pad-the-
            # entry rationale as _prepare_prefixed_batch's clamp
            # alignment) — no reachable rung is left unaligned.
            aligned = P1_rung - P1_rung % self._sp_devices
            if 0 < p1_len <= aligned:
                P1_rung = aligned
            elif P1_rung % self._sp_devices:
                P1_rung += (-P1_rung) % self._sp_devices
        if P1_rung is None or p1_len == 0:
            return None
        e1 = self._get_prefix_entry(prefix, limit, P1_rung)
        if e1 is None:
            return None
        P1b = e1["bucket"]
        Pb = P1b + Cb
        # sp up-alignment may overshoot the 64-token slack by < sp; the
        # batch assembler's limits_s guard still enforces real suffix
        # room (at sp=1 this reduces to the original Pb > limit - 64).
        if Pb >= limit - 64 + max(1, self._sp_devices):
            return None
        # Extend: prefill the core against the level-1 KV (the same
        # suffix-prefill jit every prefix-cached batch uses).
        cache = self._assemble_cache(
            (e1["kv"],), jnp.asarray(np.zeros(1, np.int32)), tail=Cb
        )
        tokens = np.full((1, Cb), self.tokenizer.pad_id, dtype=np.int32)
        cvalid = np.zeros((1, Cb), dtype=bool)
        tokens[0, Cb - len(core_toks):] = core_toks
        cvalid[0, Cb - len(core_toks):] = True
        pv = np.zeros((1, P1b), dtype=bool)
        pv[0] = e1["valid"]
        # Core-extend = prefill a suffix against a cached prefix: exactly
        # _prefill_possibly_chunked's prefix branch, which owns the
        # sp-ring-vs-replicated dispatch (and chunking for oversized
        # cores) — one copy of that logic, not two.
        _, kv = self._prefill_possibly_chunked(
            tokens, cvalid, Cb, cache,
            prefix_valid=pv, prefix_lens=np.asarray([e1["len"]], np.int32),
        )
        # Counted for the same reason as in _get_prefix_entry: this
        # prefill happens inside the caller's prefill timing window.
        self.prefill_tokens += Cb
        obs_counters.inc("engine.prefill.positions_padded", Cb)
        obs_counters.inc("engine.prefill.positions_real", len(core_toks))
        entry = {
            "kv": kv,
            "valid": np.concatenate([pv[0], cvalid[0]]),
            "len": e1["len"] + len(core_toks),
            "bucket": Pb,
            "toks": np.concatenate(
                [e1["toks"], np.asarray(core_toks, dtype=np.int32)]
            ),
        }
        entry_bytes = sum(getattr(a, "nbytes", 0) for a in jax.tree.leaves(kv))
        self._prefix_bytes += entry_bytes
        entry["bytes"] = entry_bytes
        entry["bytes_dev"] = self._entry_bytes_per_device(kv, entry_bytes)
        self._prefix_bytes_dev += entry["bytes_dev"]
        key = (composite, Pb)
        self._prefix_cache[key] = entry
        self._prefix_active.add(key)
        self._evict_prefix_over_budget()
        return entry

    def _evict_prefix_over_budget(self) -> None:
        """LRU eviction shared by both entry kinds — never a key of the
        batch being assembled (see _get_prefix_entry).  Doubles as the
        prefix account's ledger sync point: both entry creators end
        here, so re-charging the engine's single prefix-cache key with
        the post-eviction total keeps ``hbm.prefix_cache_bytes`` exact
        without per-entry ledger keys."""
        evictable = [
            k for k in self._prefix_cache if k not in self._prefix_active
        ]
        while self._prefix_bytes > self._prefix_budget and evictable:
            old = self._prefix_cache.pop(evictable.pop(0))
            self._prefix_bytes -= old["bytes"]
            self._prefix_bytes_dev -= old["bytes_dev"]
        obs_ledger.charge("prefix_cache", id(self), self._prefix_bytes_dev)
        if (
            self._prefix_bytes > self._prefix_budget
            and not self._prefix_over_budget_warned
        ):
            import warnings

            warnings.warn(
                f"prefix-KV working set ({self._prefix_bytes / 1e9:.1f} GB) "
                f"exceeds its budget ({self._prefix_budget / 1e9:.1f} GB); "
                "prefix caching will hold it anyway for this batch — "
                "reduce agents per call or disable prefix_caching if HBM "
                "is tight",
                stacklevel=2,
            )
            self._prefix_over_budget_warned = True

    def _core_seam_safe(self, core_text: str, tail_text: str) -> bool:
        """True when encode(core) + encode(tail) == encode(core + tail) —
        required for the mid-user-turn split (a BPE merge straddling the
        seam would change tokens).  Checked per batch; failure merges the
        core back into the tail (correct, just uncached)."""
        enc = self.tokenizer.encode
        return enc(core_text) + enc(tail_text) == enc(core_text + tail_text)

    def _prepare_prefixed_batch(self, parts, budgets: List[int],
                                decode_slots: Optional[int] = None):
        """Assemble a batch whose cache slots [0, P) are prefilled prefix
        KV (gathered per row from the prefix cache) and whose suffix is
        left-padded into [P, P+Ls).  Rows are (prefix, core, tail): a
        non-empty core extends the row's cached prefix by a shared
        per-round segment (two-level caching).  Returns None when any
        prefix cannot be cached (caller falls back to full-prompt
        prefill)."""
        # Entry feasibility uses the LARGEST row budget: the prefix is
        # shared, so it must leave suffix room for the row that reserves
        # the most decode slots — admitting a longer prefix would prefill
        # and cache an entry the limits_s guard below can never accept.
        limit = self.max_model_len - max(budgets) - 1
        # Seam safety decides per ROW whether its core is usable.
        rows = []
        seam_memo: Dict[Tuple[str, str], bool] = {}
        for p, c, t in parts:
            if c:
                ok = seam_memo.get((c, t))
                if ok is None:
                    ok = self._core_seam_safe(c, t)
                    seam_memo[(c, t)] = ok
                rows.append((p, c, t) if ok else (p, "", c + t))
            else:
                rows.append((p, "", t))
        # One bucket for the plain (no-core) entries: the smallest rung
        # covering the longest such prefix (uniform entry shapes — see
        # _get_prefix_entry).  Core entries carry their own bucket.
        plain_prefixes = list(dict.fromkeys(p for p, c, _ in rows if not c))
        P_rung = None
        if plain_prefixes:
            max_len = max(self._prefix_len(p) for p in plain_prefixes)
            if max_len == 0 or max_len > limit - 64:
                return None
            P_rung = next(
                (b for b in _PREFIX_BUCKETS if b >= max_len and b <= limit),
                # The smallest covering rung overshoots the limit even
                # though the prefix itself fits (checked above): clamp to
                # limit - 64 instead of silently abandoning the prefix
                # cache.  The 64-token slack keeps the limits_s guard
                # below satisfiable (P == limit would fail it AFTER
                # prefilling a dead limit-sized entry); max_len <=
                # limit - 64 is guaranteed above, so the prefix fits.
                # An off-ladder bucket costs one extra compile keyed by
                # the (phase-stable) budget — re-prefilling every system
                # prompt on every call costs far more.
                limit - 64,
            )
            # Clamp rungs sp-align by construction (ladder rungs already
            # divide): ring prefill shards the bucket's token dim, and an
            # odd clamp like limit-64=1683 would otherwise bypass sp for
            # every entry at that rung.  Align DOWN when the prefix still
            # fits; a prefix that only fits the unaligned clamp gets the
            # next sp multiple UP — < sp extra pad slots eating into the
            # 64-token slack, which the limits_s guard below still
            # polices.  Every reachable rung is therefore sp-divisible.
            if self._sp_devices > 1:
                aligned = P_rung - P_rung % self._sp_devices
                if max_len <= aligned:
                    P_rung = aligned
                elif P_rung % self._sp_devices:
                    P_rung += (-P_rung) % self._sp_devices
        entries: Dict[Tuple[str, str], Dict[str, Any]] = {}
        # _get_*_entry registers each resolved key in _prefix_active
        # (protecting the batch's working set from its own evictions),
        # including reused larger-bucket keys.
        self._prefix_active = set()
        try:
            for p, c, _ in rows:
                if (p, c) in entries:
                    continue
                e = (
                    self._get_core_entry(p, c, limit)
                    if c
                    else self._get_prefix_entry(p, limit, P_rung)
                )
                if e is None:
                    return None
                entries[(p, c)] = e
        finally:
            self._prefix_active = set()
        self._prune_prefix_memo()
        uniq = list(entries)
        max_new = max(budgets)
        # Entry buckets are heterogeneous (core entries, reused
        # larger-bucket entries) — the assembly pads every entry to the max.
        P = max(e["bucket"] for e in entries.values())
        limits_s = [self.max_model_len - b - 1 - P for b in budgets]
        if min(limits_s) < 1:
            return None

        tokens, valid, Ls = self._encode_leftpad(
            [t for _, _, t in rows], limits_s, self._suffix_buckets
        )
        B = len(rows)

        gid = np.array(
            [uniq.index((p, c)) for p, c, _ in rows], dtype=np.int32
        )
        tail = Ls + (decode_slots if decode_slots is not None else max_new + 1)
        # Align the total cache length (see _kv_align).
        tail += (-(P + tail)) % self._kv_align

        # One jitted call assembles the whole batch cache.  Done eagerly
        # this was ~6 ops x num_layers separate device executions per LLM
        # call, each paying its own dispatch latency.
        entry_kvs = tuple(entries[k]["kv"] for k in uniq)
        cache = self._assemble_cache(entry_kvs, jnp.asarray(gid), tail=tail)

        prefix_valid = np.zeros((B, P), dtype=bool)
        prefix_lens = np.zeros((B,), dtype=np.int32)
        prefix_toks = []
        for i, (p, c, _) in enumerate(rows):
            e = entries[(p, c)]
            prefix_valid[i, : e["bucket"]] = e["valid"]
            prefix_lens[i] = e["len"]
            prefix_toks.append(e["toks"])
        return (tokens, valid, Ls, cache, prefix_valid, prefix_lens,
                prefix_toks, P, P + tail)

    # ----------------------------------------------------------- paged assembly

    @staticmethod
    def _rightpad_tokens(
        token_lists, limits: List[int], bucket_ladder: Tuple[int, ...],
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """RIGHT-pad per-row token lists (already truncated to their row
        limits) into a bucketed [B, L] batch — the paged counterpart of
        :meth:`_encode_leftpad`: tokens left-ALIGNED so full real-token
        blocks are radix-insertable (see ``transformer.prefill_paged``).
        Same ladder semantics (doubling extension past the static tail,
        clamp to the largest row limit)."""
        max_len = max((len(t) for t in token_lists), default=0)
        max_limit = max(limits)
        buckets = list(bucket_ladder)
        while buckets[-1] < max_limit:
            buckets.append(buckets[-1] * 2)
        L = next((b for b in buckets if b >= max_len), max_limit)
        L = max(min(L, max_limit), max_len, 1)
        B = len(token_lists)
        tokens = np.zeros((B, L), dtype=np.int32)
        valid = np.zeros((B, L), dtype=bool)
        for i, toks in enumerate(token_lists):
            tokens[i, : len(toks)] = toks
            valid[i, : len(toks)] = True
        return tokens, valid, L

    def _paged_tokens(self, text: str) -> np.ndarray:
        """Tokenize (memoized — radix keys are token arrays, and every
        batch re-derives its entries)."""
        toks = self._paged_toks_memo.get(text)
        if toks is None:
            toks = np.asarray(self.tokenizer.encode(text), dtype=np.int32)
            self._paged_toks_memo[text] = toks
            if len(self._paged_toks_memo) > 512:
                # Same retention bound as the dense length memo: keyed
                # by multi-KB prompt strings, a long-lived process would
                # otherwise hold every prompt ever seen.
                self._paged_toks_memo = dict(
                    list(self._paged_toks_memo.items())[-256:]
                )
        return toks

    def _get_paged_entry(self, text: str, limit: int) -> Optional[Dict[str, Any]]:
        """Resolve a cachable prompt prefix against the radix index:
        longest full-block match, then ONE B=1 prefill of the unmatched
        remainder (up to the last full block boundary) into fresh blocks
        grafted onto the tree — the paged successor of
        :meth:`_get_prefix_entry`/:meth:`_get_core_entry`, with string
        keys replaced by token content (two different prefixes share
        exactly their common token-prefix blocks, and round ``r``'s
        grown history extends round ``r-1``'s chain).  The sub-block
        leftover (< block_size tokens) is returned for the caller's
        per-row suffix.  Returns None when the prefix cannot fit the
        prompt window (caller falls back to the uncached paged path)."""
        mgr = self._paged
        bs = mgr.block_size
        toks = self._paged_tokens(text)
        if toks.size == 0 or len(toks) > limit - 64:
            return None
        path, blocks = mgr.lookup(toks)
        mgr.pin(path)
        matched = len(blocks) * bs
        full_end = (len(toks) // bs) * bs
        if full_end > matched:
            Lr = full_end - matched
            # Bucket the build chunk for stable compile shapes; the pad
            # tail lands in scratch blocks freed at call end.
            Lr_pad = next((b for b in self._suffix_buckets if b >= Lr), Lr)
            Lr_pad = -(-Lr_pad // bs) * bs
            Pm_pad = 0
            if matched:
                Pm_rung = next(
                    (b for b in _PREFIX_BUCKETS if b >= matched), matched
                )
                Pm_pad = -(-Pm_rung // bs) * bs
            n_real = Lr // bs
            new_ids = mgr.alloc(Lr_pad // bs)
            # Provisional ownership: freed by the call's finally unless
            # the insert below grafts them into the radix tree.
            self._paged_call_private.extend(new_ids)
            tbl = np.zeros((1, Pm_pad // bs + Lr_pad // bs), dtype=np.int32)
            tbl[0, : len(blocks)] = blocks
            tbl[0, Pm_pad // bs:] = new_ids
            tokens = np.zeros((1, Lr_pad), dtype=np.int32)
            tokens[0, :Lr] = toks[matched:full_end]
            valid = np.zeros((1, Lr_pad), dtype=bool)
            valid[0, :Lr] = True
            pv = np.zeros((1, Pm_pad), dtype=bool)
            pv[0, :matched] = True
            cache = mgr.entries(tbl)
            self._paged_dirty = True
            # Long remainders chunk through the same driver as batch
            # prefills (prefill_chunk configured): an 8B-scale cold
            # prefix build must not regress to the O(L) activation
            # spike chunked prefill exists to cap.
            _, cache = self._prefill_paged_possibly_chunked(
                tokens, valid, Lr_pad, cache, pv,
                np.asarray([matched], np.int32),
            )
            mgr.adopt(cache)
            self._paged_dirty = False
            grafted = mgr.insert(path, toks, matched, new_ids[:n_real])
            kept = {node.block for node in grafted}
            # Everything not grafted is dead the moment insert returns —
            # the scratch pad tail AND any duplicate-content blocks.
            # Free them NOW rather than in the call's finally: holding
            # them would inflate peak pool demand past what cap_for
            # admission accounts for (B cold entries x ~bucket-padding
            # blocks), hard-failing admitted batches with PoolExhausted.
            dead = set(new_ids) - kept
            mgr.free(dead)
            self._paged_call_private = [
                i for i in self._paged_call_private
                if i not in kept and i not in dead
            ]
            path = path + grafted
            blocks = [node.block for node in path]
            # Entry builds run inside the caller's prefill window — same
            # accounting rationale as the dense entry builds.
            self.prefill_tokens += Lr_pad
            obs_counters.inc("engine.prefill.positions_padded", Lr_pad)
            obs_counters.inc("engine.prefill.positions_run", Lr_pad)
            obs_counters.inc("engine.prefill.positions_real", Lr)
        return {
            "blocks": blocks,
            "len": full_end,
            "toks": toks[:full_end],
            "leftover": toks[full_end:],
        }

    def _prepare_paged_batch(self, parts, budgets: List[int],
                             decode_slots: int):
        """Assemble a batch over the block pool: per-row block tables of
        radix-shared prefix blocks (padded with the null block to a
        bucketed prefix region) plus freshly allocated private blocks
        for the suffix window and decode tail.  Handles BOTH prompt
        paths — radix-cached prefixes when the batch qualifies (same
        safety conditions as the dense prefix cache), else the whole
        prompt as suffix over private blocks — so paged engines never
        fall back to dense slabs."""
        mgr = self._paged
        bs = mgr.block_size
        B = len(parts)
        limits = [self.max_model_len - b - 1 for b in budgets]
        if min(limits) < 1:
            raise BudgetError(
                f"max_tokens={max(budgets)} leaves no room for a prompt "
                f"within max_model_len={self.max_model_len}"
            )
        limit = self.max_model_len - max(budgets) - 1
        cacheable = (
            self.prefix_caching and self._prefix_safe
            and all(p for p, _, _ in parts)
        )
        rows = None
        entries: Optional[Dict[Tuple[str, str], Dict[str, Any]]] = None
        if cacheable:
            # Seam safety decides per ROW whether its core is usable —
            # identical policy to _prepare_prefixed_batch.
            rows = []
            seam_memo: Dict[Tuple[str, str], bool] = {}
            for p, c, t in parts:
                if c:
                    ok = seam_memo.get((c, t))
                    if ok is None:
                        ok = self._core_seam_safe(c, t)
                        seam_memo[(c, t)] = ok
                    rows.append((p, c, t) if ok else (p, "", c + t))
                else:
                    rows.append((p, "", t))
            entries = {}
            for p, c, _ in rows:
                if (p, c) in entries:
                    continue
                e = self._get_paged_entry(p + c, limit)
                if e is None:
                    entries = None
                    break
                entries[(p, c)] = e
            if entries is None:
                cacheable = False
                self.prefix_fallbacks += 1
                if not self._prefix_fallback_warned:
                    import warnings

                    warnings.warn(
                        "radix prefix sharing disengaged for this batch "
                        "(prefix too long for the prompt window) — the "
                        "whole prompt prefills into private blocks; "
                        "further fallbacks are counted in "
                        "engine.prefix_fallbacks",
                        stacklevel=2,
                    )
                    self._prefix_fallback_warned = True
        if cacheable:
            res = [entries[(p, c)] for p, c, _ in rows]
            suffix_toks = [
                list(e["leftover"]) + list(self._paged_tokens(t))
                for e, (_, _, t) in zip(res, rows)
            ]
            ladder = self._suffix_buckets
        else:
            res = [None] * B
            suffix_toks = [
                list(self._paged_tokens(p + c + t)) for p, c, t in parts
            ]
            ladder = _LEN_BUCKETS
        res_lens = [e["len"] if e else 0 for e in res]
        max_res = max(res_lens)
        P = 0
        if max_res:
            P_rung = next(
                (b for b in _PREFIX_BUCKETS if b >= max_res and b <= limit),
                # Clamp idiom (see _prepare_prefixed_batch): the entry
                # guard bounds max_res <= limit - 64, so the clamp fits.
                max(max_res, limit - 64),
            )
            P = -(-P_rung // bs) * bs
        limits_s = [l - P for l in limits]
        if min(limits_s) < 1:
            # A mixed-budget row cannot fit any suffix past the shared
            # prefix region: serve the batch uncached instead (the
            # dense path's None-return, without abandoning paging).
            # Counted + warned like every other prefix disengagement —
            # a deployment hitting this on every batch loses the
            # sharing win N-fold and must not look cache-healthy.
            self.prefix_fallbacks += 1
            if not self._prefix_fallback_warned:
                import warnings

                warnings.warn(
                    "radix prefix sharing disengaged for this batch (a "
                    "row's token budget leaves no suffix room past the "
                    "shared prefix region) — the whole prompt prefills "
                    "into private blocks; further fallbacks are counted "
                    "in engine.prefix_fallbacks",
                    stacklevel=2,
                )
                self._prefix_fallback_warned = True
            for i in range(B):
                res[i] = None
                res_lens[i] = 0
            suffix_toks = [
                list(self._paged_tokens(p + c + t)) for p, c, t in parts
            ]
            ladder = _LEN_BUCKETS
            P = 0
            limits_s = limits
            cacheable = False
        suffix_toks = [
            t[-lim:] for t, lim in zip(suffix_toks, limits_s)
        ]
        tokens, valid, Ls = self._rightpad_tokens(suffix_toks, limits_s, ladder)
        S = P + Ls + decode_slots
        S += (-S) % bs
        nblk = S // bs
        n_priv = (S - P) // bs
        priv = mgr.alloc(B * n_priv)
        self._paged_call_private.extend(priv)
        tbl = np.zeros((B, nblk), dtype=np.int32)
        prefix_valid = np.zeros((B, P), dtype=bool)
        prefix_lens = np.zeros((B,), dtype=np.int32)
        prefix_toks = []
        for i in range(B):
            e = res[i]
            if e is not None:
                tbl[i, : len(e["blocks"])] = e["blocks"]
                prefix_valid[i, : e["len"]] = True
                prefix_lens[i] = e["len"]
                prefix_toks.append(e["toks"])
            else:
                prefix_toks.append(np.zeros(0, dtype=np.int32))
            tbl[i, P // bs:] = priv[i * n_priv:(i + 1) * n_priv]
        cache = mgr.entries(tbl)
        return (tokens, valid, Ls, cache, prefix_valid, prefix_lens,
                prefix_toks, P, S, tbl)

    # ------------------------------------------------------------ decode loop

    def _make_masked_sampler(self, eos_id: int, top_p: float,
                             impl: Optional[str] = None):
        """The guided sampler shared VERBATIM by the standard,
        fast-forward, AND speculative decode loops (the equivalence
        guarantees between them depend on a single implementation — the
        XLA reference lives in :mod:`bcg_tpu.engine.speculative`, whose
        verify pass also reuses its filter stage).  ONE resolution for
        all three families, like :meth:`_resolved_loop_impl` for the
        attention kernel: ``impl`` None reads the engine's resolved
        ``_sampler_loop_impl``; the census's TPU cross-lowering twins
        (:meth:`_maybe_record_sampler_tpu_lowering`) pass it explicitly
        to build both variants of the same loop."""
        impl = self._sampler_loop_impl if impl is None else impl
        if impl in (_GS_PALLAS, _GS_PALLAS_INTERPRET):
            from bcg_tpu.ops.guided_sampler import make_fused_sampler

            return make_fused_sampler(
                eos_id, top_p, interpret=(impl == _GS_PALLAS_INTERPRET),
                mesh=self._kernel_mesh,
            )
        return _make_masked_sampler_impl(eos_id, top_p)

    def _note_jit_shape(self, entry: str, sig: Tuple,
                        names: Optional[Tuple[str, ...]] = None,
                        timing: str = "pending") -> None:
        """Count a compile (and, beyond the first signature per entry
        point, a RETRACE) into the process-wide counter registry:
        ``engine.compile.<entry>`` / ``engine.retrace.<entry>``.  Keyed
        by (entry point, shape signature), incremented exactly once per
        NEW signature — steady-state serving must show zero retrace
        movement, and a test provoking one extra shape observes exactly
        +1 (tests/test_obs.py).

        The per-entry cache is an insertion-ordered dict, not a set:
        when compile observability is on (``BCG_TPU_COMPILE_OBS``,
        obs/compile.py), a retraced signature is diffed against the
        NEAREST cached one — most recent on ties — to emit the
        structured retrace-cause record, with ``names`` labelling the
        signature positions (``max_new 32→48``, not ``arg1``)."""
        seen = self._jit_shapes.setdefault(entry, {})
        if sig in seen:
            return
        first = not seen
        prior = list(seen)
        seen[sig] = True
        obs_counters.inc(f"engine.compile.{entry}")
        if not first:
            obs_counters.inc(f"engine.retrace.{entry}")
        # ``timing`` declares this seam's note/dispatch ordering for the
        # compile-time handoff: the decode-loop builders note BEFORE the
        # first invocation (default "pending"), the prefill site notes
        # AFTER its timed dispatch ("stash") — see obs/compile.py.
        obs_compile.note_signature(entry, sig, prior, names=names,
                                   timing=timing)

    def _get_decode_loop(self, guided_sig: Tuple, max_new: int,
                         top_p: float = 1.0):
        """Build (or fetch) the compiled guided decode loop for a shape
        signature.  The whole token loop is one ``lax.while_loop`` on
        device; ``io_callback``-free and host-sync-free.

        Temperature and token budget are PER-ROW dynamic inputs, not
        compile keys: one compiled loop serves greedy and sampled rows,
        decide- and vote-budget rows, in the same batch — which is what
        lets desynchronized games merge under the collective engine."""
        # Sequence-parallel decode: keep the cache sharded over sp inside
        # the loop and merge per-slice attention partials with pmax/psum
        # (transformer.decode_step ring= -> sp_decode_attention).  An
        # int8 cache dequantizes only its local S/sp slice in there.
        ring = (self.mesh, "sp") if self._sp_devices > 1 else None
        impl = self._resolved_loop_impl()
        key = (guided_sig, int(max_new), float(top_p), impl,
               self._sampler_loop_impl)
        if key in self._decode_loops:
            return self._decode_loops[key]
        self._note_jit_shape(
            "decode_loop", key,
            names=("guided_sig", "max_new", "top_p", "attn_impl",
                   "sampler_impl"),
        )
        self._decode_ring_active = ring is not None
        compiled = self._build_decode_loop(impl, max_new, top_p, ring)
        self._decode_loops[key] = compiled
        return compiled

    def _kernel_impl(self, impl: str):
        """The marker the transformer receives for a resolved impl name:
        "pallas" under a mesh becomes :class:`bcg_tpu.ops.PallasTP` (the
        kernels shard_map'd over tp); everything else passes as is."""
        if impl == "pallas" and self._kernel_mesh is not None:
            return PallasTP(self._kernel_mesh)
        return impl

    def _resolved_loop_impl(self, chunk: bool = False):
        """Attention impl marker a decode loop passes through the
        transformer's ``impl`` parameter — ONE resolution for all three
        loop families, so a change to the selection logic can never give
        the plain/ff/spec loops different kernels for the same config.
        Paged engines pass the resolved paged marker (the "tbl" dispatch
        in ``_cache_attention``/``_block_chunk`` reads it; dense impls
        never see a paged entry and vice versa).  Dense chunk windows
        (``chunk=True``: the ff and spec K+1 verify forms) run the
        Pallas chunk kernel only for int8 caches — for bf16, flash would
        pad the K chunk rows to a 128-row query block, so stock XLA
        attention wins."""
        if self._paged is not None:
            return self._paged_loop_impl
        if not chunk:
            return self._kernel_impl(self.decode_attention_impl)
        return self._kernel_impl(
            "pallas"
            if self.kv_quantized and self.decode_attention_impl == "pallas"
            else "xla"
        )

    def _build_decode_loop(self, impl: str, max_new: int, top_p: float,
                           ring=None, sampler_impl: Optional[str] = None):
        """The standard decode loop as an (unmemoized) jitted callable
        with an EXPLICIT attention impl (and, for the sampler census
        twins, an explicit SAMPLER impl) — :meth:`_get_decode_loop` is
        the memoized resolver; the census's TPU cross-lowering twins
        (:meth:`_maybe_record_paged_tpu_lowering` /
        :meth:`_maybe_record_sampler_tpu_lowering`) build both variants
        of the same program without touching the executed loops' cache
        or compile counters."""
        spec = self.spec
        eos_id = self.tokenizer.eos_id
        sampler = self._make_masked_sampler(eos_id, top_p, impl=sampler_impl)
        # A spec with routed experts counts, in the carry, the held
        # experts each step's rows hit (summed over layers and steps):
        # read back with the steps, so no further sync.
        tally = spec.moe

        @jax.named_scope(obs_scopes.SAMPLE)
        def loop(params, cache, first_logits, valid_mask, prompt_lens, L,
                 tables, accepting, min_budget, dfa_ids, init_states,
                 row_temp, row_budget, rng):
            B = first_logits.shape[0]

            def masked_sample(logits, states, rng, pos):
                return sampler(
                    logits, states, rng, pos, tables, accepting,
                    min_budget, dfa_ids, row_temp, row_budget,
                )

            def cond(carry):
                # Position max_new-1 is the last output slot, written by
                # iteration max_new-2 — no trailing forward pass whose
                # sample would only be discarded.
                i, done, *_ = carry
                return (i < max_new - 1) & ~done.all()

            def body(carry):
                i, done, cur_tok, states, cache, valid_mask, out, rng, *hits = carry
                # Open cache slot L+i, run the step, sample token i+1.
                valid_mask = jax.lax.dynamic_update_slice(
                    valid_mask, jnp.ones((B, 1), bool), (0, L + i)
                )
                logits, cache, *hit = decode_step(
                    params, spec,
                    jnp.where(done, eos_id, cur_tok),
                    L + i, prompt_lens + i, cache, valid_mask, impl,
                    ring=ring, tally=tally,
                )
                tok, states, rng = masked_sample(logits, states, rng, i + 1)
                tok = jnp.where(done, eos_id, tok)
                out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i + 1))
                done = done | (tok == eos_id)
                cur_tok = jnp.where(done, cur_tok, tok)
                return (i + 1, done, cur_tok, states, cache, valid_mask, out, rng,
                        *[n + m for n, m in zip(hits, hit)])

            tok0, states0, rng = masked_sample(first_logits, init_states, rng, 0)
            out = jnp.full((B, max_new), eos_id, dtype=jnp.int32)
            out = out.at[:, 0].set(tok0)
            carry = (jnp.int32(0), tok0 == eos_id, tok0, states0,
                     cache, valid_mask, out, rng, *([jnp.int32(0)] if tally else []))
            i, done, cur_tok, states, cache, valid_mask, out, rng, *hits = jax.lax.while_loop(
                cond, body, carry
            )
            # Early-exit rows are already EOS-filled (out initialized to
            # EOS); budget-limited rows end in a forced completion whose
            # last token occupies slot max_new-1 (vLLM max_tokens
            # semantics).  The cache is RETURNED so the donated input can
            # alias the loop carry — without a matching output the
            # donation is unusable and the program holds TWO full caches
            # (measured: pushed an 8B compile 8 GB past HBM capacity).
            return out, (rng, i, *hits), cache

        return jax.jit(loop, static_argnames=("L",), donate_argnums=(1,))

    def _maybe_record_paged_tpu_lowering(self, max_new: int, top_p: float,
                                         args: tuple) -> None:
        """Census-only (BCG_TPU_HLO_CENSUS): pin the TPU CROSS-LOWERING
        of the paged decode loop under both impls — the XLA block-gather
        and the fused Pallas kernel — from this call's concrete
        arguments, WITHOUT executing either (trace + lower only, so the
        non-interpret kernel records its real Mosaic ``tpu_custom_call``
        lowering even on a CPU host; see obs/hlo.py's stablehlo-census
        note).  These two entries carry the acceptance inequality: the
        fused loop's step ops strictly below the gather loop's, the
        per-layer attention gather/dot chains replaced by exactly one
        ``tpu_custom_call`` per layer (tests/test_hlo_census.py;
        hlo_baseline.json drift-gates both directions — the remaining
        step gathers are the write-path table lookups and the embedding
        gather, identical in both arms).  Must run BEFORE the real loop
        call — tracing reads the donated pool buffers, execution
        consumes them."""
        from bcg_tpu.ops.paged_attention import PALLAS

        for entry, impl in (("tpu_paged_decode_loop", "xla"),
                            ("tpu_paged_pallas_decode_loop", PALLAS)):
            if obs_hlo.recorded(entry):
                continue
            obs_hlo.record_tpu_lowering(
                entry, self._build_decode_loop(impl, max_new, top_p), args,
            )

    def _maybe_record_sampler_tpu_lowering(self, family: str, builder,
                                           args: tuple) -> None:
        """Census-only (BCG_TPU_HLO_CENSUS): pin the TPU CROSS-LOWERING
        of one DENSE decode-loop family under both sampler impls — the
        XLA masked sampler and the fused Pallas kernel — from this
        call's concrete arguments, without executing either (trace +
        lower only; Mosaic serializes the kernel to ``tpu_custom_call``
        at lowering time, no hardware needed).  These entry pairs carry
        the fused-sampler acceptance inequality: per-decode-step op
        count strictly DOWN under ``fused_sampler=pallas`` for ALL
        THREE families — the [B, V] mask/filter/draw chain collapses
        into one step custom call (plus the paged twins' embedding/
        write-path gathers, identical in both arms) — drift-gated both
        directions in hlo_baseline.json.  ``builder(sampler_impl)``
        returns the family's jitted loop; must run BEFORE the real loop
        call (tracing reads the donated cache buffers, execution
        consumes them)."""
        for entry, impl in ((f"tpu_{family}", "xla"),
                            (f"tpu_fused_{family}", _GS_PALLAS)):
            if obs_hlo.recorded(entry):
                continue
            obs_hlo.record_tpu_lowering(entry, builder(impl), args)

    def _get_ff_decode_loop(self, guided_sig: Tuple, max_new: int,
                            top_p: float = 1.0):
        """Fast-forward decode loop: every iteration samples ONE token and
        rides its DFA-forced continuation (up to FF_CHUNK-1 skeleton
        tokens) through the same weight pass (models/transformer.py
        decode_chunk).  The cache write position advances by 1 + the
        iteration's WIDEST row chain (compacted; per-row gaps inside the
        window are masked out of attention); RoPE positions stay
        contiguous per row.  Greedy outputs are bit-identical to the
        standard loop; the win is weight-streaming passes ~ sampled
        tokens, not total tokens — and a cache only ~1.5x the token
        budget for the KV-bandwidth-bound attention to stream.
        """
        chunk_impl = self._resolved_loop_impl(chunk=True)
        # Sequence-parallel chunk decode: the cache stays sp-sharded
        # inside the ff loop too (sp_chunk_decode_attention); an int8
        # cache dequantizes only its local S/sp slice in there.
        ring = (self.mesh, "sp") if self._sp_devices > 1 else None
        key = ("ff", guided_sig, int(max_new), float(top_p), chunk_impl,
               self._sampler_loop_impl)
        if key in self._decode_loops:
            return self._decode_loops[key]
        self._note_jit_shape(
            "ff_decode_loop", key,
            names=("path", "guided_sig", "max_new", "top_p", "attn_impl",
                   "sampler_impl"),
        )
        self._decode_ring_active = ring is not None
        compiled = self._build_ff_decode_loop(chunk_impl, max_new, top_p, ring)
        self._decode_loops[key] = compiled
        return compiled

    def _build_ff_decode_loop(self, chunk_impl: str, max_new: int,
                              top_p: float, ring=None,
                              sampler_impl: Optional[str] = None):
        """The fast-forward loop as an (unmemoized) jitted callable —
        split from :meth:`_get_ff_decode_loop` for the same reason the
        plain loop's builder is: the sampler census twins build both
        sampler variants of the identical program."""
        from bcg_tpu.guided.processor import FF_CHUNK as K

        spec = self.spec
        eos_id = self.tokenizer.eos_id
        sampler = self._make_masked_sampler(eos_id, top_p, impl=sampler_impl)

        @jax.named_scope(obs_scopes.SAMPLE)
        def loop(params, cache, first_logits, valid_mask, prompt_lens, L,
                 tables, accepting, min_budget, dfa_ids, init_states,
                 chain_tok, chain_len, chain_next,
                 row_temp, row_budget, rng):
            B = first_logits.shape[0]

            def masked_sample(logits, states, rng, emitted):
                return sampler(
                    logits, states, rng, emitted, tables, accepting,
                    min_budget, dfa_ids, row_temp, row_budget,
                )

            def cond(carry):
                i, _wp, done, *_ = carry
                return (i < max_new) & ~done.all()

            tail_slots = _ff_decode_slots(max_new)

            def body(carry):
                (i, wp, done, emitted, states, logits, cache, valid_mask,
                 out, rng) = carry
                tok, ns, rng = masked_sample(logits, states, rng, emitted)
                tok = jnp.where(done, eos_id, tok)
                finished = tok == eos_id
                clamped_ns = jnp.maximum(ns, 0)
                # Forced continuation of the sampled token (none for EOS
                # or already-done rows).  Cache capacity guard: chains are
                # disabled once the compacted write position could no
                # longer fit the worst-case remainder (each later
                # iteration advancing 1 slot, every write needing a K
                # window).  Output is unchanged when it triggers — a
                # forced state has exactly one legal token, so the sampler
                # emits the chain one token per iteration instead.
                room_ok = (wp - L) <= tail_slots - 2 * K - (max_new - i - 1)
                cl = jnp.where(
                    done | finished | ~room_ok, 0,
                    chain_len[dfa_ids, clamped_ns],
                )
                ct = chain_tok[dfa_ids, clamped_ns]        # [B, K-1]
                chunk = jnp.concatenate([tok[:, None], ct], axis=1)  # [B, K]
                j = jnp.arange(K)[None, :]
                chunk_valid = (j == 0) | (j - 1 < cl[:, None])
                # Write real tokens into out at per-row offsets (invalid
                # and already-done positions -> dropped via OOB index).
                write_idx = jnp.where(
                    chunk_valid & ~done[:, None],
                    emitted[:, None] + j, max_new,
                )
                out = out.at[
                    jnp.arange(B)[:, None], write_idx
                ].set(chunk, mode="drop")
                positions = (prompt_lens + emitted)[:, None] + j
                logits, cache = decode_chunk(
                    params, spec, chunk, chunk_valid, wp, positions,
                    cache, valid_mask, impl=chunk_impl, ring=ring,
                )
                valid_mask = jax.lax.dynamic_update_slice(
                    valid_mask, chunk_valid, (0, wp)
                )
                emitted = jnp.where(done, emitted, emitted + 1 + cl)
                # Compacted advance: the next window starts right after
                # this iteration's widest row, not K slots later — rows
                # with shorter chains leave gaps only inside the window,
                # and the decode attention streams ~emitted slots instead
                # of K * iterations (decode is KV-bandwidth-bound, so
                # cache compaction is decode wall-clock).  Overlapped
                # slots from the previous window were invalid and are
                # simply overwritten.
                wp = wp + 1 + jnp.max(jnp.where(done, 0, cl))
                next_states = jnp.where(
                    room_ok, chain_next[dfa_ids, clamped_ns], clamped_ns
                )
                states = jnp.where(done, states, next_states)
                states = jnp.where(finished, -1, states)
                done = done | finished
                return (i + 1, wp, done, emitted, states, logits, cache,
                        valid_mask, out, rng)

            out = jnp.full((B, max_new), eos_id, dtype=jnp.int32)
            carry = (jnp.int32(0), jnp.int32(L), jnp.zeros((B,), bool),
                     jnp.zeros((B,), jnp.int32), init_states.astype(jnp.int32),
                     first_logits, cache, valid_mask, out, rng)
            (i, wp, done, emitted, states, logits, cache, valid_mask, out,
             rng) = jax.lax.while_loop(cond, body, carry)
            # Returned for donation aliasing — see the standard loop.
            return out, (rng, i), cache

        return jax.jit(loop, static_argnames=("L",), donate_argnums=(1,))

    def _get_spec_decode_loop(self, guided_sig: Tuple, max_new: int,
                              top_p: float = 1.0):
        """Speculative decode loop (engine/speculative.py): every
        iteration samples ONE token, drafts up to ``spec_k`` more by
        prompt-lookup (n-gram match against the row's token history,
        forced chains as fallback), and verifies the whole draft in one
        K+1-position forward pass with PER-ROW compacted cache writes.
        Greedy outputs are token-identical to the standard loop; the
        win is weight-streaming passes ~ verify passes, not tokens.
        Per-row acceptance counts live in the while-loop CARRY, never in
        a shape — steady-state speculative decode is retrace-free."""
        chunk_impl = self._resolved_loop_impl(chunk=True)
        ring = (self.mesh, "sp") if self._sp_devices > 1 else None
        key = ("spec", guided_sig, int(max_new), float(top_p),
               self.spec_k, self.spec_ngram, chunk_impl,
               self._sampler_loop_impl)
        if key in self._decode_loops:
            return self._decode_loops[key]
        self._note_jit_shape(
            "spec_decode_loop", key,
            names=("path", "guided_sig", "max_new", "top_p", "spec_k",
                   "spec_ngram", "attn_impl", "sampler_impl"),
        )
        self._decode_ring_active = ring is not None
        compiled = self._build_spec_decode_loop(chunk_impl, max_new, top_p,
                                                ring)
        self._decode_loops[key] = compiled
        return compiled

    def _build_spec_decode_loop(self, chunk_impl: str, max_new: int,
                                top_p: float, ring=None,
                                sampler_impl: Optional[str] = None):
        """The speculative loop as an (unmemoized) jitted callable — the
        per-iteration sampler is the engine-resolved (or census-twin)
        impl; the verify pass's filter stage stays the XLA form inside
        ``build_spec_loop`` (see its docstring)."""
        eos_id = self.tokenizer.eos_id
        loop = build_spec_loop(
            self.spec, chunk_impl, ring, eos_id, top_p,
            int(max_new), self.spec_k, self.spec_ngram,
            sampler=self._make_masked_sampler(eos_id, top_p,
                                              impl=sampler_impl),
        )
        return jax.jit(loop, static_argnames=("L",), donate_argnums=(1,))

    def _run_guided(
        self,
        parts: List[Tuple[str, str]],
        schemas: List[Dict],
        temperature,
        max_tokens,
        top_p: float = 1.0,
    ) -> List[str]:
        """``temperature`` / ``max_tokens`` may be scalars or per-row lists
        (the collective engine merges calls with different sampling
        settings into one batch)."""
        n = len(parts)
        temps = _per_row(temperature, n, float)
        budgets = _per_row(max_tokens, n, int)
        # max_num_seqs (vLLM semantics, reference config.py:38) bounds the
        # concurrently decoded rows by chunking oversized batches; the
        # hbm_utilization provisioner derives a second cap from actual
        # device memory (min of the two wins).  Off by default on TPU —
        # see EngineConfig.
        cap = self.config.max_num_seqs
        with obs_tracer.span("engine.provision"):
            derived = self._provisioned_row_cap(parts, budgets)
        if derived is not None:
            cap = min(cap, derived) if cap else derived
        mult = self._dp_mult(cap)
        if cap and _aligned_pad_batch(n, mult) > cap:
            if derived is not None and derived <= cap:
                self.provision_chunk_events += 1
            step = _chunk_size(cap, mult)
            out: List[str] = []
            for i in range(0, n, step):
                out.extend(self._run_guided(
                    parts[i:i + step], schemas[i:i + step],
                    temps[i:i + step], budgets[i:i + step], top_p,
                ))
            return out
        real_B, B, parts, schemas, temps, budgets = _pad_rows(
            parts, schemas, temps, budgets, multiple=mult
        )
        with obs_tracer.span("engine.guides", args={"rows": B}):
            built = obs_counters.value("engine.guides.built")
            guides = [
                compile_schema(
                    s, self._token_bytes, vocab_id=self.tokenizer.vocab_id,
                    compact=getattr(self.config, "guided_compact_json", False),
                )
                for s in schemas
            ]
            batch = GuidedBatch(guides)
            obs_tracer.annotate(
                schemas=batch.num_unique,
                built=obs_counters.value("engine.guides.built") - built,
            )
        sig = (batch.num_unique, batch.tables.shape[1], batch.tables.shape[2])
        return self._decode_batch(
            parts, batch, sig, real_B, temps, budgets, top_p
        )

    def _note_sp_bypass(self, reason: str) -> None:
        """Count (and warn once about) a call that fell back from a
        configured sequence-parallel path.  Only reachable for
        off-ladder shapes (every rung ladder value divides sp); ladder
        shapes are asserted bypass-free in tests and the dryrun."""
        self.sp_bypasses += 1
        if not self._sp_bypass_warned:
            import warnings

            warnings.warn(
                f"sequence-parallel path bypassed: {reason}; further "
                "bypasses are counted in engine.sp_bypasses",
                stacklevel=3,
            )
            self._sp_bypass_warned = True

    def _note_dp_bypass(self, reason: str) -> None:
        """Count (and warn once about) a batch that fell back from the
        configured data-parallel sharding.  Reachable when the row cap
        is tighter than dp (_dp_mult returns 1 and the batch runs
        replicated) — a config conflict, not a sharding regression;
        loud for the same reason as _note_sp_bypass: silent
        disengagement of a configured optimization once hid a disabled
        cache for a whole round."""
        self.dp_bypasses += 1
        if not self._dp_bypass_warned:
            import warnings

            warnings.warn(
                f"data-parallel batch sharding bypassed: {reason}; further "
                "bypasses are counted in engine.dp_bypasses",
                stacklevel=3,
            )
            self._dp_bypass_warned = True

    def _dp_mult(self, cap) -> int:
        """dp batch-padding multiple compatible with a row cap: when the
        cap is tighter than dp itself, dp cannot engage for this call
        (the batch runs replicated; _decode_batch counts the bypass)."""
        return self._dp_devices if not cap or self._dp_devices <= cap else 1

    def _put_batch(self, x):
        """Device-place a batch-major array sharded over the mesh's `dp`
        axis (replicated over tp/sp — those partition weights and the
        sequence dim).  Host numpy arrays transfer directly shard-wise
        (each device receives only its slice — no full copy staged on
        one device first).  Falls back to plain placement when dp is off
        or the axis doesn't divide (single-row prefix-entry builds)."""
        if (
            self._dp_devices > 1
            and x.shape[0] % self._dp_devices == 0
        ):
            from bcg_tpu.parallel.sharding import batch_sharding

            return jax.device_put(x, batch_sharding(self.mesh))
        return jnp.asarray(x)

    def _init_cache_sharded(self, B: int, S: int):
        """Allocate a fresh decode cache ALREADY sharded over the mesh
        (dp on batch, sp on sequence, tp on kv-heads where divisible —
        parallel/sharding.py::kv_cache_tree_sharding, the same layout
        the memory guards' divide-by-mesh-size arithmetic assumes).
        Jitted zero-init with out_shardings: no device ever materializes
        more than its shard, where init-then-reshard would stage the
        FULL unsharded cache on one device first — a transient dp× spike
        on exactly the large-batch configs dp exists to fit."""
        kw = dict(quantized=self.kv_quantized, stacked=self.scan_layers)
        by_kind = self._cache_bytes(B, S)
        obs_counters.inc("engine.cache.kv_bytes", by_kind["kv"])
        if by_kind["linear_state"]:
            obs_counters.inc(
                "engine.cache.linear_state_bytes", by_kind["linear_state"])
            obs_counters.inc(
                "engine.linear.state_rows", B * self.spec.recurrent_layers)
        if self.mesh is None or self._mesh_devices <= 1:
            return init_kv_cache(self.spec, B, S, **kw)
        key = (B, S)
        mk = self._cache_init_jits.get(key)
        if mk is None:
            from bcg_tpu.parallel.sharding import kv_cache_tree_sharding

            init = partial(init_kv_cache, self.spec, B, S, **kw)
            outs = kv_cache_tree_sharding(
                self.mesh, jax.eval_shape(init), **kw
            )
            mk = jax.jit(init, out_shardings=outs)
            self._cache_init_jits[key] = mk
        return mk()

    def _prefill_chunk_starts(self, valid, L: int) -> range:
        """Window offsets of the programs a dense prefill of the
        LEFT-padded ``valid`` [B, L] sends: offset 0 alone for a
        single pass, else from the first chunk in which ANY row holds a
        token (chunks dead in every row leave; the last always runs, so
        a batch with no token at all still yields its logits)."""
        C = self.prefill_chunk
        if not C or L <= C:
            return range(1)
        live = np.flatnonzero(valid.any(axis=0))
        first = int(live[0]) if live.size else L - 1
        return range(first // C * C, L, C)

    def _prefill_possibly_chunked(self, tokens, valid, L: int, cache,
                                  prefix_valid=None, prefix_lens=None):
        """Prefill ``tokens`` (optionally against an existing cached
        prefix occupying slots ``[0, P)``) in ``prefill_chunk``-sized
        slices when configured (0 = single pass).

        Chunked prefill caps activation memory at O(B * chunk) instead of
        O(B * L): a [10, 4096]-token batch through an 8B model needs
        several 640 MB f32 rope/attention temps, which is exactly what a
        weights+cache-full 16 GB chip does not have.  Chunk k writes
        slots ``[P + kC, P + kC + C)`` and attends the cached KV of
        everything before it plus itself, through
        ``transformer.prefill_chunk_at``: a FIXED ``[B, P + L - C]``
        history mask and a traced write slot, so every full-width chunk
        is ONE compiled program whatever its offset (a ragged tail adds
        one more shape).

        The window is LEFT-padded, so its leading chunks may hold no
        token in any row; the loop starts at the first chunk that does
        (:meth:`_prefill_chunk_starts`).  The slots it passes over keep
        what the cache was made with (zeros, unit scales) and stay
        masked for every later chunk and for decode, exactly as the KV
        of pad tokens was, so the live chunks' logits and KV are those
        of a loop over all chunks.
        Applies on BOTH prompt paths — full-prompt and prefix-cached
        suffix (the suffix region's chunks extend the prefix).
        """
        C = self.prefill_chunk
        has_prefix = prefix_valid is not None
        P = prefix_valid.shape[1] if has_prefix else 0
        B = tokens.shape[0]
        starts = self._prefill_chunk_starts(valid, L)
        # Counted here, not beside positions_padded in the callers: only
        # this loop knows what it passed over.
        obs_counters.inc(
            "engine.prefill.positions_run", B * (L - starts.start)
        )
        if self.spec.hybrid:
            # rows x positions x linear layers through the delta-rule
            # prefill (the chunkwise kernel, or its XLA twin)
            obs_counters.inc(
                "engine.linear.prefill_positions",
                B * (L - starts.start) * self.spec.recurrent_layers,
            )
        if not C or L <= C:
            if has_prefix:
                from bcg_tpu.models.transformer import _cache_len

                if (self._prefill_sp is not None
                        and _cache_len(cache) % self._sp_devices == 0):
                    # The suffix is ONE chunk against the cached prefix:
                    # prefill_chunk_at's ring branch writes it into the
                    # sp-sharded cache and attends the whole cache
                    # (prefix slots + its own causal window) — same
                    # semantics as prefill_with_prefix (identical RoPE
                    # offsets and mask), sharded instead of replicated.
                    return obs_hlo.wrap("prefill_chunk", self._prefill_chunk_at)(
                        self.params, tokens=self._put_batch(tokens),
                        valid=self._put_batch(valid), cache=cache,
                        hist_valid=self._put_batch(prefix_valid),
                        pos_offset=self._put_batch(
                            np.asarray(prefix_lens, np.int32)
                        ),
                        write_pos=jnp.int32(P),
                    )
                if self._prefill_sp is not None:
                    self._note_sp_bypass(
                        f"prefixed cache length {_cache_len(cache)} not "
                        f"divisible by sp={self._sp_devices} "
                        "(off-ladder clamp shape)"
                    )
                return obs_hlo.wrap("prefill_suffix", self._prefill_suffix)(
                    self.params, tokens=self._put_batch(tokens),
                    valid=self._put_batch(valid), cache=cache,
                    prefix_valid=self._put_batch(prefix_valid),
                    prefix_lens=self._put_batch(prefix_lens),
                )
            if self._prefill_sp is not None:
                if L % self._sp_devices == 0:
                    return obs_hlo.wrap("prefill_sp", self._prefill_sp)(
                        self.params, tokens=self._put_batch(tokens),
                        valid=self._put_batch(valid), cache=cache,
                    )
                # Batch windows are sp-aligned by _encode_leftpad;
                # reaching here means an off-ladder ENTRY bucket (a
                # clamp rung whose prefix only fits unaligned) — serve
                # replicated, counted + warned (no-silent-disengagement).
                self._note_sp_bypass(
                    f"prompt window L={L} not divisible by "
                    f"sp={self._sp_devices} (off-ladder entry bucket)"
                )
            return obs_hlo.wrap("prefill", self._prefill)(
                self.params, tokens=self._put_batch(tokens),
                valid=self._put_batch(valid), cache=cache,
            )
        # Chunked prefill under sp is ring-capable (the chunk jit carries
        # ring=): no bypass to note here.
        base_lens = (
            np.asarray(prefix_lens, dtype=np.int64)
            if has_prefix
            else np.zeros(B, np.int64)
        )
        first_logits = None
        # The flash kernel's grid steps, and those its skip guard lets
        # compute, over rows, KV heads and attention layers (ring chunks
        # attend through the sp decode kernels instead).
        flash = self.attention_impl == "pallas" and self._sp_devices <= 1
        if flash:
            from bcg_tpu.ops.attention import chunk_steps
        for start in starts:
            Ct = min(C, L - start)
            H = P + L - Ct
            hist = np.zeros((B, H), dtype=bool)
            if has_prefix:
                hist[:, :P] = prefix_valid
            hist[:, P:P + start] = valid[:, :start]
            pos_off = base_lens + valid[:, :start].sum(axis=1)
            if flash:
                live, grid = chunk_steps(
                    hist, valid[:, start:start + Ct],
                    self.spec.num_heads // self.spec.num_kv_heads,
                    self.spec.head_dim)
                heads = self.spec.num_kv_heads * self._kv_layers
                obs_counters.inc("engine.prefill.attn_steps_live", live * heads)
                obs_counters.inc("engine.prefill.attn_steps_grid", grid * heads)
            first_logits, cache, *count = obs_hlo.wrap(
                "prefill_chunk", self._prefill_chunk_at
            )(
                self.params,
                tokens=self._put_batch(tokens[:, start:start + Ct]),
                valid=self._put_batch(valid[:, start:start + Ct]),
                cache=cache,
                hist_valid=self._put_batch(hist),
                pos_offset=self._put_batch(pos_off.astype(np.int32)),
                write_pos=jnp.int32(P + start),
            )
            self._moe_prefill_counts += count
        if self.spec.moe:
            obs_counters.inc("engine.moe.prefill.tokens",
                             int(valid.sum()) * self.spec.num_layers)
        return first_logits, cache

    def _prefill_paged_possibly_chunked(self, tokens, valid, Ls: int, cache,
                                        prefix_valid, prefix_lens):
        """Paged prefill — single-pass, or ``prefill_chunk``-sized slices
        streamed through the block pool when configured and the window
        exceeds the chunk.  The paged sibling of
        :meth:`_prefill_possibly_chunked`, closing the former
        ``paged + prefill_chunk`` boot exclusion: long prompts no longer
        force an O(B * L) activation pass to use paging.

        Chunk ``k`` writes logical slots ``[P + kC, P + kC + C)`` through
        each row's block table and attends the radix prefix plus every
        earlier chunk via a FIXED ``[B, H]`` history mask + traced write
        position (transformer.prefill_paged_chunk_at), so all full-width
        chunks share ONE compiled program per (B, C, H) — same
        zero-steady-state-retrace contract as the dense chunk path.
        Because chunks are RIGHT-padded, per-row last-valid logits thread
        through a carry instead of reading the final physical position.
        Serves batch prefills AND the radix entry builds (B=1 remainder
        prefills route here too)."""
        C = self.prefill_chunk
        if not C or Ls <= C:
            return obs_hlo.wrap("prefill_paged", self._prefill_paged)(
                self.params, tokens=self._put_batch(np.asarray(tokens)),
                valid=self._put_batch(np.asarray(valid)), cache=cache,
                prefix_valid=self._put_batch(np.asarray(prefix_valid)),
                prefix_lens=self._put_batch(
                    np.asarray(prefix_lens, np.int32)
                ),
            )
        tokens = np.asarray(tokens)
        valid = np.asarray(valid)
        prefix_valid = np.asarray(prefix_valid)
        bs = self._paged.block_size
        if Ls % bs:
            # The fixed history window H = P + Ls - C must be
            # block-aligned (the chunk gathers whole table columns), and
            # C already is (boot alignment) — align the WINDOW up with
            # trailing pad columns.  Safe: the pad slots lie inside the
            # table's block-rounded coverage and are masked everywhere;
            # the decode loop overwrites them before unmasking.
            pad = (-Ls) % bs
            tokens = np.pad(tokens, ((0, 0), (0, pad)))
            valid = np.pad(valid, ((0, 0), (0, pad)))
            Ls += pad
        B = tokens.shape[0]
        P = prefix_valid.shape[1]
        base_lens = np.asarray(prefix_lens, dtype=np.int64)
        logits = jnp.zeros((B, self.spec.vocab_size), jnp.float32)
        for start in range(0, Ls, C):
            Ct = min(C, Ls - start)
            H = P + Ls - Ct
            hist = np.zeros((B, H), dtype=bool)
            hist[:, :P] = prefix_valid
            hist[:, P:P + start] = valid[:, :start]
            pos_off = base_lens + valid[:, :start].sum(axis=1)
            logits, cache = obs_hlo.wrap(
                "prefill_paged_chunk", self._prefill_paged_chunk_at
            )(
                self.params,
                tokens=self._put_batch(tokens[:, start:start + Ct]),
                valid=self._put_batch(valid[:, start:start + Ct]),
                cache=cache,
                hist_valid=self._put_batch(hist),
                pos_offset=self._put_batch(pos_off.astype(np.int32)),
                write_pos=jnp.int32(P + start),
                carry_logits=logits,
            )
        return logits, cache

    def _decode_batch(
        self, parts, batch, sig_prefix, real_B, temps, budgets,
        top_p,
    ) -> List[str]:
        """Ledger envelope around :meth:`_decode_batch_impl`: the
        decode-cache charge (made inside the impl once B/S are known) is
        credited here in a ``finally`` so an engine failure cannot leak
        a phantom KV slab into ``hbm.kv_cache_bytes``."""
        try:
            return self._decode_batch_impl(
                parts, batch, sig_prefix, real_B, temps, budgets, top_p
            )
        finally:
            # Host work after the call's last device program: a span of
            # its own, so the device's idle time here has an owner.
            with obs_tracer.span("engine.release"):
                if self._paged is not None:
                    if self._paged_dirty:
                        # A jit call raised AFTER donating the pool: the old
                        # buffers are dead and the radix's resident blocks
                        # with them — reallocate a zeroed pool so the engine
                        # stays serviceable (working set re-prefills).
                        self._paged_dirty = False
                        self._paged_call_private = []
                        self._paged.invalidate()
                    else:
                        # Release this call's private (suffix/decode) blocks
                        # and the refcount pins on its radix paths — shared
                        # prefix blocks stay resident for the next round.
                        self._paged.free(self._paged_call_private)
                        self._paged_call_private = []
                        self._paged.unpin_all()
                    # Publish the post-call pool snapshot (incl. the active
                    # impl) for consumers without an engine handle — the
                    # bench error path's forensics (runtime/metrics idiom,
                    # same as LAST_SERVE_STATS).
                    from bcg_tpu.runtime import metrics as _metrics

                    _metrics.publish_kv_pool(self.kv_pool_stats())
                # Sampler self-description (impl, interpret, fused-kernel
                # invocation count) — published per call like kv_pool so
                # the bench ERROR path keeps the forensics of completed
                # calls.
                from bcg_tpu.runtime import metrics as _metrics2

                _metrics2.publish_sampler(self.sampler_stats())
                obs_ledger.credit("kv_cache", id(self))
                obs_ledger.credit("spec_slots", id(self))
                if self._mem_limit is not None:
                    # Real allocator present: publish the drift gauge
                    # (ledger vs bytes_in_use) each call — the leak alarm.
                    obs_ledger.reconcile()

    def _decode_batch_impl(
        self, parts, batch, sig_prefix, real_B, temps, budgets,
        top_p,
    ) -> List[str]:
        """Shared prefill + guided-decode scaffolding for the guided and
        free paths; ``parts`` is a batch-padded (_pad_rows) list of
        (prefix, suffix) prompt halves, ``temps``/``budgets`` the padded
        per-row sampling settings.  When every row has a cacheable
        prefix, only the suffixes are prefilled (prefix caching);
        otherwise the joined full prompts take the plain path."""
        B = len(parts)
        max_new = max(budgets)
        if self._dp_devices > 1:
            if B % self._dp_devices:
                # Reached when the row cap is tighter than dp (_dp_mult
                # dropped the alignment) — or, loudly, if a future batch
                # path forgets to align.
                self._note_dp_bypass(
                    f"batch size {B} not divisible by dp={self._dp_devices}"
                )
            else:
                self.dp_batches += 1
        # Speculative decoding applies to BOTH paths (the free path's
        # permissive automaton just never truncates a draft); it
        # supersedes fast-forward, whose forced chains the drafter
        # subsumes as its fallback source.  Fast-forward alone only pays
        # off when the automaton HAS forced chains; the free path's
        # permissive automaton has none, so it would buy 4x decode cache
        # and padded chunks for zero skipped steps.
        use_spec = self.spec_decode
        use_ff = (
            not use_spec and self.fast_forward and sig_prefix[0] != "free"
        )
        if use_spec:
            decode_slots = _spec_decode_slots(max_new, self.spec_k)
        elif use_ff:
            decode_slots = _ff_decode_slots(max_new)
        else:
            decode_slots = max_new + 1
        self._check_kv_budget(B, budgets, decode_slots)
        t0 = time.perf_counter()
        with obs_tracer.span("engine.prefill", args={"rows": B}):
            prepped = None
            paged = self._paged is not None
            if paged:
                # Block-paged path: radix-shared prefix blocks + private
                # suffix/decode blocks per row; the pool rides the jit
                # calls via donation and is re-adopted after each.
                with obs_tracer.span("engine.tokenize"):
                    (tokens, valid, Ls, cache, prefix_valid, prefix_lens,
                     prefix_toks, P, S, _tbl) = self._prepare_paged_batch(
                        parts, budgets, decode_slots
                    )
                self._paged_dirty = True
                # time_block: a NEW prefill signature's dispatch pays
                # trace+compile synchronously inside this call; the
                # _note_jit_shape("prefill", ...) below consumes the
                # elapsed (obs/compile.py stash handoff, no-op off).
                with obs_compile.time_block("prefill"):
                    first_logits, cache = self._prefill_paged_possibly_chunked(
                        tokens, valid, Ls, cache, prefix_valid, prefix_lens
                    )
                self._paged.adopt(cache)
                self._paged_dirty = False
                cache = self._paged.entries(_tbl)
                L = P + Ls
                valid_mask = np.zeros((B, S), dtype=bool)
                valid_mask[:, :P] = prefix_valid
                valid_mask[:, P:L] = valid
                prompt_lens = (prefix_lens + valid.sum(axis=1)).astype(np.int32)
            elif self.prefix_caching and self._prefix_safe and all(p for p, _, _ in parts):
                with obs_tracer.span("engine.tokenize"):
                    prepped = self._prepare_prefixed_batch(
                        parts, budgets, decode_slots
                    )
                if prepped is None:
                    self.prefix_fallbacks += 1
                    if not self._prefix_fallback_warned:
                        import warnings

                        warnings.warn(
                            "prefix caching disengaged for this batch (prefix "
                            "too long for the prompt window or unbucketable) — "
                            "falling back to full-prompt prefill; further "
                            "fallbacks are counted in engine.prefix_fallbacks",
                            stacklevel=2,
                        )
                        self._prefix_fallback_warned = True
            if prepped is not None:
                # The assembled cache arrives ALREADY sharded onto the mesh
                # layout (_assemble_cache's with_sharding_constraint wrapper,
                # the same kv_cache_tree_sharding specs _init_cache_sharded
                # uses for fresh caches).
                (tokens, valid, Ls, cache, prefix_valid, prefix_lens,
                 prefix_toks, P, S) = prepped
                with obs_compile.time_block("prefill"):
                    first_logits, cache = self._prefill_possibly_chunked(
                        tokens, valid, Ls, cache,
                        prefix_valid=prefix_valid, prefix_lens=prefix_lens,
                    )
                L = P + Ls
                valid_mask = np.zeros((B, S), dtype=bool)
                valid_mask[:, :P] = prefix_valid
                valid_mask[:, P:L] = valid
                prompt_lens = (prefix_lens + valid.sum(axis=1)).astype(np.int32)
            elif not paged:
                prefix_toks = None
                with obs_tracer.span("engine.tokenize"):
                    full_prompts = [p + c + t for p, c, t in parts]
                    tokens, valid, L = self._prepare_batch(
                        full_prompts, budgets
                    )
                S = L + decode_slots
                S += (-S) % self._kv_align  # see _kv_align
                cache = self._init_cache_sharded(B, S)
                with obs_compile.time_block("prefill"):
                    first_logits, cache = self._prefill_possibly_chunked(
                        tokens, valid, L, cache
                    )
                valid_mask = np.zeros((B, S), dtype=bool)
                valid_mask[:, :L] = valid
                prompt_lens = valid.sum(axis=1).astype(np.int32)
            # Ledger: this call's decode slab, split into the token-
            # budget window (kv_cache) and the loop family's decode-tail
            # OVER-allocation (spec_slots — speculation's K+1 verify
            # window / fast-forward's compacted tail, the slots past
            # max_new+1).  Per-device bytes via the same placement
            # function admission uses; credited by _decode_batch's
            # finally.  The paged path charges its PRIVATE blocks only —
            # the radix-shared prefix region already lives in the
            # prefix_cache account, which is the HBM-side shape of the
            # sharing win (N rows, one prefix charge).
            if paged:
                slab = (
                    B * ((S - P) // self._paged.block_size)
                    * self._paged.block_bytes_dev
                )
            else:
                slab = self._kv_bytes_per_device(B, S)
            extra = max(0, decode_slots - (max_new + 1))
            spec_part = int(slab * extra / S) if S else 0
            obs_ledger.charge("kv_cache", id(self), slab - spec_part)
            obs_ledger.charge("spec_slots", id(self), spec_part)
            hist = None
            if use_spec:
                # Token-history buffer for the prompt-lookup drafter:
                # row i's prompt tokens left-aligned at [0, prompt_lens[i])
                # (-1 pads never match), with max_new free slots for the
                # loop to append accepted output into.  On the
                # prefix-cached path the prefix/core tokens come from the
                # cache entries ("toks") — the batch arrays only carry
                # the suffix.
                hist = np.full((B, L + max_new), -1, dtype=np.int32)
                for i in range(B):
                    row = tokens[i][valid[i]]
                    if prefix_toks is not None:
                        row = np.concatenate([prefix_toks[i], row])
                    hist[i, : len(row)] = row
            # Compile/retrace accounting: the prefill jit signature is
            # (path kind, B, token window, cache length) — the shape
            # tuple that decides whether jax.jit re-traces.
            self._note_jit_shape(
                "prefill",
                (("paged", B, Ls, P, S) if paged
                 else ("suffix", B, Ls, P, S) if prepped is not None
                 else ("full", B, L, S)),
                names=(
                    ("path", "batch", "suffix_window", "prefix_len",
                     "cache_len")
                    if (paged or prepped is not None)
                    else ("path", "batch", "prompt_window", "cache_len")
                ),
                timing="stash",
            )
            # Prefill-position counters, split real vs padded (pads cost
            # FLOPs but are not progress — cache-hit savings must be
            # measurable without pad noise; entry builds count in their
            # creators).  `prefill_tokens` keeps its documented
            # padded-positions semantics for bench compatibility.
            # `positions_run` is what went through the model: the dense
            # prefill counts its own (less the all-pad chunks it passed
            # over), the paged one runs its whole window.
            obs_counters.inc(
                "engine.prefill.positions_padded",
                B * (L if (prepped is None and not paged) else Ls),
            )
            if paged:
                obs_counters.inc("engine.prefill.positions_run", B * Ls)
            obs_counters.inc(
                "engine.prefill.positions_real", int(valid.sum())
            )
            # Always sync here: prefill/decode wall-clock split feeds the
            # achieved-GB/s / MFU accounting (the extra host round-trip is a
            # few ms against multi-hundred-ms phases).
            obs_hostsync.note("prefill_barrier", entry="prefill")
            first_logits.block_until_ready()
            cached = prepped is not None or (paged and P)
            window = Ls if (prepped is not None or paged) else L
            chunk = self.prefill_chunk
            chunks = -(-window // chunk) if chunk and window > chunk else 1
            sent = (
                chunks if paged
                else len(self._prefill_chunk_starts(valid, window))
            )
            obs_tracer.annotate(
                prompt_window=window, cache_len=S,
                chunks=sent, chunks_skipped=chunks - sent,
                prompt_max=int(prompt_lens.max()),
                prefix="hit" if cached else "miss",
                prefix_fallbacks=self.prefix_fallbacks,
            )
        t1 = time.perf_counter()

        # The span opens with the decode clock (t1): the key split and
        # the loop's choice are host work the device waits on.
        with obs_tracer.span("engine.decode",
                             args={"rows": B, "max_new": max_new}):
            self._key, sub = jax.random.split(self._key)
            drafted = accepted = None
            # HLO-census entry names: the paged loops lower different
            # programs (block gather/scatter), so they pin under their own
            # names instead of drifting the dense entries — and the fused
            # Pallas loops under theirs, so the census can assert the
            # kernel's step counts BELOW the gather baseline.  A fused-
            # sampler engine likewise tags its EXECUTED loops "fused_" (on
            # CPU that is the interpret-mode emulation — the hardware claim
            # is carried by the tpu_fused_* cross-lowering twins below), so
            # the dense xla-sampler baseline entries never drift.
            if paged:
                census_prefix = (
                    "paged_" if self._paged_loop_impl == "xla"
                    else "paged_pallas_"
                )
            else:
                census_prefix = ""
            if self._sampler_loop_impl != "xla":
                census_prefix += "fused_"
            # Host-sync attribution entry: the census name of the decode
            # loop this call executes — what the auditor attributes the
            # post-loop readbacks to when tracing is off.
            loop_entry = census_prefix + (
                "spec_decode_loop" if use_spec
                else "ff_decode_loop" if use_ff
                else "decode_loop"
            )
            if paged:
                self._paged_dirty = True  # pool rides the donated loop call
            ring = (self.mesh, "sp") if self._sp_devices > 1 else None
            if use_spec:
                loop = obs_hlo.wrap(
                    census_prefix + "spec_decode_loop",
                    self._get_spec_decode_loop(
                        sig_prefix + (B, L), max_new, top_p
                    ),
                )
                loop_args = (
                    self.params, cache, first_logits,
                    self._put_batch(valid_mask),
                    self._put_batch(prompt_lens), L,
                    batch.tables, batch.accepting, batch.min_budget,
                    self._put_batch(batch.dfa_ids),
                    self._put_batch(batch.init_states),
                    batch.chain_tok, batch.chain_len,
                    self._put_batch(hist),
                    self._put_batch(np.asarray(temps, np.float32)),
                    self._put_batch(np.asarray(budgets, np.int32)),
                    sub,
                )
                if not paged and obs_hlo.enabled():
                    # Sampler census twins (xla vs fused sampler, same
                    # program otherwise), lowering-only from the same
                    # concrete args; must precede the call — it
                    # consumes the donated cache.
                    self._maybe_record_sampler_tpu_lowering(
                        "spec_decode_loop",
                        lambda si: self._build_spec_decode_loop(
                            self._resolved_loop_impl(chunk=True), max_new,
                            top_p, ring, sampler_impl=si,
                        ),
                        loop_args,
                    )
                with obs_tracer.span(
                    "engine.spec_verify",
                    args={"rows": B, "k": self.spec_k,
                          "ngram": self.spec_ngram},
                ):
                    # time_block: _get_spec_decode_loop noted any new
                    # signature moments ago (pending marker); the first
                    # invocation below pays its compile (flushed here,
                    # no-op off).
                    with obs_compile.time_block("spec_decode_loop"):
                        out, (_, steps), (drafted, accepted), _cache_out = \
                            loop(*loop_args)
            elif use_ff:
                loop = obs_hlo.wrap(
                    census_prefix + "ff_decode_loop",
                    self._get_ff_decode_loop(sig_prefix + (B, L), max_new, top_p),
                )
                loop_args = (
                    self.params, cache, first_logits,
                    self._put_batch(valid_mask),
                    self._put_batch(prompt_lens), L,
                    batch.tables, batch.accepting, batch.min_budget,
                    self._put_batch(batch.dfa_ids),
                    self._put_batch(batch.init_states),
                    batch.chain_tok, batch.chain_len, batch.chain_next,
                    self._put_batch(np.asarray(temps, np.float32)),
                    self._put_batch(np.asarray(budgets, np.int32)),
                    sub,
                )
                if not paged and obs_hlo.enabled():
                    self._maybe_record_sampler_tpu_lowering(
                        "ff_decode_loop",
                        lambda si: self._build_ff_decode_loop(
                            self._resolved_loop_impl(chunk=True), max_new,
                            top_p, ring, sampler_impl=si,
                        ),
                        loop_args,
                    )
                with obs_compile.time_block("ff_decode_loop"):
                    out, (_, steps), _cache_out = loop(*loop_args)
            else:
                loop = obs_hlo.wrap(
                    census_prefix + "decode_loop",
                    self._get_decode_loop(sig_prefix + (B, L), max_new, top_p),
                )
                loop_args = (
                    self.params, cache, first_logits,
                    self._put_batch(valid_mask),
                    self._put_batch(prompt_lens), L,
                    batch.tables, batch.accepting, batch.min_budget,
                    self._put_batch(batch.dfa_ids),
                    self._put_batch(batch.init_states),
                    self._put_batch(np.asarray(temps, np.float32)),
                    self._put_batch(np.asarray(budgets, np.int32)),
                    sub,
                )
                if paged and obs_hlo.enabled():
                    # Lowering-only census twins (gather vs fused) from
                    # the same concrete args; must precede the call —
                    # it consumes the donated pool.
                    self._maybe_record_paged_tpu_lowering(
                        max_new, top_p, loop_args
                    )
                elif obs_hlo.enabled():
                    self._maybe_record_sampler_tpu_lowering(
                        "decode_loop",
                        lambda si: self._build_decode_loop(
                            self._resolved_loop_impl(), max_new, top_p,
                            ring, sampler_impl=si,
                        ),
                        loop_args,
                    )
                with obs_compile.time_block("decode_loop"):
                    out, (_, steps, *hits), _cache_out = loop(*loop_args)
            if paged:
                # The loop wrote decode KV into private pool blocks
                # through the donated carry: retain the returned pool
                # (the pre-call buffers are dead).
                self._paged.adopt(_cache_out)
                self._paged_dirty = False
            del _cache_out  # dense: dropped immediately (aliasing only)
            obs_hostsync.note("decode_readback", entry=loop_entry)
            out_np = np.asarray(out)
            # Decode-loop iterations of this call (each is one weight
            # pass — the wall-clock unit of the decode phase).
            obs_hostsync.note("steps_readback", entry=loop_entry)
            if self.spec.moe:
                # One transfer: the steps, the loop's expert hits and the
                # chunk programs' (token, held expert) assignments,
                # stacked on the device.  The hits are counted over steps
                # and expert layers beside the slots: every held expert a
                # step and layer.
                prefilled, self._moe_prefill_counts = self._moe_prefill_counts, []
                steps, hit, assigned = (int(n) for n in np.asarray(
                    jnp.stack([steps, *hits, sum(prefilled)])))
                obs_counters.inc("engine.moe.decode.experts_hit", hit)
                obs_counters.inc(
                    "engine.moe.decode.expert_slots",
                    steps * self.spec.num_layers * self.spec.experts_held)
                obs_counters.inc("engine.moe.prefill.assignments", assigned)
            else:
                steps = int(steps)
            obs_tracer.annotate(steps=steps)
            # The loop's device arguments and output are spent: freed
            # here, where a span holds the host's time, not when the
            # frame returns.
            del loop_args, out, first_logits, cache
        t2 = time.perf_counter()
        # The call's accounting, between the decode loop's readback and
        # the detokenizer: host work the device waits on.
        with obs_tracer.span("engine.account"):
            if not self._first_call_recorded:
                # Boot breakdown's final phase: the first serving call pays
                # the first prefill + decode-loop compiles (plus one
                # execute) — recorded so a compile-time OOM names itself.
                self._boot.note("first_compile", t2 - t0)
                self._first_call_recorded = True
            self.last_decode_steps = steps
            self.total_decode_steps += steps
            if self._sampler_loop_impl != "xla":
                # Fused-kernel invocations (``sampler_stats``): one sampler
                # program per loop iteration.
                self._sampler_fused_calls += steps
            if use_spec:
                # Draft acceptance over REAL rows only (padding rows repeat
                # row 0 and would inflate the rate).  Counted even when 0 —
                # but keys are only created once something drafted, so a
                # spec-off engine's counter namespace stays byte-identical
                # to HEAD's.
                obs_hostsync.note("spec_readback", n=2, entry=loop_entry)
                spec_drafted = int(np.asarray(drafted)[:real_B].sum())
                spec_accepted = int(np.asarray(accepted)[:real_B].sum())
                if spec_drafted:
                    obs_counters.inc("engine.spec.drafted", spec_drafted)
                    obs_counters.inc("engine.spec.accepted", spec_accepted)
                    obs_counters.inc(
                        "engine.spec.rejected", spec_drafted - spec_accepted
                    )
            # Refresh LAST_HOSTSYNC once per generation call (no-op when
            # the auditor is off) — a crash after this call keeps the sync
            # profile in the bench error JSON.
            obs_hostsync.publish()
            # Perf accounting.  A decode step streams the cache slots of
            # each row's LIVE blocks where the int8 kernel attends
            # (_decode_kv_slots) and the whole ALLOCATED window elsewhere
            # (the einsum paths read all S slots, masked), plus one full
            # weight pass per loop iteration.
            self.prefill_tokens += B * (L if (prepped is None and not paged) else Ls)
            self.prefill_seconds += t1 - t0
            self.decode_seconds += t2 - t1
            self.decode_kv_bytes += (
                self._decode_kv_slots(valid_mask, L, steps, use_spec or use_ff)
                * self._kv_slot_bytes * self._kv_layers
            )
            self.decode_weight_passes += steps
        texts = []
        served = 0
        with obs_tracer.span("engine.detokenize", args={"rows": real_B}):
            for i in range(real_B):
                row = out_np[i]
                end = np.where(row == self.tokenizer.eos_id)[0]
                row = row[: end[0]] if end.size else row
                served += len(row)
                texts.append(self.tokenizer.decode(row.tolist()))
        # The decode loop's yield, from what the call read back anyway:
        # tokens served over real rows (up to each row's first EOS) per
        # loop iteration x real rows.  1.0 when every row runs to the
        # last step, below 1 when rows sit finished while the longest
        # decodes, above 1 under fast-forward (forced-chain tokens ride
        # a step).
        obs_counters.inc("engine.decode.tokens", served)
        obs_counters.inc("engine.decode.row_steps", steps * real_B)
        return texts

    def _decode_kv_slots(self, valid_mask: np.ndarray, L: int, steps: int,
                         chunked: bool) -> int:
        """Cache slots one attention layer streamed over a decode loop
        of ``steps`` iterations, summed over rows and steps, from what
        the host holds: the call's mask at the loop's start ([B, S]) and
        the first decode slot ``L``.  Where the int8 kernel attends (a
        dense int8 cache, no ``sp`` ring) that is each row's live blocks
        (``ops/decode_attention.live_block_range``: the rule the kernel's
        own bound comes from), also added, over the attention layers, to
        ``engine.decode.kv_blocks_live`` beside the grid's
        ``kv_blocks_grid``; every other path reads the allocation.  The
        plain loop's step ``i`` has written slot ``L + i``; a chunk
        loop's write position is the device's, so its steps count to the
        allocation's end (an upper bound)."""
        B, S = valid_mask.shape
        if not (is_pallas(self._resolved_loop_impl(chunk=chunked))
                and self.kv_quantized and self._sp_devices <= 1):
            return steps * B * S
        from bcg_tpu.ops.decode_attention import (
            kernel_block, live_block_count, live_slots,
        )

        block = kernel_block(self.spec.num_kv_heads, self.spec.head_dim)
        first, last = live_slots(valid_mask, xp=np)
        at_step = np.full(steps, S - 1) if chunked else L + np.arange(steps)
        live = live_block_count(
            np.minimum(first[:, None], at_step),
            np.maximum(last[:, None], at_step), block)
        obs_counters.inc("engine.decode.kv_blocks_live", live * self._kv_layers)
        obs_counters.inc(
            "engine.decode.kv_blocks_grid",
            steps * B * -(-S // block) * self._kv_layers)
        return live * block

    def _cache_bytes(self, B: int, S: int) -> Dict[str, int]:
        """Bytes of a [B, S] decode cache by kind of state, read off the
        allocation's own shapes (``transformer.cache_bytes``): what the
        allocation counters and, for a hybrid, the row cap count."""
        got = self._cache_bytes_memo.get((B, S))
        if got is None:
            got = self._cache_bytes_memo[(B, S)] = cache_bytes(
                self.spec, B, S,
                quantized=self.kv_quantized, stacked=self.scan_layers,
            )
        return got

    def _kv_bytes_per_device(self, B: int, S: int) -> int:
        """Per-device decode-cache bytes for a [B, S] cache under the
        layout ``kv_cache_tree_sharding`` ACTUALLY places — an axis that
        fails its divisibility guard (Hkv % tp, S % sp, B % dp)
        replicates and does NOT divide.  Memoized per (B, S): eval_shape
        is cheap but this sits on every generation call's cap path."""
        if self.spec.hybrid:
            return sum(self._cache_bytes(B, S).values())
        if self.mesh is None or self._mesh_devices <= 1:
            return B * S * self._kv_slot_bytes * self.spec.num_layers
        key = (B, S)
        got = self._kv_bytes_memo.get(key)
        if got is None:
            from bcg_tpu.parallel.sharding import kv_cache_bytes_per_device

            shapes = jax.eval_shape(partial(
                init_kv_cache, self.spec, B, S,
                quantized=self.kv_quantized, stacked=self.scan_layers,
            ))
            got = kv_cache_bytes_per_device(
                self.mesh, shapes,
                quantized=self.kv_quantized, stacked=self.scan_layers,
            )
            self._kv_bytes_memo[key] = got
        return got

    def _kv_row_budget(self) -> Optional[float]:
        """Device bytes available to the decode cache: the budgeted HBM
        fraction minus this device's weight SHARD and the prefix-cache
        reserve.  The reserve is the full static BUDGET, not the current
        fill: a volatile reserve would flip the derived cap between
        calls and re-chunk the same logical batch into fresh compiled
        shapes."""
        if self._mem_limit is None:
            return None
        prefix_reserve = (
            self._prefix_budget
            if self.prefix_caching and self._prefix_safe
            else 0
        )
        return (
            self.config.hbm_utilization * self._mem_limit
            - self._param_bytes_per_device
            - prefix_reserve
        )

    def _decode_reserve(self, max_new: int) -> int:
        """Worst-case decode-tail cache slots for ``max_new`` output
        tokens under the CONFIGURED loop family — speculative over-
        allocates its K+1 verify window, fast-forward its compacted
        chain tail.  The admission/provisioning worst case: _decode_batch
        may still pick a smaller reserve per call (e.g. fast-forward
        skips the free path)."""
        if self.spec_decode:
            return _spec_decode_slots(max_new, self.spec_k)
        if self.fast_forward:
            return _ff_decode_slots(max_new)
        return max_new + 1

    def worst_case_decode_window(self) -> int:
        """Largest cache length any single admitted row can require —
        prompt window plus decode reserve, maximized over the row's
        token budget.  The serving scheduler's admission cap
        (serve/scheduler.derive_row_cap) must use THIS, not
        max_model_len: the fast-forward and speculative loops reserve
        more decode slots than the budget they serve, so sizing
        admission to max_model_len alone would overcommit exactly when
        those loops are on."""
        b = max(1, self.max_model_len - 2)
        return (self.max_model_len - b - 1) + self._decode_reserve(b)

    def _auto_pool_blocks(self, block_size: int) -> int:
        """Paged-pool auto-sizing: the WHOLE KV budget becomes one pool.
        With a known device limit that is the ``hbm_utilization``
        fraction minus the weight shard — unlike the dense provisioner
        there is NO separate prefix-cache reserve to carve out (radix-
        resident prefixes and decode tails draw from the same blocks),
        which is one of the two structural reasons paged admission caps
        come out strictly higher at the same budget (the other: no
        ``ALIGN_S`` padding of per-row windows).  Without a limit (CPU
        tests) the pool affords 16 worst-case rows."""
        tp = self.mesh.shape.get("tp", 1) if self.mesh is not None else 1
        div = tp if tp > 1 and self.spec.num_kv_heads % tp == 0 else 1
        block_bytes = max(
            1, block_size * self._kv_slot_bytes * self._kv_layers // div
        )
        if self._mem_limit:
            budget = (
                self.config.hbm_utilization * self._mem_limit
                - self._param_bytes_per_device
            )
            return max(64, min(1 << 20, int(budget // block_bytes)))
        blocks_per_row = -(-self.worst_case_decode_window() // block_size) + 1
        return 16 * blocks_per_row + 1

    def _paged_build_scratch_blocks(self) -> int:
        """Worst-case TRANSIENT blocks one radix entry build holds past
        its real content: the bucket pad tail (``_get_paged_entry``
        rounds the remainder prefill up a suffix-ladder rung for stable
        compile shapes; the pad blocks are freed the moment the insert
        returns, but they are LIVE during the build).  Admission
        (:meth:`cap_for`) carves this out of the usable pool — without
        the reserve, a boundary-sized pool admits a batch whose cold
        entry builds then hit ``PoolExhausted`` mid-prefill, exactly the
        failure admission exists to make unreachable.  One build's worth
        suffices: builds run sequentially and each frees its scratch
        before the next allocates."""
        bs = self._paged.block_size
        worst = 0
        prev = 0
        for rung in self._suffix_buckets:
            # Smallest block-aligned remainder mapping to this rung
            # (remainders are whole-block by construction).
            lr = (prev // bs + 1) * bs
            if lr > self.max_model_len:
                break
            worst = max(worst, -(-rung // bs) - lr // bs)
            prev = rung
        return worst

    def _paged_scratch_reserve(self) -> int:
        """The entry-build scratch reserve admission subtracts — 0 when
        radix prefix sharing cannot engage (uncached engines never build
        entries)."""
        return (
            self._paged_scratch_blocks
            if self.prefix_caching and self._prefix_safe
            else 0
        )

    def _paged_usable_blocks(self) -> int:
        """Blocks admission may budget: the pool minus the null block
        minus the entry-build scratch reserve, floored at 1 (a pool
        smaller than the reserve still admits single rows — the
        exhaustion warning in ``_check_kv_budget`` owns that case)."""
        return max(1, self._paged.num_blocks - 1 - self._paged_scratch_reserve())

    def cap_for(self, S: int) -> Optional[int]:
        """Concurrent-row cap for decode-cache length ``S``, derived
        from the mesh axes that actually engage (ADVICE round-5 medium).

        PAGED mode derives from free-block accounting instead: the pool
        is the budget, a row of window ``S`` needs ``ceil(S / bs)``
        blocks, and the cap is the usable block count over that — a
        static quantity (total blocks, not the fluctuating free count),
        for the same reason the dense budget ignores current prefix
        fill: a volatile cap re-chunks identical batches into fresh
        compiled shapes.  Shared prefix blocks make the real per-row
        need smaller still; the cap is the conservative floor.

        Two regimes, mirroring ``_dp_mult``: if the engaged-axes cap
        admits at least ``dp`` rows, the caller will dp-align the batch
        and the batch axis shards — per-row cost is one dp-shard's
        share.  Otherwise the batch runs dp-REPLICATED (the dp-bypass
        path), every device holds every row, and the cap must be
        re-derived at full per-row cost — the old flat
        ``/ mesh.size`` divisor overcommitted exactly here, by up to
        dp×.  tp/sp engagement (Hkv and S divisibility) is read off the
        same placement function the cache allocation uses, so engaged
        configs get every row the layout genuinely affords."""
        if self._paged is not None:
            blocks_per_row = -(-S // self._paged.block_size)
            return max(1, self._paged_usable_blocks() // blocks_per_row)
        budget = self._kv_row_budget()
        if budget is None:
            return None
        S += (-S) % self._kv_align
        dp = max(self._dp_devices, 1)
        per_row = self._kv_bytes_per_device(dp, S) / dp
        if per_row <= 0:
            return None
        cap = max(1, int(budget // per_row))
        if dp > 1 and cap < dp:
            # dp-bypass: _dp_mult will drop the alignment and the batch
            # axis replicates — re-derive at replicated per-row cost.
            per_row = float(self._kv_bytes_per_device(1, S))
            cap = max(1, int(budget // per_row))
        return cap

    def _provisioned_row_cap(self, parts, budgets: List[int]) -> Optional[int]:
        """``hbm_utilization`` as an ACTUAL provisioner — the reference's
        ``gpu_memory_utilization`` provisions the vLLM KV pool
        (vllm_agent.py:129-136); round-2 VERDICT called our warn-only
        guard "a bound in name only".  Estimates the batch's per-row
        decode-cache bytes from the ACTUAL prompt lengths (bucketed the
        way _decode_batch will bucket them) and caps the concurrently
        decoded rows so cache + weights + live prefix entries fit the
        budgeted fraction of device memory; oversized batches then chunk
        through the max_num_seqs machinery.  Returns None when the
        device limit is unknown (CPU tests) or the whole batch fits.
        PAGED mode provisions even without a device limit: the pool is
        finite everywhere, and ``cap_for`` answers from free-block
        accounting."""
        if self._mem_limit is None and self._paged is None:
            return None
        max_new = max(budgets)
        decode_res = self._decode_reserve(max_new)
        limit = self.max_model_len - min(budgets) - 1
        B_pad = _aligned_pad_batch(len(parts), self._dp_devices)
        # Cheap pre-check at the WORST-CASE prompt window: if even that
        # fits the whole padded batch, skip the per-row tokenization
        # below (~1.4 ms/row on HF tokenizers — real host time on every
        # call of a 1-core box when it can never change the answer).
        worst = self.cap_for(limit + decode_res)
        if worst is None or worst >= B_pad:
            return None
        longest = max(
            len(self.tokenizer.encode(p + c + t)[-limit:]) for p, c, t in parts
        )
        L = next((b for b in _LEN_BUCKETS if b >= longest), limit)
        cap = self.cap_for(min(L, limit) + decode_res)
        if cap is None or cap >= B_pad:
            return None
        # The caller (_run_guided/_run_free) re-derives the dp padding
        # multiple against this cap and counts provision_chunk_events
        # only when the cap actually forces a chunk split — a cap that
        # merely disables dp alignment is not a chunk event.
        return cap

    def _check_kv_budget(self, B: int, budgets: List[int],
                         decode_res: int) -> None:
        """hbm_utilization as an OOM guard (the reference's
        ``gpu_memory_utilization``, config.py:36): warn — once — when the
        worst-case KV cache for this batch would push past the budgeted
        fraction of device memory, naming the knobs that bound it.  B is
        the batch ACTUALLY decoded, so the engaged-axes accounting is
        exact here: a B that skips dp alignment counts replicated.
        ``decode_res`` is the decode-tail reservation of the loop that
        will actually run (plain / fast-forward / speculative — the
        caller's ``decode_slots``).  PAGED mode guards in blocks: the
        worst-case block need of the batch against the usable pool."""
        if self._kv_budget_warned:
            return
        if self._paged is not None:
            bs_blk = self._paged.block_size
            S = self.max_model_len - min(budgets) - 1 + decode_res
            needed = B * (-(-S // bs_blk))
            usable = self._paged_usable_blocks()
            if needed > usable:
                import warnings

                warnings.warn(
                    f"worst-case KV need ({needed} blocks for B={B}, "
                    f"S={S}) exceeds the paged pool ({usable} usable "
                    f"blocks of {bs_blk} tokens); bound it with "
                    "max_num_seqs, a smaller max_model_len, or a larger "
                    "BCG_TPU_KV_POOL_BLOCKS",
                    stacklevel=3,
                )
                self._kv_budget_warned = True
            return
        if self._mem_limit is None:
            return
        spec = self.spec
        # Worst case for a mixed-budget batch: a min-budget row's prompt
        # window (max_model_len - min - 1) plus the batch-wide decode
        # reservation.
        S = self.max_model_len - min(budgets) - 1 + decode_res
        kv_total = B * S * self._kv_slot_bytes * self._kv_layers
        per_device = (
            self._kv_bytes_per_device(B, S) + self._param_bytes_per_device
        )
        if per_device > self.config.hbm_utilization * self._mem_limit:
            import warnings

            warnings.warn(
                f"worst-case KV cache ({kv_total / 1e9:.1f} GB for B={B}, "
                f"S={S}) plus weights ({self._param_bytes / 1e9:.1f} GB) "
                f"exceeds hbm_utilization={self.config.hbm_utilization} of "
                f"device memory ({self._mem_limit / 1e9:.1f} GB); bound it "
                "with max_num_seqs, a smaller max_model_len, or "
                "kv_cache_dtype='int8'",
                stacklevel=3,
            )
            self._kv_budget_warned = True

    # -------------------------------------------------------- public surface

    def generate_json(self, prompt, schema, temperature=0.0, max_tokens=512,
                      system_prompt=None) -> Dict[str, Any]:
        return self.batch_generate_json(
            [(system_prompt or "", prompt, schema)], temperature, max_tokens
        )[0]

    def batch_generate_json(self, prompts, temperature=0.8, max_tokens=512):
        """Rows are (system, user, schema); ``user`` may be a plain string
        or a ``(shared_core, tail)`` pair — the core (identical across
        agents of a role within a round) is then served from a two-level
        cached KV prefix and only the tail prefills per row."""
        if not prompts:
            return []
        with obs_tracer.span("engine.call", args={
            "rows": len(prompts),
            "max_tokens": max(_per_row(max_tokens, len(prompts), int)),
        }):
            return self._batch_generate_json(prompts, temperature, max_tokens)

    def _batch_generate_json(self, prompts, temperature, max_tokens):
        # Chaos seam (BCG_TPU_CHAOS `crash|hang|exhaust@engine.generate`):
        # an injected engine failure surfaces exactly where a compiler/
        # runtime crash would — BEFORE the guided run, so no partial
        # cache state is left behind — and reaches the caller's retry
        # ladder (serve dispatch recovery, orchestrator fallback).
        resilience.inject("engine.generate")
        parts = []
        with obs_tracer.span("engine.format"):
            for system_prompt, user_prompt, _ in prompts:
                if isinstance(user_prompt, tuple):
                    core, tail = user_prompt
                    parts.append(format_chat_parts3(
                        self.config.model_name, system_prompt, core, tail,
                        self.config.disable_qwen3_thinking,
                    ))
                else:
                    prefix, suffix = format_chat_parts(
                        self.config.model_name, system_prompt, user_prompt,
                        self.config.disable_qwen3_thinking,
                    )
                    parts.append((prefix, "", suffix))
        schemas = [schema for _, _, schema in prompts]
        try:
            texts = self._run_guided(parts, schemas, temperature, max_tokens)
        except BudgetError as e:
            # ONLY the engine's own budget check degrades to error dicts
            # (the caller's retry ladder absorbs them).  A broad
            # `except ValueError` here once swallowed a Pallas LOWERING
            # error: every call "failed fast", every agent silently
            # abstained, and the bench printed a 6x-too-good number —
            # compiler/runtime errors must crash, not masquerade as bad
            # LLM output.
            self.total_rows += len(prompts)
            self.failed_rows += len(prompts)
            return [{"error": "generation_failed", "message": str(e)} for _ in prompts]
        results = []
        with obs_tracer.span("engine.parse"):
            for text in texts:
                try:
                    results.append(json.loads(text))
                except json.JSONDecodeError:
                    salvaged = self.extract_json(text)
                    results.append(
                        salvaged
                        if salvaged is not None
                        else {"error": "json_parse_failed", "raw": text[:200]}
                    )
        self.total_rows += len(results)
        self.failed_rows += sum(
            1 for r in results if isinstance(r, dict) and "error" in r
        )
        return results

    def generate(self, prompt, temperature=0.0, max_tokens=256, top_p=1.0,
                 system_prompt=None) -> str:
        return self.batch_generate(
            [
                format_chat_prompt(
                    self.config.model_name, system_prompt, prompt,
                    self.config.disable_qwen3_thinking,
                )
                if system_prompt
                else prompt
            ],
            temperature, max_tokens, top_p,
        )[0]

    def batch_generate(self, prompts, temperature=0.0, max_tokens=256, top_p=1.0):
        """Unguided generation: same loop with a permissive one-state DFA
        that allows every token and EOS everywhere."""
        return self._run_free(prompts, temperature, max_tokens, top_p)

    def _run_free(self, full_prompts, temperature, max_tokens, top_p=1.0):
        # Free-form prompts arrive pre-joined (no prefix/suffix split), so
        # they always take the full-prefill path.
        parts = [("", "", p) for p in full_prompts]
        n = len(parts)
        temps = _per_row(temperature, n, float)
        budgets = _per_row(max_tokens, n, int)
        cap = self.config.max_num_seqs
        with obs_tracer.span("engine.provision"):
            derived = self._provisioned_row_cap(parts, budgets)
        if derived is not None:
            cap = min(cap, derived) if cap else derived
        mult = self._dp_mult(cap)
        if cap and _aligned_pad_batch(n, mult) > cap:
            if derived is not None and derived <= cap:
                self.provision_chunk_events += 1
            step = _chunk_size(cap, mult)
            out: List[str] = []
            for i in range(0, n, step):
                out.extend(self._run_free(
                    full_prompts[i:i + step], temps[i:i + step],
                    budgets[i:i + step], top_p,
                ))
            return out
        real_B, B, parts, temps, budgets = _pad_rows(
            parts, temps, budgets, multiple=mult
        )
        batch = GuidedBatch.permissive(B, self.spec.vocab_size)
        texts = self._decode_batch(
            parts, batch, ("free", 1, self.spec.vocab_size), real_B,
            temps, budgets, top_p,
        )
        return [t.strip() for t in texts]

    def kv_pool_stats(self) -> Optional[Dict[str, Any]]:
        """Paged-pool snapshot (block counts, free-block headroom bytes,
        radix prefix hit rate, the ACTIVE attention impl + kernel knobs)
        for serve stats and bench JSON; None on dense engines so
        consumers can render conditionally."""
        if self._paged is None:
            return None
        from bcg_tpu.ops.paged_attention import (
            PALLAS_INTERPRET, configured_pages_per_program,
        )

        stats = self._paged.stats()
        stats["impl"] = self.paged_kv_impl
        # Packed-bytes honesty: block_bytes_dev (and every *_bytes field
        # derived from it) already reads the POOL'S actual leaves, so an
        # int4 pool reports half an int8 pool's bytes without special
        # casing — the dtype rides along so consumers can tell why.
        stats["kv_dtype"] = self.kv_dtype
        stats["interpret"] = self._paged_loop_impl == PALLAS_INTERPRET
        # The CONFIGURED group size — each kernel call clamps it to its
        # table width at trace time (ops/paged_attention).
        stats["pages_per_program"] = (
            configured_pages_per_program(stats["interpret"])
            if self.paged_kv_impl == "pallas" else None
        )
        # The TRUE reserve, not num_blocks-1-usable: when the pool is
        # smaller than the reserve, usable's floor of 1 would otherwise
        # fabricate a smaller reserve in exactly the PoolExhausted
        # forensics this field exists for.
        stats["scratch_reserve_blocks"] = self._paged_scratch_reserve()
        return stats

    def sampler_stats(self) -> Dict[str, Any]:
        """Guided-sampler self-description (the bench JSON ``sampler``
        block): the resolved impl, whether the kernel runs in interpret
        mode (explicit pallas off-TPU — the parity-test path), the
        instance's fused-kernel invocation count (one program per decode
        iteration; 0 on the xla path), and the resolved KV dtype riding
        along so hardware A/B runs of BOTH ISSUE-10 features are
        self-describing from one snapshot."""
        return {
            "impl": self.fused_sampler,
            "interpret": self._sampler_loop_impl == _GS_PALLAS_INTERPRET,
            "fused_calls": self._sampler_fused_calls,
            "kv_dtype": self.kv_dtype,
        }

    def shutdown(self) -> None:
        self.params = None
        self._decode_loops.clear()
        self._prefix_cache.clear()
        if self._paged is not None:
            self._paged.close()
        self._paged = None
        self._paged_call_private = []
        self._paged_toks_memo.clear()
        self._prefix_bytes = 0
        self._prefix_bytes_dev = 0
        self._prefix_lens_memo.clear()
        # Release this engine's ledger accounts (weights + prefix KV;
        # per-call kv_cache/spec_slots charges are credited by their own
        # finally) so hbm.* gauges reflect the post-shutdown device.
        obs_ledger.credit("params", id(self))
        obs_ledger.credit("prefix_cache", id(self))
