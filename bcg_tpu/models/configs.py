"""Model architecture specs.

Shapes for the reference's model presets (Qwen3-8B/14B/32B,
Mistral-Small-22B — reference config.py:20-25) plus a tiny hermetic spec
for tests and CPU smoke runs.  Those are one architecture family:
pre-RMSNorm decoder blocks, rotary positions, grouped-query attention,
SwiGLU MLP.  Qwen3 additionally applies RMSNorm to per-head q/k
projections (qk_norm=True).

A second family states ``layer_types``: a HYBRID stack whose layers are
either ``"full_attention"`` or ``"linear_attention"`` (a gated
delta-rule layer, ``ops/gated_delta.py``), in a repeating period
(Olmo-Hybrid-7B: three linear layers to one full layer).  Such a spec
also says where its norms sit (``norm_placement="post"``: ``x +
norm(sublayer(x))``), what its q/k norm spans (``qk_norm="full"``: the
whole projection before the split into heads) and may have no rotary
embedding at all (``rope_theta=None``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

FULL_ATTENTION = "full_attention"
LINEAR_ATTENTION = "linear_attention"
LAYER_TYPES = (FULL_ATTENTION, LINEAR_ATTENTION)


# Parameter count at/above which single-chip serving needs the memory
# levers (int8 KV + scan-over-layers): an 8B-class bf16 KV cache next to
# int8 weights exceeds a 16 GB v5e.  Shared by the bench's config gates
# and the engine's int8-KV speed warning.
LARGE_MODEL_PARAMS = 6_000_000_000

# At/above this, even int8 weights (>= 12 GB) crowd out the KV cache on
# a 16 GB chip: single-chip serving needs the int4 weight path
# (models/quantize.py quantize_weight_int4) — the reference's 14B preset
# is the first to cross it.
XL_MODEL_PARAMS = 12_000_000_000


@dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1-style NTK-by-parts rope scaling (HF ``rope_type:
    "llama3"``): frequencies whose wavelength exceeds the original
    training context are stretched by ``factor``, short wavelengths are
    kept, and the band between is smoothly interpolated."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position: int = 8192


@dataclass(frozen=True)
class ModelSpec:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: Optional[float] = 1_000_000.0   # None: no rotary embedding
    rms_eps: float = 1e-6
    # False; True: Qwen3-style per-head q/k RMSNorm; "full": one RMSNorm
    # over the whole q and the whole k projection (Olmo 2/3 convention)
    qk_norm: Union[bool, str] = False
    attn_bias: bool = False        # Qwen2-style q/k/v projection biases
    rope_scaling: Optional[RopeScaling] = None
    tie_embeddings: bool = False
    max_position: int = 40960
    # "pre": x + sublayer(norm(x)); "post": x + norm(sublayer(x))
    norm_placement: str = "pre"
    # None: every layer is the dense family's block.  A tuple (one entry
    # of LAYER_TYPES per layer) makes the spec a hybrid; the linear_*
    # sizes then describe its delta-rule layers.
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            return
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - set(LAYER_TYPES):
            raise ValueError(
                f"{self.name}: layer_types must name {self.num_layers} layers "
                f"from {LAYER_TYPES}, got {self.layer_types}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError(
                f"{self.name}: the delta-rule layer is built for as many key "
                "heads as value heads")

    @property
    def hybrid(self) -> bool:
        return self.layer_types is not None

    @property
    def layer_period(self) -> Tuple[str, ...]:
        """The shortest prefix of ``layer_types`` whose repetition is the
        whole list: what one step of the layer scan applies."""
        types = self.layer_types
        for n in range(1, len(types) + 1):
            if len(types) % n == 0 and types == types[:n] * (len(types) // n):
                return types[:n]
        return types

    def layers_of(self, kind: str) -> int:
        if self.layer_types is None:
            return self.num_layers if kind == FULL_ATTENTION else 0
        return self.layer_types.count(kind)

    @property
    def linear_key_size(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_size(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_size(self) -> int:
        """Channels of the depthwise conv: q, k and v side by side."""
        return 2 * self.linear_key_size + self.linear_value_size

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def matmul_shapes(self, kind: str = FULL_ATTENTION) -> Dict[str, Tuple[int, int]]:
        """``leaf -> (in, out)`` of the dense matmuls of one layer of
        ``kind``, the SwiGLU half included: the one table the parameter
        plan, the size-class gate and the byte estimates read."""
        D, F = self.hidden_size, self.intermediate_size
        mlp = {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
        if kind == LINEAR_ATTENTION:
            K, V = self.linear_key_size, self.linear_value_size
            return {"lin_wq": (D, K), "lin_wk": (D, K), "lin_wv": (D, V),
                    "lin_wa": (D, self.linear_num_value_heads),
                    "lin_wb": (D, self.linear_num_value_heads),
                    "lin_wg": (D, V), "lin_wo": (V, D), **mlp}
        return {"wq": (D, self.q_size), "wk": (D, self.kv_size),
                "wv": (D, self.kv_size), "wo": (self.q_size, D), **mlp}

    @property
    def matmul_params_per_layer(self) -> int:
        """Dense matmul parameters of one decoder block (q/k/v/o +
        SwiGLU MLP) — the unit both the size-class gate and the bench's
        MFU accounting are built from (single source, so they can't
        drift).  For a hybrid: the mean over its layers."""
        return self.block_matmul_params // self.num_layers

    @property
    def block_matmul_params(self) -> int:
        """Dense matmul parameters of all blocks, counted by layer type."""
        return sum(
            self.layers_of(kind) * sum(i * o for i, o in self.matmul_shapes(kind).values())
            for kind in LAYER_TYPES
        )

    @property
    def param_count(self) -> int:
        """Approximate parameter count (matmuls + embeddings; norm
        vectors are noise at this granularity).  Size-class gates key on
        this instead of substring-matching model names — ``"8b" in
        model`` silently mis-defaulted renamed or larger presets
        (VERDICT round-2 weak #6)."""
        embed = self.vocab_size * self.hidden_size
        embed_total = embed if self.tie_embeddings else 2 * embed
        return embed_total + self.block_matmul_params

    def weight_bytes(self, quantization: Optional[str] = None) -> int:
        """Estimated served-weight footprint in bytes for a quantization
        mode (None = bf16, "int8" = W8A8, "int4" = grouped W4A16).

        Counts what the engine actually holds: the bf16 embedding table
        (token gathers stay bf16), a quantized LM head (explicit for
        tied models too, models/quantize.py), and the per-layer matmul
        weights with their scale tensors (int8: f32 per-output-channel;
        int4: bf16 per (group=128, output)).  Norm vectors are noise.
        This is the capacity-math half of the single-chip fit question;
        add KV cache + activations (config-dependent) for the total.
        """
        embed = self.vocab_size * self.hidden_size  # bf16 gathers
        mm = self.block_matmul_params + embed  # + head
        # Scale elements = one per output channel (int8) or per
        # (group, output) (int4).  Output-channel totals, by layer type
        # (a hybrid's two per-head gate projections stay bf16 and are
        # counted as if quantized: 0.1% of a layer):
        out_total = self.vocab_size + sum(
            self.layers_of(kind) * sum(o for _, o in self.matmul_shapes(kind).values())
            for kind in LAYER_TYPES
        )
        if quantization is None:
            # Tied bf16 serving shares ONE table (transformer._logits
            # uses embed.T; no lm_head is stored) — don't double-count.
            head_bf16 = 0 if self.tie_embeddings else embed
            return embed * 2 + (mm - embed + head_bf16) * 2
        if quantization == "int8":
            return embed * 2 + mm + out_total * 4
        if quantization == "int4":
            group = 128
            # gscale elements ~= (in/group) * out summed over matmuls
            # ~= mm / group.
            return embed * 2 + mm // 2 + (mm // group) * 2
        raise ValueError(f"unknown quantization {quantization!r}")


def _olmo_hybrid(name: str, max_position: int) -> ModelSpec:
    """Olmo-Hybrid-7B's public ``config.json``: (linear x 3, full) x 8,
    30-head MHA of 128 without rotary positions, delta-rule heads of
    key dim 96 and value dim 192.  ``rope_theta: null`` is read as no
    rotary embedding; norm placement and the q/k norm follow the Olmo
    2/3 convention (see benchmark/configs/olmo-hybrid-7b-int8.json,
    ``assumed``)."""
    return ModelSpec(
        name=name, vocab_size=100352, hidden_size=3840, num_layers=32,
        num_heads=30, num_kv_heads=30, head_dim=128, intermediate_size=11008,
        rope_theta=None, qk_norm="full", norm_placement="post",
        max_position=max_position,
        layer_types=((LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,)) * 8,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    )


MODEL_SPECS: Dict[str, ModelSpec] = {
    # Qwen3 dense family (HF config.json values).
    "Qwen/Qwen3-8B": ModelSpec(
        name="Qwen/Qwen3-8B",
        vocab_size=151936, hidden_size=4096, num_layers=36,
        num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=12288, qk_norm=True,
    ),
    "Qwen/Qwen3-14B": ModelSpec(
        name="Qwen/Qwen3-14B",
        vocab_size=151936, hidden_size=5120, num_layers=40,
        num_heads=40, num_kv_heads=8, head_dim=128,
        intermediate_size=17408, qk_norm=True,
    ),
    "Qwen/Qwen3-32B": ModelSpec(
        name="Qwen/Qwen3-32B",
        vocab_size=151936, hidden_size=5120, num_layers=64,
        num_heads=64, num_kv_heads=8, head_dim=128,
        intermediate_size=25600, qk_norm=True,
    ),
    # Families beyond the reference's presets that its engine layer
    # special-cases chat templates for (vllm_agent.py:199-292) — specs
    # here so those templates are servable, not just formattable.
    "Qwen/Qwen2.5-7B-Instruct": ModelSpec(
        name="Qwen/Qwen2.5-7B-Instruct",
        vocab_size=152064, hidden_size=3584, num_layers=28,
        num_heads=28, num_kv_heads=4, head_dim=128,
        intermediate_size=18944, attn_bias=True, max_position=32768,
    ),
    "meta-llama/Meta-Llama-3.1-8B-Instruct": ModelSpec(
        name="meta-llama/Meta-Llama-3.1-8B-Instruct",
        vocab_size=128256, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=14336, rope_theta=500_000.0,
        rms_eps=1e-5, rope_scaling=RopeScaling(), max_position=131072,
    ),
    "mistralai/Mistral-Small-Instruct-2409": ModelSpec(
        name="mistralai/Mistral-Small-Instruct-2409",
        vocab_size=32768, hidden_size=6144, num_layers=56,
        num_heads=48, num_kv_heads=8, head_dim=128,
        intermediate_size=16384, rope_theta=1_000_000.0,
        rms_eps=1e-5, max_position=32768,
    ),
    # Hybrid family (layer_types): gated delta-rule layers beside full
    # attention.  No checkpoint loader is built for it: the published
    # name is here for its shapes, the bcg-tpu/ twin serves random
    # weights behind the byte tokenizer.
    "allenai/Olmo-Hybrid-7B": _olmo_hybrid("allenai/Olmo-Hybrid-7B", 65536),
    # Hermetic HF-artifact specs (models/hf_fixture.py): loaded through
    # the REAL checkpoint pipeline — AutoTokenizer + safetensors shards +
    # config.json on local disk — with random weights.  `tiny` proves the
    # pipeline on CPU in tests; `bench-1b` is the TPU-scale variant.
    "bcg-hf/tiny": ModelSpec(
        name="bcg-hf/tiny",
        vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, qk_norm=True, max_position=2048,
    ),
    "bcg-hf/bench-1b": ModelSpec(
        name="bcg-hf/bench-1b",
        vocab_size=32768, hidden_size=2048, num_layers=16,
        num_heads=16, num_kv_heads=8, head_dim=128,
        intermediate_size=6144, qk_norm=True, max_position=8192,
    ),
    # Family-fidelity fixtures (models/hf_fixture.py): Llama-3-shaped
    # byte-BPE vocab (<|eot_id|> specials, header-id template) and a
    # true-SentencePiece Mistral-shaped one ([INST] template, Metaspace
    # pieces) — so template selection and tokenizer detection are proven
    # against each family the reference special-cases
    # (vllm_agent.py:199-292), not just ChatML.
    "bcg-hf/tiny-llama3": ModelSpec(
        name="bcg-hf/tiny-llama3",
        vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, rope_theta=500_000.0,
        rms_eps=1e-5, max_position=2048,
    ),
    "bcg-hf/tiny-mistral": ModelSpec(
        name="bcg-hf/tiny-mistral",
        vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, rms_eps=1e-5, max_position=2048,
    ),
    # Hermetic tiny model: byte tokenizer vocabulary, runs on CPU in ms.
    "bcg-tpu/tiny-test": ModelSpec(
        name="bcg-tpu/tiny-test",
        vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, qk_norm=True, max_position=2048,
    ),
    # Tiny spec with a LANE-ALIGNED head dim (128): exercises the
    # TPU-kernel selection branches (Pallas decode/flash gating keys on
    # head_dim % 128) at test sizes where tiny-test's Dh=16 cannot.
    "bcg-tpu/tiny-dh128": ModelSpec(
        name="bcg-tpu/tiny-dh128",
        vocab_size=512, hidden_size=256, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=128,
        intermediate_size=512, qk_norm=True, max_position=2048,
    ),
    # Mid-size random-weight spec for single-chip benchmarking.
    "bcg-tpu/bench-1b": ModelSpec(
        name="bcg-tpu/bench-1b",
        vocab_size=151936, hidden_size=2048, num_layers=16,
        num_heads=16, num_kv_heads=8, head_dim=128,
        intermediate_size=6144, qk_norm=True, max_position=8192,
    ),
    # Qwen3-8B dims with random weights: real-scale single-chip serving
    # (int8 weights ~8.8 GB incl. the bf16 embedding — fits one v5e-16GB
    # chip with the KV cache and a reduced prefix-cache budget).
    "bcg-tpu/bench-8b": ModelSpec(
        name="bcg-tpu/bench-8b",
        vocab_size=151936, hidden_size=4096, num_layers=36,
        num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=12288, qk_norm=True, max_position=8192,
    ),
    # Olmo-Hybrid-7B dims with random weights (int8: 7.8 GB of weights;
    # per row 53 MB of float32 recurrent state beside the KV of its 8
    # full-attention layers).
    "bcg-tpu/bench-olmo-hybrid-7b": _olmo_hybrid("bcg-tpu/bench-olmo-hybrid-7b", 8192),
    # One period of a hybrid at tiny widths, key dim != value dim: the
    # CPU tests' stand-in for the spec above.
    "bcg-tpu/tiny-hybrid": ModelSpec(
        name="bcg-tpu/tiny-hybrid",
        vocab_size=512, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=4, head_dim=16, intermediate_size=128,
        rope_theta=None, qk_norm="full", norm_placement="post",
        max_position=2048,
        layer_types=(LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,),
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    ),
    # Qwen3-14B / 32B dims with random weights: the reference's larger
    # presets (config.py:20-25) as hermetic multi-chip TP targets —
    # int8 14B (~15 GB) needs tp>=2 on 16 GB chips, 32B tp>=4.  Shard
    # layouts validated on the virtual CPU mesh (tests/test_parallel.py,
    # __graft_entry__.dryrun_multichip).
    "bcg-tpu/bench-14b": ModelSpec(
        name="bcg-tpu/bench-14b",
        vocab_size=151936, hidden_size=5120, num_layers=40,
        num_heads=40, num_kv_heads=8, head_dim=128,
        intermediate_size=17408, qk_norm=True, max_position=8192,
    ),
    "bcg-tpu/bench-32b": ModelSpec(
        name="bcg-tpu/bench-32b",
        vocab_size=151936, hidden_size=5120, num_layers=64,
        num_heads=64, num_kv_heads=8, head_dim=128,
        intermediate_size=25600, qk_norm=True, max_position=8192,
    ),
}


def spec_for_model(model_name: str) -> Optional[ModelSpec]:
    return MODEL_SPECS.get(model_name)
