"""Operations and bytes the hybrid decoder needs, from shapes alone: the
cost model of the configurations whose file says ``"costs":
"olmo_hybrid"`` (found by ``lib/costs.py::module_for``).

The model has layers of two kinds (``cfg["layer_types"]``): full
attention (MHA or GQA, as the dense decoder's) and gated delta-rule
layers, each followed by a SwiGLU MLP.  Counts are of what the layer's
EQUATIONS need at the TRUE lengths, whatever algorithm implements them:
pad positions, the chunkwise form's intra-chunk matrices, recomputation
and allocated-but-unused cache slots are not work.  A multiply-add is
two operations.

The delta rule, per head and token: ``6 dk dv`` operations (decay and
project ``exp(g) S k``, the rank-one update, the read-out ``S q``);
bytes q, k, v, g, beta in and o out per token, and the state ``S`` in
and out once per program call, row and layer.

Every function a metric's file can name takes ``(cfg, call)``: ``call``
is one recorded engine call as ``readers/work.py`` hands it over
(``prompt_lens`` the rows' true prompt lengths, ``passes`` the forward
passes each row needed in the decode loop, ``steps`` the loop's
iterations); dtypes are read from ``cfg``.
"""

from __future__ import annotations

_FULL, _LINEAR = "full_attention", "linear_attention"
_WIDTH = {"bfloat16": 2, "float32": 4}


def dims(cfg: dict) -> dict:
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg["hidden_size"] // H
    Hl = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {
        "D": cfg["hidden_size"], "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "H": H, "Hkv": Hkv, "Dh": Dh, "q": H * Dh, "kv": Hkv * Dh,
        "Hl": Hl, "dk": dk, "dv": dv, "K": Hl * dk, "Vl": Hl * dv,
        "taps": cfg["linear_conv_kernel_dim"],
        "full": cfg["layer_types"].count(_FULL),
        "linear": cfg["layer_types"].count(_LINEAR),
    }


def _layer_matmuls(cfg: dict) -> dict:
    """``kind -> (weights that are served quantised, weights that stay
    bfloat16, output channels of the quantised ones)`` of one layer."""
    d = dims(cfg)
    mlp, mlp_out = 3 * d["D"] * d["F"], 2 * d["F"] + d["D"]
    return {
        _FULL: (d["D"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["D"] + mlp, 0,
                d["q"] + 2 * d["kv"] + d["D"] + mlp_out),
        _LINEAR: (d["D"] * (2 * d["K"] + 2 * d["Vl"]) + d["Vl"] * d["D"] + mlp,
                  2 * d["D"] * d["Hl"],
                  2 * d["K"] + 2 * d["Vl"] + d["D"] + mlp_out),
    }


def block_matmul_params(cfg: dict) -> int:
    """Weights of every block matmul, the two per-head gates included."""
    per = _layer_matmuls(cfg)
    return sum(per[kind][0] + per[kind][1] for kind in cfg["layer_types"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def delta_rule_flops(cfg: dict, tokens: int) -> int:
    """The recurrence over ``tokens`` positions in every linear layer,
    with the conv's taps (two operations a tap and channel)."""
    d = dims(cfg)
    per_token = d["Hl"] * 6 * d["dk"] * d["dv"] + 2 * d["taps"] * (2 * d["K"] + d["Vl"])
    return per_token * d["linear"] * tokens


def prefill_attention_flops(cfg: dict, prompt_lens) -> int:
    """Causal attention over each row's true prompt in the full layers:
    QK^T and PV, each 2*Dh operations per (query, key) pair."""
    d = dims(cfg)
    pairs = sum(n * (n + 1) // 2 for n in prompt_lens)
    return 4 * d["Dh"] * d["H"] * d["full"] * pairs


def prefill_flops(cfg: dict, call) -> int:
    """One prefill call: every prompt token through both kinds of
    layer, the head once per row."""
    tokens = sum(call.prompt_lens)
    return (
        2 * block_matmul_params(cfg) * tokens
        + 2 * head_params(cfg) * len(call.prompt_lens)
        + prefill_attention_flops(cfg, call.prompt_lens)
        + delta_rule_flops(cfg, tokens)
    )


def _context_tokens(prompt_lens, new_tokens) -> int:
    """Token j of a row attends its n prompt tokens and the j+1 tokens
    decoded so far (itself included)."""
    return sum(m * n + m * (m + 1) // 2 for n, m in zip(prompt_lens, new_tokens))


def decode_attention_flops(cfg: dict, prompt_lens, new_tokens) -> int:
    d = dims(cfg)
    return 4 * d["Dh"] * d["H"] * d["full"] * _context_tokens(prompt_lens, new_tokens)


def decode_flops(cfg: dict, call) -> int:
    """One decode loop: ``call.passes[i]`` forward passes of row i."""
    passes = sum(call.passes)
    return (
        2 * (block_matmul_params(cfg) + head_params(cfg)) * passes
        + decode_attention_flops(cfg, call.prompt_lens, call.passes)
        + delta_rule_flops(cfg, passes)
    )


def weight_bytes(cfg: dict, weight_dtype: str) -> int:
    """Bytes of the weights one decode step streams: the block matmuls
    and the head at the served width (int8: plus an f32 scale per output
    channel; the per-head gate projections stay bfloat16).  The
    embedding is a gather of B rows: not counted."""
    per = _layer_matmuls(cfg)
    quantised = sum(per[k][0] for k in cfg["layer_types"]) + head_params(cfg)
    plain = sum(per[k][1] for k in cfg["layer_types"])
    if weight_dtype == "bfloat16":
        return 2 * (quantised + plain)
    if weight_dtype == "int8":
        channels = sum(per[k][2] for k in cfg["layer_types"]) + cfg["vocab_size"]
        return quantised + 4 * channels + 2 * plain
    raise ValueError(f"no byte count for weight dtype {weight_dtype!r}")


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """K and V of one token in every FULL layer; int8 adds one f32
    scale per token per kv head for each of K and V."""
    d = dims(cfg)
    if kv_dtype == "bfloat16":
        return 2 * 2 * d["kv"] * d["full"]
    if kv_dtype == "int8":
        return 2 * (d["kv"] + 4 * d["Hkv"]) * d["full"]
    raise ValueError(f"no byte count for kv dtype {kv_dtype!r}")


def state_bytes_per_row(cfg: dict) -> int:
    """One row's recurrent state and conv tail in every linear layer."""
    d = dims(cfg)
    state = d["Hl"] * d["dv"] * d["dk"] * _WIDTH[cfg["state_dtype"]]
    tail = (d["taps"] - 1) * (2 * d["K"] + d["Vl"]) * 2
    return (state + tail) * d["linear"]


def decode_bytes(cfg: dict, call) -> int:
    """One decode loop of ``call.steps`` iterations: every iteration
    streams the weights once; pass j of a row reads the K/V of its
    n + j + 1 tokens in the full layers, and reads and writes its state
    in the linear ones."""
    return (
        call.steps * weight_bytes(cfg, cfg["weight_dtype"])
        + kv_bytes_per_token(cfg, cfg["kv_dtype"]) * _context_tokens(call.prompt_lens, call.passes)
        + 2 * state_bytes_per_row(cfg) * sum(call.passes)
    )


def flash_prefill_kernel(cfg: dict, call) -> dict:
    """The prefill attention kernel over one call, the full layers:
    operations as :func:`prefill_attention_flops`; bytes are q, k, v
    read and the output written once, bf16."""
    d = dims(cfg)
    tokens = sum(call.prompt_lens)
    return {
        "flops": prefill_attention_flops(cfg, call.prompt_lens),
        "bytes": 2 * tokens * (2 * d["q"] + 2 * d["kv"]) * d["full"],
    }


def decode_attention_kernel(cfg: dict, call) -> dict:
    """The decode attention kernel over one loop, the full layers: it
    reads each row's K/V cache up to the current token once per pass."""
    d = dims(cfg)
    return {
        "flops": decode_attention_flops(cfg, call.prompt_lens, call.passes),
        "bytes": kv_bytes_per_token(cfg, cfg["kv_dtype"]) * _context_tokens(call.prompt_lens, call.passes)
        + 2 * 2 * d["q"] * d["full"] * sum(call.passes),
    }


def prefill_programs(cfg: dict, call) -> int:
    """Chunk programs one prefill call sends: the left-padded window is
    a whole number of chunks and the leading all-pad ones are skipped,
    so the longest prompt decides."""
    chunk = cfg["program"]["engine"].get("prefill_chunk") or 0
    return -(-max(call.prompt_lens) // chunk) if chunk else 1


def gated_delta_prefill_kernel(cfg: dict, call) -> dict:
    """The delta-rule prefill kernel over one call, the linear layers:
    ``6 dk dv`` operations a head and token; bytes q, k, v (bf16), g and
    beta (f32) read and o (bf16) written per token and head, and the
    float32 state read and written once per chunk program, row and
    layer."""
    d = dims(cfg)
    tokens, rows = sum(call.prompt_lens), len(call.prompt_lens)
    per_token_head = 2 * (2 * d["dk"] + d["dv"]) + 2 * 4 + 2 * d["dv"]
    state = d["dv"] * d["dk"] * _WIDTH[cfg["state_dtype"]]
    return {
        "flops": 6 * d["dk"] * d["dv"] * d["Hl"] * d["linear"] * tokens,
        "bytes": d["Hl"] * d["linear"] * (
            per_token_head * tokens + 2 * state * rows * prefill_programs(cfg, call)),
    }
