"""Operations and bytes the Solar-Open2 decoder needs, from shapes alone:
the cost model of the configurations whose file says ``"costs":
"solar_open2"`` (found by ``lib/costs.py::module_for``).

The model has layers of two kinds (``cfg["layer_types"]``): gated full
attention (grouped-query, an output gate ``x W_g`` beside its four
projections) and KDA layers (the channel-gated delta rule), each
followed by an expert layer: a router over ``router_experts``, a shared
expert, and the ``n_routed_experts`` experts this chip holds.  Counts
are of what the layer's EQUATIONS need at the TRUE lengths, whatever
algorithm implements them: pad positions, the chunkwise form's
intra-chunk matrices, the grouped matmul's padding rows and allocated
but unused cache slots are not work.  A multiply-add is two operations.

The routed experts' work is a token's count of chosen HELD experts
times one expert's SwiGLU.  That count is the routing's, and a recorded
call (``readers/work.py``) carries none of the program's counters; but
the configuration's ``screen`` holds the window's readings to their
bands (``lib/window.py``), so where the file bands a count, its band's
lower edge is used: a lower bound of the work, within the band's width
of it.  A count the file does not band is taken at the routing's
expectation under uniform routing (``k * held / router_experts`` a token
and layer; of the held experts, the share a decode step's ``B`` rows
hit, ``1 - (1 - 1/router_experts)^(B k)``).

The KDA rule, per head and token: ``7 dk dv`` operations (the channel
decay of ``S``, ``S k``, the rank-one update, ``S q``); bytes q, k, v, g
(float32, a value per channel) and beta in, o out per token, the state
in and out once per chunk program, row and layer.

The two expert kernels multiply int8 by int8, which the chip runs at
twice its bfloat16 rate (``lib/peaks.py``), while a kernel's roofline is
read against the bfloat16 peak (``readers/kernel_roofline.py``): their
``flops`` count an int8 multiply-add as ONE bfloat16-rate operation, so
that the share stays one of the kernel's own roof.

Every function a metric's file can name takes ``(cfg, call)``: ``call``
is one recorded engine call (``prompt_lens``, ``passes``, ``steps``).
"""

from __future__ import annotations

_FULL, _KDA = "full_attention", "kda"
_WIDTH = {"bfloat16": 2, "float32": 4}
HITS, SLOTS = "engine.moe.decode.experts_hit", "engine.moe.decode.expert_slots"
ASSIGNED, TOKENS = "engine.moe.prefill.assignments", "engine.moe.prefill.tokens"


def dims(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    H, dk = lin["num_heads"], lin["head_dim"]
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
        "Dh": cfg["head_dim"],
        "q": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
        "Hl": H, "dk": dk, "dv": dk, "K": H * dk, "Vl": H * dk,
        "r": cfg["kda_gate_rank"], "taps": lin["short_conv_kernel_size"],
        "E": cfg["router_experts"], "held": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "F": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "full": cfg["layer_types"].count(_FULL), "kda": cfg["layer_types"].count(_KDA),
        "layers": len(cfg["layer_types"]),
    }


def _band_low(cfg: dict, numerator: str, denominator: str):
    """The lowest lower edge of the file's screens on this ratio, or
    None where no screen bands it from above 0."""
    lows = [s["band"][0] for s in cfg.get("screen", [])
            if s["numerator"] == numerator and s["denominator"] == denominator]
    return min(lows) if lows and min(lows) > 0 else None


def held_per_token(cfg: dict) -> float:
    """Chosen held experts a token and layer."""
    d = dims(cfg)
    low = _band_low(cfg, ASSIGNED, TOKENS)
    return low if low is not None else d["k"] * d["held"] / d["E"]


def hit_share(cfg: dict, rows: int) -> float:
    """Of the held experts, the share a decode step's ``rows`` hit."""
    d = dims(cfg)
    low = _band_low(cfg, HITS, SLOTS)
    return low if low is not None else 1.0 - (1.0 - 1.0 / d["E"]) ** (rows * d["k"])


def expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["D"] * d["F"]


def _layer_matmuls(cfg: dict) -> dict:
    """``kind -> (weights served quantised, weights that stay bfloat16,
    output channels of the quantised ones)`` of one layer, the held
    experts left out."""
    d = dims(cfg)
    shared = d["shared"] * expert_params(cfg)
    shared_out = d["shared"] * (2 * d["F"] + d["D"])
    router = d["D"] * d["E"]
    return {
        _FULL: (d["D"] * (2 * d["q"] + 2 * d["kv"]) + d["q"] * d["D"] + shared, router,
                2 * d["q"] + 2 * d["kv"] + d["D"] + shared_out),
        _KDA: (d["D"] * (2 * d["K"] + d["Vl"]) + d["Vl"] * d["D"] + shared,
               router + d["D"] * d["Hl"] + d["D"] * 2 * d["r"] + d["r"] * (d["K"] + d["Vl"]),
               2 * d["K"] + d["Vl"] + d["D"] + shared_out),
    }


def block_matmul_params(cfg: dict) -> int:
    """Weights every token passes through: every block matmul but the
    held experts, the bfloat16 ones included."""
    per = _layer_matmuls(cfg)
    return sum(per[kind][0] + per[kind][1] for kind in cfg["layer_types"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def routed_flops(cfg: dict, tokens: int) -> int:
    """The held experts' SwiGLUs over ``tokens`` positions in every layer."""
    return int(2 * expert_params(cfg) * held_per_token(cfg) * dims(cfg)["layers"] * tokens)


def kda_flops(cfg: dict, tokens: int) -> int:
    """The recurrence over ``tokens`` positions in every KDA layer, with
    the conv's taps (two operations a tap and channel)."""
    d = dims(cfg)
    per_token = d["Hl"] * 7 * d["dk"] * d["dv"] + 2 * d["taps"] * (2 * d["K"] + d["Vl"])
    return per_token * d["kda"] * tokens


def prefill_attention_flops(cfg: dict, prompt_lens) -> int:
    """Causal attention over each row's true prompt in the full layers:
    QK^T and PV, each 2*Dh operations per (query, key) pair."""
    d = dims(cfg)
    pairs = sum(n * (n + 1) // 2 for n in prompt_lens)
    return 4 * d["Dh"] * d["H"] * d["full"] * pairs


def prefill_flops(cfg: dict, call) -> int:
    """One prefill call: every prompt token through both kinds of layer
    and the held experts it chose, the head once per row."""
    tokens = sum(call.prompt_lens)
    return (
        2 * block_matmul_params(cfg) * tokens
        + 2 * head_params(cfg) * len(call.prompt_lens)
        + prefill_attention_flops(cfg, call.prompt_lens)
        + kda_flops(cfg, tokens)
        + routed_flops(cfg, tokens)
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
        + kda_flops(cfg, passes)
        + routed_flops(cfg, passes)
    )


def weight_bytes(cfg: dict, weight_dtype: str) -> int:
    """Bytes of the weights every decode step streams, the held experts
    left out: the block matmuls and the head at the served width (int8:
    plus an f32 scale per output channel; the router, beta and the
    low-rank pairs stay bfloat16).  The embedding is a gather of B rows:
    not counted."""
    per = _layer_matmuls(cfg)
    quantised = sum(per[k][0] for k in cfg["layer_types"]) + head_params(cfg)
    plain = sum(per[k][1] for k in cfg["layer_types"])
    if weight_dtype == "int8":
        channels = sum(per[k][2] for k in cfg["layer_types"]) + cfg["vocab_size"]
        return quantised + 4 * channels + 2 * plain
    raise ValueError(f"no byte count for weight dtype {weight_dtype!r}")


def expert_bytes(cfg: dict) -> int:
    """One held expert's int8 matrices and their f32 channel scales."""
    d = dims(cfg)
    return expert_params(cfg) + 4 * (2 * d["F"] + d["D"])


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """K and V of one token in every FULL layer; int8 adds one f32
    scale per token per kv head for each of K and V."""
    d = dims(cfg)
    if kv_dtype == "int8":
        return 2 * (d["kv"] + 4 * d["Hkv"]) * d["full"]
    raise ValueError(f"no byte count for kv dtype {kv_dtype!r}")


def state_bytes_per_row(cfg: dict) -> int:
    """One row's recurrent state and conv tail in every KDA layer."""
    d = dims(cfg)
    state = d["Hl"] * d["dv"] * d["dk"] * _WIDTH[cfg["state_dtype"]]
    tail = (d["taps"] - 1) * (2 * d["K"] + d["Vl"]) * 2
    return (state + tail) * d["kda"]


def moe_decode_kernel(cfg: dict, call) -> dict:
    """The decode expert kernel over one loop, every layer: each step
    streams the held experts its rows hit (:func:`hit_share` of them),
    once for all rows; operations are the rows' chosen held experts'
    SwiGLUs (int8, counted at the bfloat16 rate)."""
    d = dims(cfg)
    hit = hit_share(cfg, len(call.prompt_lens)) * d["held"] * d["layers"] * call.steps
    return {
        "flops": routed_flops(cfg, sum(call.passes)) // 2,
        "bytes": int(hit * expert_bytes(cfg)),
    }


def decode_bytes(cfg: dict, call) -> int:
    """One decode loop of ``call.steps`` iterations: every iteration
    streams the weights once and the held experts its rows hit; pass j
    of a row reads the K/V of its n + j + 1 tokens in the full layers,
    and reads and writes its state in the KDA ones."""
    return (
        call.steps * weight_bytes(cfg, cfg["weight_dtype"])
        + moe_decode_kernel(cfg, call)["bytes"]
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


def kda_prefill_kernel(cfg: dict, call) -> dict:
    """The KDA prefill kernel over one call, the KDA layers: ``7 dk dv``
    operations a head and token; bytes q, k, v (bf16), g (f32, a value a
    channel) and beta (f32) read and o (bf16) written per token and
    head, and the float32 state read and written once per chunk
    program, row and layer."""
    d = dims(cfg)
    tokens, rows = sum(call.prompt_lens), len(call.prompt_lens)
    per_token_head = 2 * (2 * d["dk"] + d["dv"]) + 4 * d["dk"] + 4 + 2 * d["dv"]
    state = d["dv"] * d["dk"] * _WIDTH[cfg["state_dtype"]]
    return {
        "flops": 7 * d["dk"] * d["dv"] * d["Hl"] * d["kda"] * tokens,
        "bytes": d["Hl"] * d["kda"] * (
            per_token_head * tokens + 2 * state * rows * prefill_programs(cfg, call)),
    }


def moe_prefill_kernel(cfg: dict, call) -> dict:
    """The grouped expert matmul over one prefill call, every layer:
    the real tokens' chosen held experts' SwiGLUs (int8, counted at the
    bfloat16 rate); bytes each held expert's matrices once a chunk
    program, the int8 rows in (the input, the hidden row) and the
    float32 rows out (the hidden row, the output)."""
    d = dims(cfg)
    rows = held_per_token(cfg) * d["layers"] * sum(call.prompt_lens)
    return {
        "flops": routed_flops(cfg, sum(call.prompt_lens)) // 2,
        "bytes": int(d["held"] * d["layers"] * prefill_programs(cfg, call) * expert_bytes(cfg)
                     + rows * (d["D"] + d["F"] + 4 * d["F"] + 4 * d["D"])),
    }
