"""Operations and bytes the Jamba2 decoder needs, from shapes alone: the
cost model of the configurations whose file says ``"costs": "jamba2"``
(found by ``lib/costs.py::module_for``).

The model has layers of two kinds: full attention (multi-query: one KV
head) at every layer ``i % attn_layer_period == attn_layer_offset`` and
Mamba-1 layers elsewhere, each followed by a dense SwiGLU; the head is
the embedding's transpose.  Counts are of what the layer's EQUATIONS
need at the TRUE lengths, whatever algorithm implements them: pad
positions, the kernel's padded groups and allocated but unused cache
slots are not work.  A multiply-add is two operations.

A Mamba layer, per token, beside its four matmuls: the conv's taps (two
operations a tap and channel, and its bias), and the scan's elementwise
work on the ``[channels, N]`` state: the discretisation (``delta A``, its
exponential, ``B (delta u)``), the update (a multiply and an add) and the
readout (``C S`` and its sum over N): ``7 N`` operations a channel, and
``delta u``, ``D u`` and its add: ``3`` more.  These are vector-unit
operations that a kernel's roofline reads against the matrix unit's peak
(``readers/kernel_roofline.py``): the scan kernel's share reads low by
construction.

Every function a metric's file can name takes ``(cfg, call)``: ``call``
is one recorded engine call (``prompt_lens``, ``passes``, ``steps``);
dtypes are read from ``cfg``.
"""

from __future__ import annotations

_WIDTH = {"bfloat16": 2, "float32": 4}


def dims(cfg: dict) -> dict:
    D, H, Hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = D // H
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    full = sum(i % period == offset for i in range(cfg["num_hidden_layers"]))
    return {
        "D": D, "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "H": H, "Hkv": Hkv, "Dh": Dh, "q": H * Dh, "kv": Hkv * Dh,
        "Di": cfg["mamba_expand"] * D, "N": cfg["mamba_d_state"],
        "R": cfg["mamba_dt_rank"], "taps": cfg["mamba_d_conv"],
        "full": full, "mamba": cfg["num_hidden_layers"] - full,
    }


def _layer_matmuls(cfg: dict) -> dict:
    """``kind -> weights of its matmuls`` of one layer, the SwiGLU's
    included."""
    d = dims(cfg)
    mlp = 3 * d["D"] * d["F"]
    return {
        "full": d["D"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["D"] + mlp,
        "mamba": (d["D"] * 2 * d["Di"] + d["Di"] * (d["R"] + 2 * d["N"])
                  + d["R"] * d["Di"] + d["Di"] * d["D"] + mlp),
    }


def block_matmul_params(cfg: dict) -> int:
    d, per = dims(cfg), _layer_matmuls(cfg)
    return d["full"] * per["full"] + d["mamba"] * per["mamba"]


def head_params(cfg: dict) -> int:
    """The head's matmul: the embedding's transpose, ``[D, V]``."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def scan_flops(cfg: dict, tokens: int) -> int:
    """The selective scan over ``tokens`` positions in every Mamba layer:
    ``7 N + 3`` operations a channel and token."""
    d = dims(cfg)
    return (7 * d["N"] + 3) * d["Di"] * d["mamba"] * tokens


def mamba_flops(cfg: dict, tokens: int) -> int:
    """A Mamba layer's work beside its matmuls, every Mamba layer: the
    conv with its bias and the scan."""
    d = dims(cfg)
    conv = (2 * d["taps"] + 1) * d["Di"] * d["mamba"] * tokens
    return conv + scan_flops(cfg, tokens)


def prefill_attention_flops(cfg: dict, prompt_lens) -> int:
    """Causal attention over each row's true prompt in the full layers:
    QK^T and PV, each 2*Dh operations per (query, key) pair."""
    d = dims(cfg)
    pairs = sum(n * (n + 1) // 2 for n in prompt_lens)
    return 4 * d["Dh"] * d["H"] * d["full"] * pairs


def prefill_flops(cfg: dict, call) -> int:
    """One prefill call: every prompt token through both kinds of layer,
    the head once per row."""
    tokens = sum(call.prompt_lens)
    return (
        2 * block_matmul_params(cfg) * tokens
        + 2 * head_params(cfg) * len(call.prompt_lens)
        + prefill_attention_flops(cfg, call.prompt_lens)
        + mamba_flops(cfg, tokens)
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
        + mamba_flops(cfg, passes)
    )


def weight_bytes(cfg: dict, weight_dtype: str) -> int:
    """Bytes of the weights one decode step streams: the block matmuls
    and the head (the embedding, read whole by the logits) at the served
    width.  The embedding's gather of B rows is not counted."""
    if weight_dtype != "bfloat16":
        raise ValueError(f"no byte count for weight dtype {weight_dtype!r}")
    return 2 * (block_matmul_params(cfg) + head_params(cfg))


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
    """One row's scan state and conv tail (bfloat16) in every Mamba layer."""
    d = dims(cfg)
    state = d["N"] * d["Di"] * _WIDTH[cfg["state_dtype"]]
    tail = (d["taps"] - 1) * d["Di"] * 2
    return (state + tail) * d["mamba"]


def decode_bytes(cfg: dict, call) -> int:
    """One decode loop of ``call.steps`` iterations: every iteration
    streams the weights once; pass j of a row reads the K/V of its
    n + j + 1 tokens in the full layers, and reads and writes its state
    in the Mamba ones."""
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


def mamba_prefill_kernel(cfg: dict, call) -> dict:
    """The selective-scan kernel over one call, the Mamba layers:
    operations as :func:`scan_flops`; bytes, each operand and result
    once: u and delta in and y out (float32) a token and channel, B and
    C in (float32) a token, and per chunk program, row and layer the
    float32 state in and out, with A and D once per program and layer."""
    d = dims(cfg)
    tokens, rows = sum(call.prompt_lens), len(call.prompt_lens)
    programs = prefill_programs(cfg, call)
    state = d["N"] * d["Di"] * _WIDTH[cfg["state_dtype"]]
    per_layer = (4 * (3 * d["Di"] + 2 * d["N"]) * tokens
                 + 2 * state * rows * programs
                 + 4 * (d["N"] + 1) * d["Di"] * programs)
    return {"flops": scan_flops(cfg, tokens), "bytes": per_layer * d["mamba"]}
