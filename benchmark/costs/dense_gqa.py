"""Operations and bytes the dense GQA decoder needs, from shapes alone:
the cost model of the configurations whose file says ``"costs":
"dense_gqa"`` (found by ``lib/costs.py::module_for``).

``cfg`` is a configuration file's dict (the published ``config.json``
keys).  Counts are of what the mathematics requires at the TRUE lengths:
pad positions, recomputation and allocated-but-unused cache slots are not
work.  A multiply-add is two operations.

The functions a metric's file can name (``need``, ``cost``) and the two
that ``round_mfu_pct`` reads (``prefill_flops``, ``decode_flops``) take
``(cfg, call)``: ``call`` is one recorded engine call as
``readers/work.py`` hands it over (``prompt_lens`` the rows' true prompt
lengths, ``passes`` the forward passes each row needed in the decode
loop, ``steps`` the loop's iterations); dtypes are read from ``cfg``.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    H, Hkv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {
        "D": cfg["hidden_size"], "F": cfg["intermediate_size"],
        "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
        "H": H, "Hkv": Hkv, "Dh": Dh, "q": H * Dh, "kv": Hkv * Dh,
    }


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights of the seven dense matmuls of one block."""
    d = dims(cfg)
    return d["D"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["D"] + 3 * d["D"] * d["F"]


def block_matmul_params(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * matmul_params_per_layer(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def prefill_attention_flops(cfg: dict, prompt_lens) -> int:
    """Causal attention over each row's true prompt: QK^T and PV, each
    2*Dh operations per (query, key) pair, n(n+1)/2 pairs per head."""
    d = dims(cfg)
    pairs = sum(n * (n + 1) // 2 for n in prompt_lens)
    return 4 * d["Dh"] * d["H"] * d["L"] * pairs


def prefill_flops(cfg: dict, call) -> int:
    """One prefill call: every prompt token through the blocks, the head
    once per row (only the last position is sampled from)."""
    tokens = sum(call.prompt_lens)
    return (
        2 * block_matmul_params(cfg) * tokens
        + 2 * head_params(cfg) * len(call.prompt_lens)
        + prefill_attention_flops(cfg, call.prompt_lens)
    )


def _context_tokens(prompt_lens, new_tokens) -> int:
    """Token j of a row attends its n prompt tokens and the j+1 tokens
    decoded so far (itself included)."""
    return sum(m * n + m * (m + 1) // 2 for n, m in zip(prompt_lens, new_tokens))


def decode_attention_flops(cfg: dict, prompt_lens, new_tokens) -> int:
    d = dims(cfg)
    return 4 * d["Dh"] * d["H"] * d["L"] * _context_tokens(prompt_lens, new_tokens)


def decode_flops(cfg: dict, call) -> int:
    """One decode loop: ``call.passes[i]`` forward passes of row i (the
    first token of a row is sampled from the prefill's logits, so a row
    that emitted m tokens made m - 1 passes)."""
    return (
        2 * (block_matmul_params(cfg) + head_params(cfg)) * sum(call.passes)
        + decode_attention_flops(cfg, call.prompt_lens, call.passes)
    )


def weight_bytes(cfg: dict, weight_dtype: str) -> int:
    """Bytes of the weights one decode step streams: seven matmuls per
    block and the head at the served width, plus int8's f32 scale per
    output channel.  The embedding is a gather of B rows: not counted."""
    d = dims(cfg)
    params = block_matmul_params(cfg) + head_params(cfg)
    if weight_dtype == "bfloat16":
        return 2 * params
    if weight_dtype == "int8":
        channels = d["L"] * (d["q"] + 2 * d["kv"] + d["D"] + 2 * d["F"] + d["D"]) + d["V"]
        return params + 4 * channels
    raise ValueError(f"no byte count for weight dtype {weight_dtype!r}")


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """K and V of one token in every layer; int8 adds one f32 scale per
    token per kv head for each of K and V."""
    d = dims(cfg)
    if kv_dtype == "bfloat16":
        return 2 * 2 * d["kv"] * d["L"]
    if kv_dtype == "int8":
        return 2 * (d["kv"] + 4 * d["Hkv"]) * d["L"]
    raise ValueError(f"no byte count for kv dtype {kv_dtype!r}")


def decode_bytes(cfg: dict, call) -> int:
    """One decode loop of ``call.steps`` iterations: every iteration
    streams the weights once; pass j of a row reads the K/V of its
    n + j + 1 tokens."""
    return (call.steps * weight_bytes(cfg, cfg["weight_dtype"])
            + kv_bytes_per_token(cfg, cfg["kv_dtype"]) * _context_tokens(call.prompt_lens, call.passes))


def flash_prefill_kernel(cfg: dict, call) -> dict:
    """The prefill attention kernel over one call, all layers: operations
    as :func:`prefill_attention_flops`; bytes are q, k, v read and the
    output written once, bf16."""
    d = dims(cfg)
    tokens = sum(call.prompt_lens)
    return {
        "flops": prefill_attention_flops(cfg, call.prompt_lens),
        "bytes": 2 * tokens * (2 * d["q"] + 2 * d["kv"]) * d["L"],
    }


def decode_attention_kernel(cfg: dict, call) -> dict:
    """The decode attention kernel over one loop, all layers: it reads
    each row's K/V cache up to the current token once per pass."""
    d = dims(cfg)
    return {
        "flops": decode_attention_flops(cfg, call.prompt_lens, call.passes),
        "bytes": kv_bytes_per_token(cfg, cfg["kv_dtype"]) * _context_tokens(call.prompt_lens, call.passes)
        + 2 * 2 * d["q"] * d["L"] * sum(call.passes),
    }
