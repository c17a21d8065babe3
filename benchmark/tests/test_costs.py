"""The operation and byte functions of the cost model that the 8B
configuration's file names, against hand counts at the 8B and 14B
shapes."""

import json
import os

import pytest

from lib import costs as lookup
from lib import peaks
from readers.work import Recorded

HERE = os.path.dirname(os.path.abspath(__file__))
Q8 = json.load(open(os.path.join(HERE, "..", "configs", "qwen3-8b-int8.json")))
Q14 = dict(Q8, hidden_size=5120, intermediate_size=17408, num_hidden_layers=40,
           num_attention_heads=40, num_key_value_heads=8)
costs = lookup.module_for(Q8)


def _call(prompt_lens, passes=(), steps=0):
    return Recorded(list(prompt_lens), list(passes), steps)


def test_matmul_params_8b_by_hand():
    # wq 4096x4096, wk/wv 4096x1024 each, wo 4096x4096, three 4096x12288.
    per_layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12288
    assert per_layer == 192_937_984
    assert costs.matmul_params_per_layer(Q8) == per_layer
    assert costs.block_matmul_params(Q8) == 36 * per_layer == 6_945_767_424
    assert costs.head_params(Q8) == 4096 * 151936 == 622_329_856


def test_matmul_params_14b_by_hand():
    # q is 40 heads x 128 = 5120 wide, kv 8 x 128 = 1024.
    per_layer = 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120 + 3 * 5120 * 17408
    assert per_layer == 330_301_440
    assert costs.block_matmul_params(Q14) == 40 * per_layer


def test_prefill_flops_one_row_by_hand():
    n = 3000
    attn = 4 * 128 * 32 * 36 * (n * (n + 1) // 2)      # QK and PV, causal pairs
    assert costs.prefill_attention_flops(Q8, [n]) == attn
    want = 2 * 6_945_767_424 * n + 2 * 622_329_856 + attn
    assert costs.prefill_flops(Q8, _call([n])) == want
    # ten such rows: 4.4e14 operations, 1.1 s of one v5e's int8 peak
    assert costs.prefill_flops(Q8, _call([n] * 10)) == 10 * want
    assert 1.0 < 10 * want / peaks.matmul_peak("TPU v5 lite", "int8") < 1.3


def test_decode_flops_and_bytes_by_hand():
    n, m = 3000, 299
    pairs = m * n + m * (m + 1) // 2
    assert costs.decode_attention_flops(Q8, [n], [m]) == 4 * 128 * 32 * 36 * pairs
    assert costs.decode_flops(Q8, _call([n], [m], m)) == (
        2 * (6_945_767_424 + 622_329_856) * m + 4 * 128 * 32 * 36 * pairs)
    # int8 weights: a byte a parameter, plus an f32 scale per output channel
    channels = 36 * (4096 + 1024 + 1024 + 4096 + 12288 + 12288 + 4096) + 151936
    w = 6_945_767_424 + 622_329_856 + 4 * channels
    assert costs.weight_bytes(Q8, "int8") == w
    assert costs.weight_bytes(Q8, "bfloat16") == 2 * (6_945_767_424 + 622_329_856)
    # int8 KV: 1024 bytes of K and of V per token per layer, 8 f32 scales each
    per_tok = 2 * (1024 + 4 * 8) * 36
    assert costs.kv_bytes_per_token(Q8, "int8") == per_tok == 76_032
    assert costs.decode_bytes(Q8, _call([n], [m], m)) == m * w + per_tok * pairs


def test_kernel_needs_and_roofs():
    n, m = 3000, 299
    flash = costs.flash_prefill_kernel(Q8, _call([n]))
    assert flash["flops"] == costs.prefill_attention_flops(Q8, [n])
    assert flash["bytes"] == 2 * n * (2 * 4096 + 2 * 1024) * 36
    table = peaks.peaks_for("TPU v5 lite")
    _, bound = lookup.roofline_seconds(flash["flops"], flash["bytes"],
                                      table["bf16_flops"], table["hbm_bytes_per_s"])
    assert bound == "compute"
    dec = costs.decode_attention_kernel(Q8, _call([n], [m], m))
    _, bound = lookup.roofline_seconds(dec["flops"], dec["bytes"],
                                      table["bf16_flops"], table["hbm_bytes_per_s"])
    assert bound == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(RuntimeError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.matmul_peak("TPU v5 lite", "int8") == 393e12
