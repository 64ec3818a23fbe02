import json
import os

import pytest

from benchmarks import kernel_costs

ROOT = os.path.dirname(os.path.dirname(__file__))


def _cfg(name):
    with open(os.path.join(ROOT, "configs", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_trace_names_map_to_kernels():
    assert kernel_costs.kernel_of("paged_attention.9") == "paged_attention"
    assert kernel_costs.kernel_of("ragged_paged_attention.10") == "ragged_paged_attention"
    assert kernel_costs.kernel_of("fusion.190") is None
    assert kernel_costs.is_attention_kernel("ragged_paged_attention.10")
    assert not kernel_costs.is_attention_kernel("copy.245")


def test_decode_bytes_are_the_live_keys_and_values():
    # phi-2's sizes: 32 layers, 32 kv heads of 80, no window
    cfg = {"hidden_size": 2560, "num_attention_heads": 32,
           "num_key_value_heads": 32, "num_hidden_layers": 32}
    c = kernel_costs.decode_attention_cost(cfg, [100], 1)
    kv = 32 * 2 * 100 * 32 * 80 * 2
    qo = 32 * 2 * 32 * 80 * 2
    assert c["bytes"] == kv + qo
    assert c["flops"] == 32 * 4 * 100 * 32 * 80
    # the second step of a scan sees one token more
    c2 = kernel_costs.decode_attention_cost(cfg, [100], 1, first_step=1)
    assert c2["bytes"] - c["bytes"] == 32 * 2 * 1 * 32 * 80 * 2


def test_the_window_caps_what_is_live():
    cfg = _cfg("mistral-7b-int8")  # window 4096, 8 kv heads of 128
    a = kernel_costs.decode_attention_cost(cfg, [4096], 1)
    b = kernel_costs.decode_attention_cost(cfg, [7000], 1)
    assert a == b
    assert a["bytes"] == pytest.approx(32 * (2 * 4096 * 8 * 128 * 2 + 2 * 32 * 128 * 2))
