"""The operation and byte counts against shapes worked out by hand: the
shared ones in ``costs`` and the Llama module's."""

import pytest

import costs
import harness

LLAMA = harness.model_module("llama")


def _cfg(name):
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "configs", f"{name}.json")
    with open(path) as f:
        return LLAMA.arch_config(json.load(f))


def test_gemm_counts():
    assert costs.gemm_flops(3, 4, 5) == 120
    # A 3x4 and B 4x5 in bf16, C 3x5 in f32
    assert costs.gemm_bytes(3, 4, 5, 2, 4) == (12 + 20) * 2 + 15 * 4


@pytest.mark.parametrize("name, params", [
    # q 7168x7168, k and v 7168x1024, o 7168x7168, three 7168x20480
    ("yi-34b", 51380224 + 2 * 7340032 + 51380224 + 3 * 146800640),
    # four 4096x4096 (MHA), three 4096x11008
    ("deepseek-llm-7b", 4 * 16777216 + 3 * 45088768),
])
def test_layer_params(name, params):
    assert LLAMA.layer_params(_cfg(name)) == params


def test_attention_counts():
    cfg = _cfg("yi-34b")
    # a 3-row chunk at positions 10, 11, 12 sees 11 + 12 + 13 keys
    assert costs.attended_keys(10, 3) == 36
    # one decode row at kv 99: 100 keys, 4 * 56 heads * 128 flops per key
    assert LLAMA.attn_flops(cfg, [(99, 1)]) == 100 * 4 * 56 * 128
    # bytes: 100 K and V rows of 8 heads x 128 in bf16, one q and one o row
    assert LLAMA.attn_bytes(cfg, [(99, 1)], 2) == \
        100 * 2 * 8 * 128 * 2 + 2 * 56 * 128 * 2
    assert LLAMA.attn_flops(cfg, [(5, 0)]) == 0


def test_model_flops_and_bounds():
    cfg = _cfg("deepseek-llm-7b")
    lanes = [(0, 2), (7, 1)]
    want = (6 * (2 * LLAMA.layer_params(cfg) * 3
                 + 4 * 32 * 128 * (1 + 2 + 8))
            + 2 * 4096 * 102400 * 2)
    assert LLAMA.model_flops(cfg, lanes, 2) == want
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_seconds(200.0, 10.0, peaks) == 2.0
    assert costs.least_seconds(100.0, 50.0, peaks) == 5.0
