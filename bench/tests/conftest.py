"""Tiny cells for the benchmark's own tests, which run on the CPU."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import pytest  # noqa: E402

TINY_CONF = {
    "name": "tiny", "model_type": "llama", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "vocab_size": 512,
    "serving": {"slots": 4, "max_len": 128, "block_size": 16,
                "prefill_chunk": 16, "kv_pages": 32,
                "max_prefills_per_step": 4},
}

TINY_MIX = {
    "arrivals": {"kind": "poisson", "rate_per_s": 8.0},
    "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
}


@pytest.fixture
def tiny_cell():
    import harness
    e2e = [{"name": n, "unit": u} for n, u in
           (("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
            ("out_tok_per_s", "tokens/s"), ("setup_s", "s"))]
    return harness.Cell("tiny.chat", 1, "tiny", dict(TINY_CONF),
                        dict(TINY_MIX), {"max_logit_gap": 1e-3}, e2e, [],
                        harness.model_module("llama"))
