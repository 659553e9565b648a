"""What the two Llama cells read, pinned as the code gave it before the
Llama-specific part moved into ``bench/models/llama.py``: the digest of
every leaf of the served tree, the reference's float32 logits and its
float8 control, the operation and byte counts, and three readers on a
hand-made trace.  The seeds of every recorded run depend on these."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

import harness
import trace_reduce as tr
from conftest import TINY_CONF

LLAMA = harness.model_module("llama")
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")

LEAVES = {
    "float32": {
        "['embed']": "285435f68308d20d",
        "['layers']['attn']['wk']": "01836408bab7e2c3",
        "['layers']['attn']['wo']": "3f0f65e56dff318f",
        "['layers']['attn']['wq']": "ca24bfe30b80e7e3",
        "['layers']['attn']['wv']": "0d61cb785642626d",
        "['layers']['ln1']": "32de6d83b92acea3",
        "['layers']['ln2']": "16a0a7a4f811799c",
        "['layers']['mlp']['w_down']": "16c8b5f56ca3a73b",
        "['layers']['mlp']['w_gate']": "df14eee73ac1b931",
        "['layers']['mlp']['w_up']": "352416bab2f91192",
        "['ln_f']": "982cbf0964c8f734",
        "['unembed']": "c9dff3ad7d2429e3"},
    "bfloat16": {
        "['embed']": "5b6a01d1ec8f40c0",
        "['layers']['attn']['wk']": "ee278314a7eae83c",
        "['layers']['attn']['wo']": "c58dfbed7904faf2",
        "['layers']['attn']['wq']": "39c3db7c4fedf57c",
        "['layers']['attn']['wv']": "be4d1f2de859f4a9",
        "['layers']['ln1']": "758ded317996494c",
        "['layers']['ln2']": "c4fa48abb2e6c784",
        "['layers']['mlp']['w_down']": "421f19d5c9e9b8cb",
        "['layers']['mlp']['w_gate']": "e5261570619c2f2f",
        "['layers']['mlp']['w_up']": "18be93f5c92c8a03",
        "['ln_f']": "99edd9dd4669c799",
        "['unembed']": "ad703bd226738fde"},
}
LOGITS = {
    ("float32", "f32"): ["28c5a52476b31984", "212cf63af631e71f"],
    ("float32", "fp8"): ["050cc480207e4532", "ef25c77d3a781257"],
    ("bfloat16", "f32"): ["cc5dc573c2298fd6", "f13d9c1fdd071052"],
    ("bfloat16", "fp8"): ["bbc738c9015d7003", "9b3f6166c94d9203"],
}
LANES = [(0, 128), (17, 1), (1023, 1), (2000, 96), (4095, 1), (5, 0)]
COUNTS = {
    "yi-34b": {"model_flops": 1560285626368.0, "attn_flops": 6022553600.0,
               "attn_bytes": 36663296.0},
    "deepseek-llm-7b": {"model_flops": 576113016832.0,
                        "attn_flops": 3441459200.0,
                        "attn_bytes": 124338176.0},
}
READERS = {
    "yi-34b": {"rsa_gemm_roofline": 1.6605033542556566,
               "paged_attn_roofline": 0.017763305901965377,
               "step_mfu": 1.6394504056852792},
    "deepseek-llm-7b": {"rsa_gemm_roofline": 0.6951826165926158,
                        "paged_attn_roofline": 0.04784968205128205,
                        "step_mfu": 0.6025193544771573},
}


def _digest(a):
    a = np.asarray(a)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:16]


def _tiny(dtype):
    return LLAMA.arch_config(dict(TINY_CONF, torch_dtype=dtype))


def _real(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        conf = json.load(f)
    return conf, LLAMA.arch_config(conf)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_tree_is_unchanged(dtype):
    params = LLAMA.make_params(11, _tiny(dtype))
    got = {jax.tree_util.keystr(p): _digest(leaf)
           for p, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert got == LEAVES[dtype]


@pytest.mark.parametrize("dtype, precision", sorted(LOGITS))
def test_reference_logits_are_unchanged(dtype, precision):
    cfg = _tiny(dtype)
    rng = np.random.default_rng(20261018)
    seqs = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (41, 70)]
    rows = [np.arange(len(s)) for s in seqs]
    out = LLAMA.served_logits(11, cfg, seqs, rows, precision=precision)
    assert [_digest(x) for x in out] == LOGITS[(dtype, precision)]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_are_unchanged(name):
    _, cfg = _real(name)
    got = {"model_flops": LLAMA.model_flops(cfg, LANES, 5),
           "attn_flops": LLAMA.attn_flops(cfg, LANES),
           "attn_bytes": LLAMA.attn_bytes(cfg, LANES, 2)}
    assert got == COUNTS[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_are_unchanged(name):
    """One RSA GEMM, one paged decode and one paged prefill kernel of a
    second each; a chunk step and a decode step over the Llama sites."""
    conf, cfg = _real(name)
    S = 1e9
    ops = [tr.Op("%rsa_gemm.1 = bf16[8,8]{1,0} custom-call(%a)", 0, S),
           tr.Op("%paged_attention.2 = bf16[4,8]{1,0} custom-call(%q)",
                 S, 2 * S),
           tr.Op("%paged_prefill_attention.3 = bf16[4,8]{1,0} "
                 "custom-call(%q)", 2 * S, 3 * S)]
    d, q, kv, ff, V = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff, \
        cfg.vocab_size
    cell = harness.Cell(name, 1, "x", conf, {}, {}, [], [], LLAMA)
    run = harness.RunData(cell, cfg, harness.peaks_for("TPU v5 lite"))
    run.trace = tr.Trace({0: ops}, [("bench.window", 0, 3 * S)])
    run.trace_window = (0, 3 * S)
    run.step_walls = [0.25, 0.5]
    run.calls = [
        harness.StepCall("prefill_chunk", np.array([0, 128, 0, 2000]),
                         np.array([128, 128, 0, 96])),
        harness.StepCall("decode", np.array([17, 1023, 0, 4095]),
                         np.array([1, 1, 0, 1]))]
    run.sites = {
        "decode": [("layer.attn.q", 4, d, q), ("layer.attn.k", 4, d, kv),
                   ("layer.attn.v", 4, d, kv), ("layer.attn.out", 4, q, d),
                   ("layer.mlp.gate", 4, d, ff), ("layer.mlp.up", 4, d, ff),
                   ("layer.mlp.down", 4, ff, d), ("unembed", 4, d, V)],
        "prefill_chunk": [("layer.attn.q", 512, d, q),
                          ("layer.mlp.down", 512, ff, d),
                          ("unembed", 4, d, V)]}
    got = {m: harness.reader(m)(run) for m in READERS[name]}
    assert got == READERS[name]
