"""The plain reference against the program's own forward pass at a tiny
size on the CPU: the same weights (the bench's seeded tree, whose norm
``scale`` the program applies as ``1 + scale`` and the reference takes as
the HF weight ``1 + scale``) must give the same logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import TINY_CONF

LLAMA = harness.model_module("llama")


@pytest.fixture(scope="module")
def tiny():
    cfg = LLAMA.arch_config(TINY_CONF)
    return cfg, LLAMA.make_params(11, cfg)


def test_reference_matches_program_forward(tiny):
    from repro import dispatch
    from repro.models import transformer
    cfg, params = tiny
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (37, 90)]
    rows = [np.arange(len(s)) for s in seqs]
    ref = LLAMA.served_logits(11, cfg, seqs, rows)
    with dispatch.use(execute="xla"), jax.default_matmul_precision("highest"):
        for s, want in zip(seqs, ref):
            toks = jnp.asarray(np.concatenate([s, [0]])[None])
            got = transformer.logits(params, {"tokens": toks}, cfg)[0]
            np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                       atol=2e-4 * np.abs(want).max())


def test_layer_weights_match_the_served_tree(tiny):
    """make_layer (the reference's weights) equals make_params (the
    program's) leaf for leaf, the norms mapped through 1 + scale."""
    from weights import seed_key
    cfg, params = tiny
    for layer in range(cfg.num_layers):
        w = LLAMA.make_layer(seed_key(11), cfg, layer)
        lp = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
        flat = {**lp["attn"], **lp["mlp"]}
        for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(np.asarray(flat[name], np.float32),
                                          np.asarray(w[name]))
        for name in ("ln1", "ln2"):
            np.testing.assert_array_equal(
                1.0 + np.asarray(lp[name], np.float32), np.asarray(w[name]))


def test_fp8_control_is_coarser(tiny):
    cfg, _ = tiny
    seq = [np.arange(1, 60, dtype=np.int32) % cfg.vocab_size]
    rows = [np.arange(59)]
    f32 = LLAMA.served_logits(11, cfg, seq, rows)[0]
    fp8 = LLAMA.served_logits(11, cfg, seq, rows, precision="fp8")[0]
    err = np.abs(fp8 - f32).max() / np.abs(f32).max()
    assert 1e-3 < err < 0.5, err
