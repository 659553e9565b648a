"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't crash collection
from hypothesis import given, settings, strategies as st

from repro.core.hw import IS, OS, WS
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# rsa_gemm
# ---------------------------------------------------------------------------

GEMM_SHAPES = [
    (128, 256, 128),      # exact blocks
    (256, 256, 256),
    (300, 520, 260),      # padding on every dim
    (64, 64, 64),         # smaller than one block
    (129, 257, 131),      # prime-ish
]


@pytest.mark.parametrize("mode", [OS, WS, IS], ids=["OS", "WS", "IS"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", GEMM_SHAPES)
def test_rsa_gemm_matches_ref(mode, dtype, shape):
    M, K, N = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (M, K), jnp.float32).astype(dtype)
    b = jax.random.normal(k2, (K, N), jnp.float32).astype(dtype)
    out = ops.rsa_gemm(a, b, block_m=128, block_n=128, block_k=256,
                       mode=mode)
    gold = ref.rsa_gemm_ref(a, b)
    tol = 2e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gold, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("blocks", [(128, 128, 128), (256, 128, 512),
                                    (128, 256, 128)])
def test_rsa_gemm_block_configs(blocks):
    """Different SARA-recommended tilings compute the same function."""
    bm, bn, bk = blocks
    a = jax.random.normal(jax.random.PRNGKey(1), (384, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (512, 384), jnp.float32)
    out = ops.rsa_gemm(a, b, block_m=bm, block_n=bn, block_k=bk, mode=OS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.rsa_gemm_ref(a, b)),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(M=st.integers(8, 300), K=st.integers(8, 300), N=st.integers(8, 300),
       mode=st.sampled_from([OS, WS, IS]))
def test_rsa_gemm_property_shapes(M, K, N, mode):
    a = jnp.ones((M, K), jnp.float32)
    b = jnp.full((K, N), 0.5, jnp.float32)
    out = ops.rsa_gemm(a, b, block_m=128, block_n=128, block_k=128,
                       mode=mode)
    assert out.shape == (M, N)
    np.testing.assert_allclose(np.asarray(out), 0.5 * K, rtol=1e-5)


@pytest.mark.parametrize("mode", [OS, WS, IS], ids=["OS", "WS", "IS"])
def test_rsa_gemm_output_blocks_never_revisited(mode):
    """The TPU pipeline writes an output block back when the grid moves off
    it and never reads it again, so a kernel may only accumulate into a
    block over consecutive grid steps.  Interpret mode keeps the whole
    output in memory and cannot show the difference; walk the grid the
    way the TPU does instead."""
    import itertools
    from repro.kernels.rsa_gemm import rsa_gemm_pallas
    a = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda x, y: rsa_gemm_pallas(
        x, y, block_m=128, block_n=128, block_k=128, mode=mode))(a, a)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    out_map = grid.block_mappings[-1].index_map_jaxpr
    visits = [tuple(int(i) for i in jax.core.eval_jaxpr(
        out_map.jaxpr, out_map.consts, *step))
        for step in itertools.product(*map(range, grid.grid))]
    runs = [b for i, b in enumerate(visits) if i == 0 or visits[i - 1] != b]
    assert len(runs) == len(set(runs)), visits


# ---------------------------------------------------------------------------
# adaptnetx
# ---------------------------------------------------------------------------

def _adaptnet_params(num_classes, seed=0):
    from repro.core.adaptnet import AdaptNetConfig, init_params
    return init_params(jax.random.PRNGKey(seed),
                       AdaptNetConfig(num_classes=num_classes))


@pytest.mark.parametrize("num_classes", [75, 108])
def test_adaptnetx_matches_ref(num_classes):
    p = _adaptnet_params(num_classes)
    for ids in ([1, 1, 1], [9999, 5000, 1], [123, 4567, 8910]):
        ids = jnp.asarray(ids, jnp.int32)
        out = ops.adaptnetx_recommend(ids, p)
        gold = ref.adaptnetx_ref(ids, p["emb_m"], p["emb_k"], p["emb_n"],
                                 p["w1"], p["b1"], p["w2"], p["b2"])
        np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                                   rtol=1e-5, atol=1e-5)


def test_adaptnetx_matches_host_adaptnet():
    """The hardware kernel computes exactly the software ADAPTNET."""
    from repro.core.adaptnet import logits_fn
    p = _adaptnet_params(108, seed=3)
    feats = jnp.array([[300, 4000, 77]], jnp.int32)
    sw = logits_fn(p, feats)[0]
    hw = ops.adaptnetx_recommend(feats[0], p)
    np.testing.assert_allclose(np.asarray(hw), np.asarray(sw),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# linear_attn
# ---------------------------------------------------------------------------

LA_SHAPES = [(2, 64, 16, 16), (4, 100, 16, 32), (1, 257, 32, 32)]


@pytest.mark.parametrize("shape", LA_SHAPES)
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_linear_attn_matches_sequential_ref(shape, chunk):
    BH, S, K, V = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (BH, S, K)) * 0.5
    k = jax.random.normal(ks[1], (BH, S, K)) * 0.5
    v = jax.random.normal(ks[2], (BH, S, V))
    logw = -jnp.exp(jax.random.normal(ks[3], (BH, S, K)) * 0.5 - 3.0)
    u = jax.random.normal(ks[4], (BH, K)) * 0.1
    out = ops.linear_attn(r, k, v, logw, u, chunk=chunk)
    gold = ref.linear_attn_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=2e-4, atol=2e-4)


def test_linear_attn_chunk_invariance():
    BH, S, K, V = 2, 96, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    r = jax.random.normal(ks[0], (BH, S, K)) * 0.5
    k = jax.random.normal(ks[1], (BH, S, K)) * 0.5
    v = jax.random.normal(ks[2], (BH, S, V))
    logw = -jnp.exp(jax.random.normal(ks[3], (BH, S, K)) - 3.0)
    u = jnp.zeros((BH, K))
    a = ops.linear_attn(r, k, v, logw, u, chunk=16)
    b = ops.linear_attn(r, k, v, logw, u, chunk=48)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)


def test_linear_attn_no_decay_is_cumulative_attention():
    """With w=1 (logw=0) and u=0, o_t = r_t @ sum_{j<t} k_j v_j^T."""
    BH, S, K, V = 1, 40, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    r = jax.random.normal(ks[0], (BH, S, K))
    k = jax.random.normal(ks[1], (BH, S, K))
    v = jax.random.normal(ks[2], (BH, S, V))
    logw = jnp.zeros((BH, S, K))
    u = jnp.zeros((BH, K))
    out = ops.linear_attn(r, k, v, logw, u, chunk=16)
    kv = jnp.cumsum(jnp.einsum("bsk,bsv->bskv", k, v), axis=1)
    kv_prev = jnp.concatenate([jnp.zeros_like(kv[:, :1]), kv[:, :-1]], 1)
    gold = jnp.einsum("bsk,bskv->bsv", r, kv_prev)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=1e-4, atol=1e-4)
