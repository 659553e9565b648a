"""The serving MoE and MLA of the DeepSeek-V3 family at a tiny size.

- the paged chunked prefill and then paged decode of a tiny
  DeepSeek-V3-family model (d 64, 8 experts top-2 + 1 shared, 2 heads,
  r 32, rd 16, 1 dense + 2 MoE layers) give the logits of the plain
  float32 reference in ``bench/models/deepseek_v3.py``
- no routed row is dropped, even when every row picks the same experts
- the selection bias changes which experts are chosen, never their weights
- the grouped expert GEMM (interpret mode) equals an einsum over ragged
  groups, with empty groups and a segment across a tile edge, and reads
  one layer of a stack of banks in place exactly as it reads that bank
- rows of dead lanes and past a lane's chunk route to no expert
"""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch
from repro.kernels import ops
from repro.models.api import build_model
from repro.models.moe import _route, group_rows, moe_apply, moe_serve

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")

TINY = {
    "name": "tiny-ds", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 2,
    "num_key_value_heads": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "q_lora_rank": None,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "rope_theta": 50000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "torch_dtype": "float32", "vocab_size": 256, "rope_scaling": None}
SEED = 2147483999
BS = 4                       # page rows
C = 5                        # chunk rows


@pytest.fixture(scope="module")
def ds():
    """The family's benchmark module: weights, the reference, the config
    mapping (it imports the benchmark's shared helpers by name)."""
    sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_model_deepseek_v3", os.path.join(BENCH, "models",
                                                "deepseek_v3.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(ds):
    cfg = ds.arch_config(TINY)
    return cfg, ds.make_params(SEED, cfg)


def _moe_params(tiny):
    cfg, params = tiny
    moe = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    return cfg, moe


# ---------------------------------------------------------------------------
# the engine's step programs against the reference
# ---------------------------------------------------------------------------

def test_chunked_prefill_then_decode_match_reference(ds, tiny):
    """Three ragged prompts stream through the arena in chunks of 5 (one
    lane idle in the last chunk steps), then every lane decodes 3 tokens
    with lane 2 masked in the second.  Tolerance 2e-4 on logits of spread
    ~1: both sides compute in float32; they differ in summation order,
    the absorbed against the expanded MLA, and the order in which expert
    outputs are added, each a rounding of ~1e-6 relative per layer."""
    cfg, params = tiny
    model = build_model(cfg)
    rng = np.random.default_rng(1)
    lens = np.array([9, 4, 12], np.int32)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    width = 8
    tables = np.arange(len(lens) * width, dtype=np.int32).reshape(-1, width)
    arena = model.init_paged_arena(len(lens) * width + 1, BS)
    chunk = jax.jit(lambda *a: model.paged_prefill_step(*a, moe_loads=True))
    got = [[] for _ in lens]
    pos = np.zeros_like(lens)
    while (pos < lens).any():
        n = np.minimum(C, lens - pos)
        toks = np.zeros((len(lens), C), np.int32)
        for s in range(len(lens)):
            toks[s, :n[s]] = prompts[s][pos[s]:pos[s] + n[s]]
        (logits, loads), arena = chunk(
            params, jnp.asarray(toks), arena, jnp.asarray(tables),
            jnp.asarray(np.where(n > 0, pos, 0)), jnp.asarray(n))
        # every live chunk row routed to k experts in each MoE layer
        assert np.all(np.asarray(loads)[:, 0].sum(-1) == 2 * n.sum())
        pos += n
        for s in range(len(lens)):
            if n[s] and pos[s] == lens[s]:
                got[s].append(np.asarray(logits[s]))
    seqs = [list(p) for p in prompts]
    kv = lens.copy()
    decode = jax.jit(lambda *a: model.paged_decode_step(*a, moe_loads=True))
    for step in range(3):
        wm = np.array([1, 0 if step == 1 else 1, 1], np.int32)
        toks = rng.integers(0, cfg.vocab_size, (len(lens), 1))
        (logits, loads), arena = decode(
            params, jnp.asarray(toks, jnp.int32), {}, arena,
            jnp.asarray(tables), jnp.asarray(kv), jnp.asarray(wm))
        assert np.all(np.asarray(loads)[:, 0].sum(-1) == 2 * wm.sum())
        for s in range(len(lens)):
            if wm[s]:
                seqs[s].append(int(toks[s, 0]))
                got[s].append(np.asarray(logits[s]))
        kv = kv + wm
    rows = [np.arange(n - 1, len(sq)) for n, sq in zip(lens, seqs)]
    want = ds.served_logits(SEED, cfg, [np.asarray(s, np.int32)
                                        for s in seqs], rows)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.stack(g), w, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the serving MoE
# ---------------------------------------------------------------------------

def _plain_moe(moe, x, cfg, idx, wts):
    """Gather by expert in float32: each row sums its chosen experts'
    outputs with its weights, plus the shared experts."""
    x = np.asarray(x, np.float64)
    silu = lambda v: v / (1.0 + np.exp(-v))   # noqa: E731
    w = {k: np.asarray(v, np.float64) for k, v in moe.items()
         if k != "shared"}
    sh = {k: np.asarray(v, np.float64) for k, v in moe["shared"].items()}
    y = silu(x @ sh["w_gate"]) * (x @ sh["w_up"]) @ sh["w_down"]
    for t in range(x.shape[0]):
        for e, g in zip(np.asarray(idx[t]), np.asarray(wts[t])):
            h = silu(x[t] @ w["w_gate"][e]) * (x[t] @ w["w_up"][e])
            y[t] += g * (h @ w["w_down"][e])
    return y


@pytest.mark.parametrize("forced", [False, True],
                         ids=["routed", "all-rows-to-experts-3-and-5"])
def test_serving_moe_drops_nothing(tiny, forced):
    """40 rows through the serving MoE equal the plain gather-by-expert
    MoE; with the bias forcing every row onto experts 3 and 5 the training
    path's capacity (1.25 x its share) would drop most of them, the
    serving path none."""
    cfg, moe = _moe_params(tiny)
    if forced:
        bias = np.zeros(8, np.float32)
        bias[[3, 5]] = 10.0
        moe = dict(moe, e_score_correction_bias=jnp.asarray(bias))
    x = jax.random.normal(jax.random.PRNGKey(2), (40, cfg.d_model))
    idx, wts, _, _ = _route(moe, x, cfg)
    if forced:
        assert set(np.unique(np.asarray(idx))) == {3, 5}
        capped = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.25))
        _, aux = moe_apply(moe, x[None], capped)
        assert float(aux["moe_drop_frac"]) > 0.5
    y, load = moe_serve(moe, x, cfg, jnp.ones((40,), bool))
    assert int(np.asarray(load)[0].sum()) == 40 * 2
    np.testing.assert_allclose(np.asarray(y), _plain_moe(moe, x, cfg, idx,
                                                         wts),
                               rtol=1e-4, atol=1e-4)


def test_bias_changes_selection_not_weights(tiny):
    """The bias moves some rows' choices; the weights of the chosen
    experts are their unbiased sigmoid scores, normalised over the k and
    scaled, with or without it."""
    cfg, moe = _moe_params(tiny)
    x = jax.random.normal(jax.random.PRNGKey(3), (256, cfg.d_model))
    unbiased = dict(moe, e_score_correction_bias=jnp.zeros(8))
    idx_b, w_b, scores, _ = _route(moe, x, cfg)
    idx_u, w_u, _, _ = _route(unbiased, x, cfg)
    moved = np.any(np.sort(np.asarray(idx_b), -1)
                   != np.sort(np.asarray(idx_u), -1), axis=-1)
    assert 0 < moved.sum() < len(moved)
    for idx, w in ((idx_b, w_b), (idx_u, w_u)):
        chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
        want = chosen / chosen.sum(-1, keepdims=True) * 2.446
        np.testing.assert_allclose(np.asarray(w), want, rtol=1e-6)


def test_dead_rows_route_nothing(tiny):
    """Dead rows add no routed rows and get only the shared experts'
    output; live rows are as if routed alone."""
    cfg, moe = _moe_params(tiny)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, cfg.d_model))
    live = jnp.arange(5)[None, :] < jnp.asarray([5, 2, 0])[:, None]
    y, load = moe_serve(moe, x, cfg, live)
    assert np.asarray(load)[0].tolist() == np.asarray(
        moe_serve(moe, x[:2].reshape(-1, cfg.d_model)[
            np.asarray(live[:2]).reshape(-1)], cfg,
            jnp.ones((7,), bool))[1])[0].tolist()
    assert int(np.asarray(load)[0].sum()) == 7 * 2
    shared_only, _ = moe_serve(moe, x, cfg, jnp.zeros((3, 5), bool))
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(y)[dead],
                                  np.asarray(shared_only)[dead])
    alone, _ = moe_serve(moe, x[0], cfg, jnp.ones((5,), bool))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(alone),
                               rtol=1e-6, atol=1e-6)


def test_static_rows_hold_every_routing():
    """The grouped buffer holds the routed rows plus (tile - 1) per
    expert: every split of the rows over the experts, each segment padded
    to the tile, fits, down to one row for all experts but one."""
    assert group_rows(384, 64, 16) == 1344
    rng = np.random.default_rng(0)
    for rows, experts, bm in ((384, 64, 16), (49152, 64, 256), (10, 8, 4)):
        cases = [rng.multinomial(rows, np.ones(experts) / experts)
                 for _ in range(50)]
        cases.append(np.array([1] * (experts - 1) + [rows - experts + 1]))
        cases.append(np.array([rows] + [0] * (experts - 1)))
        for counts in cases:
            assert (-(-counts // bm) * bm).sum() <= group_rows(rows, experts,
                                                               bm)


# ---------------------------------------------------------------------------
# the grouped GEMM
# ---------------------------------------------------------------------------

def _layout(counts, bm, R):
    counts = np.asarray(counts)
    sizes = -(-counts // bm) * bm
    ends = np.cumsum(sizes)
    used = int(ends[-1] // bm)
    first = np.arange(R // bm) * bm
    group = np.searchsorted(ends, first, side="right")
    last = np.searchsorted(ends, max(used - 1, 0) * bm, side="right")
    group = np.minimum(np.where(first < ends[-1], group, last),
                       len(counts) - 1)
    return dispatch.GroupLayout(jnp.asarray(group, jnp.int32),
                                jnp.asarray([used], jnp.int32),
                                jnp.asarray(sizes, jnp.int32), bm, 4)


@pytest.mark.parametrize("backend,layers", [
    ("pallas", None), ("xla", None), ("pallas", 3), ("xla", 3)],
    ids=["pallas", "xla", "pallas-stack", "xla-stack"])
def test_grouped_gemm_matches_ragged_einsum(backend, layers):
    """Groups of 5, 0, 19 (across a tile edge), 0 and 8 rows in tiles of
    8: each group's rows times its own weights; the kernel in interpret
    mode, and ragged_dot.  With ``layers``, the bank is one layer of a
    stack read in place: at every layer the whole output, unused tiles
    included, is bit for bit the call on that layer's bank alone."""
    counts, bm, K, N = [5, 0, 19, 0, 8], 8, 256, 128
    sizes = -(-np.asarray(counts) // bm) * bm
    R = 64
    rng = np.random.default_rng(0)
    x = rng.standard_normal((R, K)).astype(np.float32)
    stack = rng.standard_normal((layers or 1, len(counts), K, N)
                                ).astype(np.float32)
    starts = np.cumsum(sizes) - sizes
    for s, c, z in zip(starts, counts, sizes):
        x[s + c:s + z] = 0.0                          # a segment's padding
    layout = _layout(counts, bm, R)
    for layer, w in enumerate(stack):
        with dispatch.use(execute=backend, interpret=True):
            y = np.asarray(dispatch.grouped_gemm(
                jnp.asarray(x), jnp.asarray(w), layout, site="test.grouped"))
            if layers:
                np.testing.assert_array_equal(np.asarray(
                    dispatch.grouped_gemm(
                        jnp.asarray(x), jnp.asarray(stack), layout,
                        site="test.grouped",
                        layer=jnp.asarray(layer, jnp.int32))), y)
        for g, (s, z) in enumerate(zip(starts, sizes)):
            np.testing.assert_allclose(y[s:s + z], x[s:s + z] @ w[g],
                                       rtol=1e-4, atol=1e-4)


def test_grouped_kernel_skips_unused_tiles():
    """Tiles past the used ones are neither computed nor written: their
    rows keep whatever the output held, the used rows are exact, and the
    bank is the one of the layer asked for in the stack."""
    counts, bm = [3, 9], 4
    R = 32
    layout = _layout(counts, bm, R)
    x = jnp.ones((R, 128), jnp.float32)
    w = jnp.stack([jnp.full((2, 128, 128), 1.0),
                   jnp.stack([jnp.full((128, 128), 3.0),
                              jnp.full((128, 128), 4.0)])])
    y = np.asarray(ops.moe_grouped_gemm(x, w, layout.group, layout.used,
                                        jnp.asarray([1], jnp.int32),
                                        block_m=bm, block_n=128,
                                        block_k=128, interpret=True))
    assert int(layout.used[0]) == 4
    np.testing.assert_array_equal(y[:4], 384.0)
    np.testing.assert_array_equal(y[4:16], 512.0)
