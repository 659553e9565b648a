"""Paged flash-decode acceptance tests.

- kernel parity: the Pallas paged kernel (interpret mode) == the XLA
  gather reference == masked-dense attention over the linearized rows, for
  GQA and absorbed MLA, across ragged per-lane lengths including block
  boundaries (kv_len % block_size == 0 and +-1) and empty lanes; a batch
  whose every lane is empty comes out as zeros
- model parity: paged_decode_step logits == vmapped dense decode_step
  logits to fp32 tolerance (GQA and MLA-with-leading-dense-stack archs)
- engine parity: a kv_layout="paged" engine generates exactly the greedy
  tokens of a kv_layout="dense" engine on ragged prompts
- preempt -> free -> realloc page-reuse round trip through the engine
- defrag compacts the bound arena's storage consistently with the
  remapped tables, and the KV-traffic metrics expose the paged win
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.kernels import ops
from repro.kernels.ref import flash_attention_ref, paged_gather
from repro.models.api import build_model
from repro.serving import EngineConfig, KVArena, KVBlockPool, Request, \
    ServingEngine

GQA_ARCH = "llama3.2-1b"
MLA_ARCH = "deepseek-v3-671b"        # MLA + moe + leading dense stack

BS = 4
# ragged: mid-block, boundary, boundary+1, boundary-1, empty lane
LENGTHS = [6, 8, 9, 7, 0]


def _tables(lengths, bs, width, cover_write=True):
    """Contiguous per-lane tables (lane pages are disjoint), tail-padded
    with the last live id; covers the incoming token when cover_write."""
    t = np.zeros((len(lengths), width), np.int32)
    nxt = 0
    for i, n in enumerate(lengths):
        nblk = -(-(n + (1 if cover_write else 0)) // bs)
        if nblk == 0:
            continue
        ids = list(range(nxt, nxt + nblk))
        nxt += nblk
        t[i, :nblk] = ids
        t[i, nblk:] = ids[-1]
    return t, nxt


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def test_gqa_kernel_matches_reference_and_dense():
    rng = np.random.default_rng(0)
    S, KVH, G, hd = len(LENGTHS), 2, 3, 16
    tables, used = _tables(LENGTHS, BS, width=3, cover_write=False)
    NB = used + 2
    q = jnp.asarray(rng.standard_normal((S, KVH * G, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((NB, BS, KVH, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB, BS, KVH, hd)), jnp.float32)
    lens = jnp.asarray(LENGTHS, jnp.int32)
    t = jnp.asarray(tables)

    o_ref = ops.paged_attention(q, k, v, t, lens, impl="xla")
    o_pal = ops.paged_attention(q, k, v, t, lens, impl="pallas",
                                interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)
    # the gather itself: per lane, linearized pages == masked-dense attn
    for s, n in enumerate(LENGTHS):
        if n == 0:
            assert np.allclose(np.asarray(o_ref[s]), 0.0)
            continue
        k_lin = paged_gather(k, t[s:s + 1])
        v_lin = paged_gather(v, t[s:s + 1])
        o_dense = flash_attention_ref(q[s:s + 1, None], k_lin, v_lin,
                                      causal=False, kv_len=n)
        np.testing.assert_allclose(np.asarray(o_ref[s]),
                                   np.asarray(o_dense[0, 0]),
                                   rtol=1e-5, atol=1e-5)


def test_mla_kernel_matches_reference():
    rng = np.random.default_rng(1)
    S, H, r, rd = len(LENGTHS), 4, 8, 4
    tables, used = _tables(LENGTHS, BS, width=3, cover_write=False)
    NB = used + 2
    qa = jnp.asarray(rng.standard_normal((S, H, r)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((S, H, rd)), jnp.float32)
    ckv = jnp.asarray(rng.standard_normal((NB, BS, r)), jnp.float32)
    kro = jnp.asarray(rng.standard_normal((NB, BS, rd)), jnp.float32)
    lens = jnp.asarray(LENGTHS, jnp.int32)
    t = jnp.asarray(tables)
    m_ref = ops.mla_paged_attention(qa, qr, ckv, kro, t, lens, qk_dim=24,
                                    impl="xla")
    m_pal = ops.mla_paged_attention(qa, qr, ckv, kro, t, lens, qk_dim=24,
                                    impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(m_pal), np.asarray(m_ref),
                               rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(m_ref[LENGTHS.index(0)]), 0.0)


def test_gqa_kernel_logit_softcap():
    rng = np.random.default_rng(5)
    S, H, hd = 2, 2, 8
    tables, used = _tables([5, 3], BS, width=2, cover_write=False)
    q = jnp.asarray(rng.standard_normal((S, H, hd)) * 4, jnp.float32)
    k = jnp.asarray(rng.standard_normal((used + 1, BS, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((used + 1, BS, H, hd)), jnp.float32)
    lens = jnp.asarray([5, 3], jnp.int32)
    t = jnp.asarray(tables)
    capped_p = ops.paged_attention(q, k, v, t, lens, logit_cap=10.0,
                                   impl="pallas", interpret=True)
    capped_r = ops.paged_attention(q, k, v, t, lens, logit_cap=10.0,
                                   impl="xla")
    plain = ops.paged_attention(q, k, v, t, lens, impl="xla")
    np.testing.assert_allclose(np.asarray(capped_p), np.asarray(capped_r),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(capped_r), np.asarray(plain))


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_all_empty_batch_yields_zeros(kind):
    """Every lane at length 0 (a step whose lanes are all masked): no
    table column is live, so both paged kernels skip every page and
    return zeros, XLA and Pallas interpret alike."""
    rng = np.random.default_rng(4)
    S, H, NB = 3, 4, 6
    tables = jnp.asarray(rng.integers(0, NB, (S, 2)), jnp.int32)
    zeros = jnp.zeros((S,), jnp.int32)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    if kind == "gqa":
        q, k, v = normal(S, H, 8), normal(NB, BS, 2, 8), normal(NB, BS, 2, 8)
        outs = [ops.paged_attention(q, k, v, tables, zeros, impl="xla"),
                ops.paged_attention(q, k, v, tables, zeros, impl="pallas",
                                    interpret=True)]
    else:
        qa, qr = normal(S, H, 16), normal(S, H, 8)
        ckv, kro = normal(NB, BS, 16), normal(NB, BS, 8)
        outs = [ops.mla_paged_attention(qa, qr, ckv, kro, tables, zeros,
                                        qk_dim=24, impl=impl,
                                        interpret=True)
                for impl in ("xla", "pallas")]
    for o in outs:
        np.testing.assert_array_equal(np.asarray(o), 0.0)


# ---------------------------------------------------------------------------
# model-level parity (paged_decode_step vs vmapped dense decode_step)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [GQA_ARCH, MLA_ARCH])
def test_paged_decode_step_matches_dense(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens = [7, 8, 9]
    S, max_len = len(lens), 32
    tables, used = _tables(lens, BS, width=max_len // BS)
    arena = model.init_paged_arena(used + 1, BS)     # +1 trash page
    rng = np.random.default_rng(1)

    caches = []
    for s, n in enumerate(lens):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
        _, cache = model.prefill(params, {"tokens": toks},
                                 model.init_cache(1, max_len))
        caches.append(cache)
        nblk = -(-n // BS)
        arena = model.paged_prefill_write(
            arena, cache["layers"], jnp.asarray(tables[s, :nblk], jnp.int32))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, 1)), jnp.int32)
    d_logits, _ = jax.vmap(model.decode_step, in_axes=(None, 0, 0))(
        params, toks[:, None], stacked)
    p_logits, new_arena = model.paged_decode_step(
        params, toks, {}, arena, jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), jnp.ones((S,), jnp.int32))
    np.testing.assert_allclose(np.asarray(p_logits),
                               np.asarray(d_logits)[:, 0],
                               rtol=2e-5, atol=2e-5)
    # masked lanes must leave every live page untouched
    _, frozen = model.paged_decode_step(
        params, toks, {}, arena, jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), jnp.zeros((S,), jnp.int32))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(frozen[name][:, :-1]),
                                      np.asarray(arena[name][:, :-1]))


# ---------------------------------------------------------------------------
# engine parity + page reuse
# ---------------------------------------------------------------------------

def _greedy_outputs(cfg, layout, prompts, gens, max_len, **kw):
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=len(prompts), max_len=max_len,
        max_prefills_per_step=len(prompts), temperature=0.0,
        kv_layout=layout, **kw))
    res = eng.run([Request(f"r{i}", prompts[i], gens[i])
                   for i in range(len(prompts))])
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.num_blocks
    return res, eng


@pytest.mark.parametrize("arch", [GQA_ARCH, MLA_ARCH])
def test_engine_paged_matches_dense_greedy(arch):
    """Greedy generations agree token-for-token between layouts; prompt
    lengths straddle block boundaries (16 % bs == 0, 15, 17)."""
    cfg = get_arch(arch).reduced()
    rng = np.random.default_rng(2)
    plens = [15, 16, 17]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    gens = [6, 5, 4]
    res_p, eng_p = _greedy_outputs(cfg, "paged", prompts, gens, max_len=40,
                                   block_size=8)
    res_d, eng_d = _greedy_outputs(cfg, "dense", prompts, gens, max_len=40,
                                   block_size=8)
    for rid in res_d:
        np.testing.assert_array_equal(res_p[rid], res_d[rid])
    assert eng_p.kv_layout == "paged" and eng_d.kv_layout == "dense"
    s = eng_p.summary()
    # 40-token slots holding <= 23 live tokens: paged must stream less
    assert 0 < s["kv_read_tokens_per_step"] < \
        s["kv_read_tokens_dense_per_step"]
    assert s["kv_read_reduction_x"] > 1.0


def test_engine_preempt_free_realloc_page_reuse():
    """Tight pool + incremental reserve drives a full stall -> preemption;
    the victim's pages return to the pool, get reallocated by other lanes,
    and the victim re-prefills into fresh pages — outputs still complete
    and the pool ends clean."""
    cfg = get_arch(GQA_ARCH).reduced()
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=2, max_len=40, block_size=4, num_blocks=6,
        reserve="incremental", max_prefills_per_step=2, temperature=0.0,
        kv_layout="paged"))
    rng = np.random.default_rng(7)
    reqs = [Request(f"r{i}", rng.integers(0, cfg.vocab_size, 8)
                    .astype(np.int32), 12) for i in range(2)]
    res = eng.run(reqs)
    assert eng.metrics.preemptions >= 1
    assert all(len(res[r.rid]) == 12 for r in reqs)
    assert eng.metrics.completed == 2
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.num_blocks
    assert np.all(eng._kv_rows == 0)


def test_engine_paged_incremental_matches_full_reserve():
    """Stalled lanes write only to the trash page, so an incremental run
    (with stalls) must still produce the same greedy tokens as a
    non-stalling full-reserve run."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
               for _ in range(3)]
    gens = [10, 10, 10]
    res_full, _ = _greedy_outputs(cfg, "paged", prompts, gens, max_len=40)
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=3, max_len=40, block_size=8, num_blocks=8,
        reserve="incremental", max_prefills_per_step=3, temperature=0.0,
        kv_layout="paged"))
    res_inc = eng.run([Request(f"r{i}", prompts[i], gens[i])
                       for i in range(3)])
    assert eng.metrics.stalls > 0 or eng.metrics.preemptions > 0
    for rid in res_full:
        np.testing.assert_array_equal(res_inc[rid], res_full[rid])


# ---------------------------------------------------------------------------
# defrag: the move map is applied to storage
# ---------------------------------------------------------------------------

def _stamped_arena(num_blocks, bs):
    """Every row carries (page_id, row) so moves are detectable."""
    L, KVH, hd = 2, 1, 4
    base = np.zeros((L, num_blocks + 1, bs, KVH, hd), np.float32)
    for b in range(num_blocks + 1):
        for r in range(bs):
            base[:, b, r] = b * 100 + r
    return {"k": jnp.asarray(base), "v": jnp.asarray(base + 0.5)}


def test_defrag_moves_pages_consistently_with_tables():
    pool = KVBlockPool(num_blocks=12, block_size=2)
    arena = KVArena(_stamped_arena(12, 2), block_size=2)
    pool.bind_arena(arena)
    for i in range(6):
        pool.alloc(f"r{i}", 2)                     # 1 page each
    for i in range(6):
        pool.extend(f"r{i}", 4)                    # 2nd page non-adjacent
    def read(rid):
        """A request's rows through its current table (layer axis leads)."""
        return np.asarray(arena.leaves["k"])[:, pool.table(rid).blocks]

    # remember each live request's row contents before compaction
    before = {rid: read(rid) for rid in pool.live_requests()}
    for i in (0, 2, 4):
        pool.free(f"r{i}")
        del before[f"r{i}"]
    assert pool.fragmentation() > 0.0
    moves = pool.defrag()
    assert moves and pool.defrag_moves == len(moves)
    pool.check()
    # tables remapped to the compact front...
    used = sorted(b for rid in pool.live_requests()
                  for b in pool.table(rid).blocks)
    assert used == list(range(len(used)))
    # ...the freed tail is contiguous...
    assert list(pool._free) == list(range(len(used), pool.num_blocks))
    # ...and every request reads the SAME rows through its new table
    for rid in pool.live_requests():
        np.testing.assert_array_equal(read(rid), before[rid])
    assert pool.fragmentation() == 0.0
    # the trash page never moves
    np.testing.assert_array_equal(np.asarray(arena.leaves["k"][:, -1]),
                                  np.asarray(_stamped_arena(12, 2)["k"][:, -1]))


def test_engine_defrag_midstream_preserves_generation():
    """Defragging between engine steps must not change what lanes decode."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
               for _ in range(3)]

    def run(defrag_every):
        eng = ServingEngine(cfg, EngineConfig(
            num_slots=2, max_len=32, block_size=4, temperature=0.0,
            max_prefills_per_step=2, kv_layout="paged"))
        reqs = [Request(f"r{i}", p, 8) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        steps = 0
        while eng.step():
            steps += 1
            if steps % defrag_every == 0:
                eng.defrag()
                eng.pool.check()
        return {r.rid: np.asarray(r.generated) for r in reqs}, eng

    outs_a, eng_a = run(defrag_every=2)
    outs_b, eng_b = run(defrag_every=10 ** 9)      # never defrags
    assert eng_a.metrics.completed == eng_b.metrics.completed == 3
    for rid in outs_b:
        np.testing.assert_array_equal(outs_a[rid], outs_b[rid])


def test_engine_vlm_paged_reserves_frontend_rows():
    cfg = get_arch("internvl2-76b").reduced()
    fe = cfg.frontend.num_tokens
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=2, max_len=24, block_size=8, temperature=0.0,
        max_prefills_per_step=2, kv_layout="paged"))
    assert eng.sched.token_overhead == fe
    rng = np.random.default_rng(6)
    reqs = [Request(f"r{i}", rng.integers(0, cfg.vocab_size, 7)
                    .astype(np.int32), 4,
                    extras={"patch_embeds": rng.standard_normal(
                        (1, fe, cfg.frontend.feature_dim))
                        .astype(np.float32)})
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    # each admitted lane's table covers frontend + prompt rows
    for req in eng.sched.active.values():
        cap = eng.pool.table(req.rid).capacity(eng.pool.block_size)
        assert cap >= fe + req.prompt_len + 1
        # step() ran prefill + one decode: rows = frontend + prompt + the
        # first decoded token's KV (the newest token is still pending)
        assert eng._kv_rows[req.slot] == \
            fe + req.prompt_len + len(req.generated) - 1
    while eng.step():
        pass
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.num_blocks
