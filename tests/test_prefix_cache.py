"""Cross-request prefix caching acceptance tests.

- pool: refcounted sharing (a shared page outlives its donor's free and
  reclaims on the last release), pin/unpin cache references, the
  copy-on-write gate diverging a writer without perturbing sibling
  reads, and defrag treating shared/pinned pages as immovable landmarks
  while content still follows every remapped table
- trie: longest-prefix match at page granularity, insert pinning only
  new spans, LRU leaf-first eviction that never touches a page a live
  table still references, clear() returning the pool to fully free
- scheduler: suffix-only reservation on a cache hit; over-capacity
  prompts rejected at submit with PoolError
- engine: exact greedy parity cache-on vs cache-off with COW exercised
  (whole-prompt hit resumes inside a shared page), cache-hit lanes
  decoding side by side through the donor's physical pages, auto-defrag
  from the step loop
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.serving import EngineConfig, KVArena, KVBlockPool, Request, \
    ServingEngine
from repro.serving.kv_pool import PoolError
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import ContinuousScheduler

GQA_ARCH = "llama3.2-1b"


def _stamped_arena(num_blocks, bs):
    """Every row carries (page_id, row) so moves/copies are detectable."""
    L, KVH, hd = 2, 1, 4
    base = np.zeros((L, num_blocks + 1, bs, KVH, hd), np.float32)
    for b in range(num_blocks + 1):
        for r in range(bs):
            base[:, b, r] = b * 100 + r
    return {"k": jnp.asarray(base), "v": jnp.asarray(base + 0.5)}


# ---------------------------------------------------------------------------
# pool: refcounts, pins, copy-on-write, defrag landmarks
# ---------------------------------------------------------------------------

def test_pool_share_refcount_free_order():
    pool = KVBlockPool(num_blocks=6, block_size=4)
    a = pool.alloc("a", 8)                       # pages [0, 1]
    pool.share("b", a.blocks[:1])                # b maps page 0
    assert pool.refcount(a.blocks[0]) == 2
    assert pool.shared_pages == 1
    # donor frees first: only its exclusive page returns
    assert pool.free("a") == 1
    assert pool.num_free == 5
    pool.check()
    # last table reference reclaims the shared page
    assert pool.free("b") == 1
    assert pool.num_free == 6
    pool.check()


def test_pool_pin_outlives_tables_and_unpin_reclaims():
    pool = KVBlockPool(num_blocks=4, block_size=4)
    t = pool.alloc("a", 4)
    bid = t.blocks[0]
    pool.pin(bid)
    assert pool.free("a") == 0                   # pinned page stays held
    assert pool.num_free == 3
    pool.check()
    with pytest.raises(PoolError):
        pool.unpin(bid + 1)                      # never pinned
    assert pool.unpin(bid) is True               # last reference reclaims
    assert pool.num_free == 4
    pool.check()
    with pytest.raises(PoolError):
        pool.pin(bid)                            # cannot pin a free page


def test_pool_cow_diverges_writer_without_perturbing_sibling():
    pool = KVBlockPool(num_blocks=6, block_size=2)
    arena = KVArena(_stamped_arena(6, 2), block_size=2)
    pool.bind_arena(arena)
    a = pool.alloc("a", 4)                       # pages [0, 1]
    pool.share("b", a.blocks)
    before_a = np.asarray(arena.leaves["k"])[:, a.blocks].copy()

    new = pool.ensure_writable("b", 1)
    assert new != a.blocks[1]                    # b got a private copy
    assert pool.cow_copies == 1
    assert pool.table("b").blocks[0] == a.blocks[0]   # page 0 still shared
    # the copy starts as a bitwise clone of the source page
    np.testing.assert_array_equal(np.asarray(arena.leaves["k"])[:, new],
                                  np.asarray(arena.leaves["k"])[:, a.blocks[1]])
    # b mutates its copy; a's rows are untouched
    arena.leaves = {n: leaf.at[:, new].set(-1.0)
                    for n, leaf in arena.leaves.items()}
    np.testing.assert_array_equal(
        np.asarray(arena.leaves["k"])[:, a.blocks], before_a)
    pool.check()
    # exclusive unpinned pages pass through without copying
    assert pool.ensure_writable("b", 1) == new
    assert pool.cow_copies == 1


def test_pool_cow_oom_raises():
    pool = KVBlockPool(num_blocks=2, block_size=2)
    a = pool.alloc("a", 2)
    pool.share("b", a.blocks)
    pool.extend("b", 4)                          # pool now fully allocated
    with pytest.raises(PoolError):
        pool.ensure_writable("b", 0)             # shared, but no free page


def test_pool_defrag_shared_and_pinned_are_landmarks():
    pool = KVBlockPool(num_blocks=10, block_size=2)
    arena = KVArena(_stamped_arena(10, 2), block_size=2)
    pool.bind_arena(arena)
    a = pool.alloc("a", 4)                       # pages [0, 1]
    pool.alloc("f", 2)                           # page [2] (filler)
    pool.share("b", a.blocks[:1])                # page 0 shared (refs 2)
    pool.extend("b", 4)                          # + page 3
    c = pool.alloc("c", 2)                       # page 4
    pool.pin(c.blocks[0])
    shared_bid, pinned_bid = a.blocks[0], c.blocks[0]
    pool.free("f")                               # page 2 gap -> fragmentation

    def read(rid):
        return np.asarray(arena.leaves["k"])[:, pool.table(rid).blocks]

    before = {rid: read(rid) for rid in pool.live_requests()}
    assert pool.fragmentation() > 0.0
    moves = pool.defrag()
    pool.check()
    # shared and pinned pages kept their physical ids (other tables and
    # the cache index hold them by id); movable pages compacted around
    assert pool.table("a").blocks[0] == shared_bid
    assert pool.table("b").blocks[0] == shared_bid
    assert pool.table("c").blocks[0] == pinned_bid
    assert shared_bid not in moves and pinned_bid not in moves
    # every table still reads the same rows through its remapped blocks
    for rid in pool.live_requests():
        np.testing.assert_array_equal(read(rid), before[rid])


# ---------------------------------------------------------------------------
# trie: match / insert / LRU eviction / clear
# ---------------------------------------------------------------------------

def _cached_prompt(pool, cache, rid, tokens):
    """Donor lifecycle: alloc, 'prefill', index full pages, retire."""
    t = pool.alloc(rid, len(tokens))
    nfull = len(tokens) // pool.block_size
    cache.insert(tokens, t.blocks[:nfull])
    pool.free(rid)
    return t.blocks[:nfull]


def test_prefix_cache_match_insert_partial_pages():
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    toks = np.arange(10, dtype=np.int32)         # 2 full pages + 2 spare
    pages = _cached_prompt(pool, cache, "d", toks)
    assert len(pages) == 2 and cache.inserted_pages == 2
    # full match, prefix match, first-page-only match, miss
    assert cache.match(toks) == pages
    assert cache.match(toks[:8]) == pages
    assert cache.match(np.concatenate([toks[:4],
                                       toks[:4] + 90])) == pages[:1]
    assert cache.match(toks + 50) == []
    assert cache.match(toks[:3]) == []           # shorter than one page
    # re-inserting the same span pins nothing new
    t2 = pool.alloc("d2", 8)
    assert cache.insert(toks[:8], t2.blocks) == 0
    pool.free("d2")
    assert cache.num_entries == 2
    cache.record_lookup(2)
    cache.record_lookup(0)
    assert cache.hits == 1 and cache.misses == 1 and cache.reused_pages == 2
    assert cache.stats()["prefix_cache_hit_rate"] == 0.5


def test_prefix_cache_lru_evicts_leaf_first_and_skips_referenced():
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    chain = _cached_prompt(pool, cache, "d0",
                           np.arange(8, dtype=np.int32))      # 2-node chain
    solo = _cached_prompt(pool, cache, "d1",
                          np.arange(100, 104, dtype=np.int32))  # 1 leaf
    cache.match(np.arange(100, 104, dtype=np.int32))   # touch solo (MRU)
    free0 = pool.num_free
    # LRU leaf is the chain's tail; its parent only evicts after it
    assert cache.evict(2) == 2
    assert pool.num_free == free0 + 2
    assert cache.match(np.arange(8, dtype=np.int32)) == []
    assert cache.match(np.arange(100, 104, dtype=np.int32)) == solo
    # a page a live table references is not reclaimable
    pool.share("r", solo)
    assert cache.evict(1) == 0
    pool.free("r")
    assert cache.evict(1) == 1
    assert pool.num_free == pool.num_blocks
    pool.check()
    assert cache.evicted_pages == 4 - 1          # chain(2) + solo(1)


def test_prefix_cache_evict_exclude_protects_pages():
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    chain = _cached_prompt(pool, cache, "d",
                           np.arange(12, dtype=np.int32))  # 3-node chain
    # excluding the head spares it even once eviction exposes it as a leaf
    assert cache.evict(3, exclude=chain[:1]) == 2
    assert cache.match(np.arange(12, dtype=np.int32)) == chain[:1]
    assert cache.evict(3) == 1
    assert pool.num_free == pool.num_blocks
    pool.check()


def test_prefix_cache_clear_returns_pool_to_free():
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    _cached_prompt(pool, cache, "d0", np.arange(12, dtype=np.int32))
    _cached_prompt(pool, cache, "d1", np.arange(50, 58, dtype=np.int32))
    assert pool.num_free < pool.num_blocks
    assert cache.clear() == 5                    # 3 + 2 nodes
    assert cache.num_entries == 0
    assert pool.num_free == pool.num_blocks
    pool.check()


# ---------------------------------------------------------------------------
# scheduler: suffix reservation + submit rejection
# ---------------------------------------------------------------------------

def test_scheduler_submit_rejects_prompt_exceeding_pool():
    pool = KVBlockPool(num_blocks=2, block_size=4)
    sched = ContinuousScheduler(1, pool)
    with pytest.raises(PoolError, match="can never be admitted"):
        sched.submit(Request("big", np.zeros((40,), np.int32), 4))
    assert sched.pending() == 0                  # rejected, not queued


def test_scheduler_cache_hit_reserves_suffix_only():
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    sched = ContinuousScheduler(2, pool, max_prefills_per_step=2,
                                reserve="incremental", prefill_chunk=4,
                                prefix_cache=cache)
    donor_prompt = np.arange(8, dtype=np.int32)
    pages = _cached_prompt(pool, cache, "donor", donor_prompt)
    free_before = pool.num_free                  # 6: two pages pinned

    prompt = np.concatenate([donor_prompt,
                             np.arange(90, 94, dtype=np.int32)])
    req = Request("hit", prompt.astype(np.int32), 4)
    sched.submit(req)
    plan = sched.plan()
    assert plan.prefills == [req]
    # shared pages head the table; only the suffix chunk was newly reserved
    table = pool.table("hit")
    assert table.blocks[:2] == pages
    assert pool.num_free == free_before - 1      # 1 new page, not 3
    assert req.prefill_pos == 8
    assert req.cached_prefix_tokens == 8 and req.cached_pages == 2
    assert cache.hits == 1 and cache.reused_pages == 2
    # a miss resets nothing it shouldn't
    miss = Request("miss", (prompt + 7).astype(np.int32), 4)
    sched.submit(miss)
    sched.plan()
    assert miss.cached_prefix_tokens == 0 and cache.misses == 1
    sched.retire(req)
    sched.retire(miss)
    cache.clear()
    pool.check()
    assert pool.num_free == pool.num_blocks


def test_scheduler_pressure_eviction_spares_matched_pages():
    """Regression: under pool pressure plan() evicts cache entries to
    admit the head, but the pages ``_match_prefix`` just returned are
    pin-only (no table references them yet) — once their trie
    descendants evicted they became evictable leaves themselves, and
    ``share()`` then raised ``cannot share dead page`` out of plan().
    The matched pages must survive the eviction pass."""
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    donor_prompt = np.arange(16, dtype=np.int32)
    pages = _cached_prompt(pool, cache, "donor", donor_prompt)  # 4 pinned
    pool.alloc("live", 16)                       # 4 blocks held -> 0 free

    sched = ContinuousScheduler(2, pool, reserve="incremental",
                                prefill_chunk=4, prefix_cache=cache)
    prompt = np.concatenate([donor_prompt[:8],
                             np.arange(90, 102, dtype=np.int32)])
    req = Request("hit", prompt.astype(np.int32), 4)
    sched.submit(req)
    plan = sched.plan(0.0)                       # must not raise
    assert plan.prefills == [req]
    # the two matched pages head the table; only the unmatched chain
    # tail (donor page 3) was evicted to fund the suffix chunk
    assert pool.table("hit").blocks[:2] == pages[:2]
    assert req.cached_pages == 2 and cache.hits == 1
    assert cache.evicted_pages == 1
    pool.check()


def test_scheduler_pressure_falls_back_to_cache_miss():
    """When sparing the matched pages cannot free enough pool, admission
    gives the hit up and retries as a cache miss (the matched pages
    become reclaimable) instead of crashing or starving the head."""
    pool = KVBlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    donor_prompt = np.arange(16, dtype=np.int32)
    _cached_prompt(pool, cache, "donor", donor_prompt)  # 4 pinned pages
    pool.alloc("live", 16)                       # 4 blocks held -> 0 free

    # chunk 12: the hit path needs 5 blocks (8 cached + one chunk) with
    # only the two unmatched tail pages evictable — short by one
    sched = ContinuousScheduler(2, pool, reserve="incremental",
                                prefill_chunk=12, prefix_cache=cache)
    prompt = np.concatenate([donor_prompt[:8],
                             np.arange(90, 102, dtype=np.int32)])
    req = Request("fb", prompt.astype(np.int32), 4)
    sched.submit(req)
    plan = sched.plan(0.0)                       # must not raise
    assert plan.prefills == [req]
    assert req.cached_pages == 0 and req.cached_prefix_tokens == 0
    assert len(pool.table("fb").blocks) == 3     # fresh first-chunk table
    assert cache.misses == 1 and cache.hits == 0
    assert cache.evicted_pages == 3              # tail pair + one matched
    pool.check()


def test_scheduler_submit_full_reserve_rejects_impossible_reservation():
    """reserve='full' reserves prompt + max_new + 1 at admission, so a
    request whose full reservation exceeds the pool livelocked at the
    queue head even though the prompt alone fits; the submit floor now
    follows the reservation policy."""
    pool = KVBlockPool(num_blocks=2, block_size=4)
    sched = ContinuousScheduler(1, pool, reserve="full")
    with pytest.raises(PoolError, match="can never be admitted"):
        sched.submit(Request("big", np.zeros((4,), np.int32), 16))
    assert sched.pending() == 0
    # the same request is admissible under incremental reservations
    # (it can stop at EOS well inside the pool)
    inc = ContinuousScheduler(1, pool, reserve="incremental")
    inc.submit(Request("ok", np.zeros((4,), np.int32), 16, eos_id=0))
    assert inc.pending() == 1


# ---------------------------------------------------------------------------
# engine: end-to-end parity, COW, shared-page decode, auto-defrag
# ---------------------------------------------------------------------------

def _engine(cfg, **kw):
    base = dict(num_slots=2, max_len=23, block_size=8, temperature=0.0,
                kv_layout="paged", prefill_chunk=8)
    base.update(kw)
    return ServingEngine(cfg, EngineConfig(**base))


def _run(eng, prompts, gen=6):
    res = eng.run([Request(f"r{i}", p, gen) for i, p in enumerate(prompts)])
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.num_blocks
    return res


def test_engine_prefix_cache_parity_and_cow():
    """Three identical 16-token prompts (page-aligned): recipients match
    the whole prompt, resume at the minus-one offset INSIDE the last
    shared page — the write that must copy-on-write — and still emit
    exactly the cache-off greedy tokens."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = [prompt.copy() for _ in range(3)]
    # one slot serializes the requests, so each recipient admits after
    # the donor's insert; num_blocks leaves headroom for the COW copy
    kw = dict(num_slots=1, num_blocks=6)

    res_off = _run(_engine(cfg, **kw), prompts)
    eng = _engine(cfg, prefix_cache=True, **kw)
    res_on = _run(eng, prompts)
    for rid in res_off:
        np.testing.assert_array_equal(res_on[rid], res_off[rid])
    assert eng.prefix_cache.hits == 2            # both recipients hit
    assert eng.prefix_cache.reused_pages == 4
    assert eng.pool.cow_copies >= 2              # last shared page diverged
    assert eng.metrics.cache_hit_tokens == 2 * 15    # minus-one offset
    assert eng.metrics.prefill_flops_saved > 0
    s = eng.summary()
    assert s["prefix_cache_hit_rate"] > 0.5
    assert s["kv_cow_copies"] == eng.pool.cow_copies
    assert s["kv_shared_pages"] > 0
    # recipients wrote only their suffixes: fewer KV rows than cache-off
    off_rows = 3 * 16
    assert eng.metrics.prefill_kv_write_rows < off_rows


def test_engine_cache_hit_lanes_decode_through_shared_pages():
    """Two cache-hit lanes decode side by side, their block tables opening
    with the donor's physical pages, through the one paged decode program:
    their greedy tokens equal the cache-off engine's."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(12)
    shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    # 4 requests on 2 slots: r0/r1 prefill concurrently (r1 misses — r0
    # inserts only at its final chunk), then r2/r3 both hit and decode
    # side by side through the donor's physical pages
    prompts = []
    for i in range(4):
        p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
        p[:16] = shared
        prompts.append(p)

    res_off = _run(_engine(cfg, max_len=30), prompts)
    eng = _engine(cfg, max_len=30, prefix_cache=True)
    reqs = [Request(f"r{i}", p, 6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    side_by_side = 0
    while eng.step():
        lanes = [r for r in eng.sched.active.values()
                 if r.rid in ("r2", "r3") and r.generated]
        if len(lanes) == 2:
            first = {eng.pool.table(r.rid).blocks[0] for r in lanes}
            side_by_side += len(first) == 1
    assert side_by_side > 0
    assert eng.summary()["kv_shared_pages"] > 0
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(r.generated, np.int32),
                                      res_off[r.rid])
    eng.prefix_cache.clear()
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.num_blocks


def test_engine_auto_defrag_from_step_loop():
    """A sub-zero threshold trips auto-defrag every step; the counter
    advances and generations are unchanged."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 14, 11)]
    res_base = _run(_engine(cfg), prompts)
    eng = _engine(cfg, defrag_threshold=-1.0)
    res = _run(eng, prompts)
    assert int(eng.obs.counters.get("kv_defrag_auto", 0)) > 0
    for rid in res_base:
        np.testing.assert_array_equal(res[rid], res_base[rid])


def test_engine_prefix_cache_requires_chunked_prefill():
    cfg = get_arch(GQA_ARCH).reduced()
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(cfg, EngineConfig(
            num_slots=2, max_len=23, kv_layout="paged", prefix_cache=True))


def test_metrics_cache_hit_accounting():
    from repro.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.on_cache_hit(15, 2, flops_per_token=10.0)
    m.on_cache_hit(8, 1, flops_per_token=10.0)
    s = m.summary()
    assert s["cache_hit_tokens"] == 23
    assert s["cache_hit_pages"] == 3
    assert s["prefill_flops_saved"] == 230.0
