"""Chunked paged prefill acceptance tests.

- kernel parity: the Pallas chunked-prefill kernel (interpret mode) == the
  XLA gather reference == a per-row causal dense computation, for GQA and
  absorbed MLA, across ragged (start, chunk) pairs straddling chunk and
  page boundaries, including empty lanes
- model parity: streaming a ragged prompt batch through paged_prefill_step
  chunk by chunk reproduces the dense bucketed prefill's last-token logits
  exactly (GQA and MLA-with-leading-dense-stack archs), and a subsequent
  paged decode step matches the dense decode step
- engine parity: a prefill_chunk engine generates exactly the greedy
  tokens of the dense bucketed-prefill engine on prompts straddling chunk
  and page boundaries (including length-1 prompts), and its prefill
  KV-write accounting shows rows == real prompt tokens (no bucket padding)
- chunk-incremental reservations (the satellite bugfix): admission
  reserves only the first chunk's pages, mid-prefill preemption frees
  exactly the pages written, and a pressure run (stalls + preemptions)
  still matches the full-reserve greedy output
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.kernels import ops
from repro.kernels.ref import paged_gather
from repro.models.api import build_model
from repro.serving import ContinuousScheduler, EngineConfig, KVBlockPool, \
    Request, ServingEngine

GQA_ARCH = "llama3.2-1b"
MLA_ARCH = "deepseek-v3-671b"        # MLA + moe + leading dense stack

BS = 4                               # arena page size (tokens)
C = 5                                # chunk width (query rows per lane)
# ragged (start, chunk_len): fresh lane, mid-stream, start on a page
# boundary, empty lane
STARTS = np.array([0, 3, 8, 0], np.int32)
CHUNKS = np.array([5, 4, 2, 0], np.int32)


def _tables(lengths, bs, width):
    """Contiguous per-lane tables (lane pages are disjoint), tail-padded
    with the last live id."""
    t = np.zeros((len(lengths), width), np.int32)
    nxt = 0
    for i, n in enumerate(lengths):
        nblk = -(-int(n) // bs)
        if nblk == 0:
            continue
        ids = list(range(nxt, nxt + nblk))
        nxt += nblk
        t[i, :nblk] = ids
        t[i, nblk:] = ids[-1]
    return t, nxt


def _causal_rows_ref(q, k_lin, v_lin, start, length):
    """Per-row causal attention over linearized pages (numpy oracle)."""
    C_, H, hd = q.shape
    KVH = k_lin.shape[1]
    G = H // KVH
    out = np.zeros((C_, H, v_lin.shape[-1]), np.float32)
    for r in range(C_):
        pos = start + r
        qr = q[r].reshape(KVH, G, hd)
        s = np.einsum("hgd,lhd->hgl", qr, k_lin[:length]) / np.sqrt(hd)
        mask = np.arange(length) <= pos
        s = np.where(mask[None, None, :], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[r] = np.einsum("hgl,lhd->hgd", p, v_lin[:length]).reshape(H, -1)
    return out


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("row_block", [None, 4])
def test_gqa_prefill_kernel_matches_reference_and_causal_dense(row_block,
                                                              monkeypatch):
    """``row_block=4`` splits each lane's C*G = 15 query rows into padded
    4-row grid blocks that straddle chunk rows (the long-chunk path)."""
    if row_block is not None:
        from repro.kernels import paged_attn
        monkeypatch.setattr(paged_attn, "ROW_BLOCK", row_block)
        ops.paged_prefill_attention.clear_cache()
    rng = np.random.default_rng(0)
    S, KVH, G, hd = len(STARTS), 2, 3, 16
    lengths = STARTS + CHUNKS
    tables, used = _tables(lengths, BS, width=4)
    NB = used + 2
    q = jnp.asarray(rng.standard_normal((S, C, KVH * G, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((NB, BS, KVH, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB, BS, KVH, hd)), jnp.float32)
    t, st, ln = (jnp.asarray(x) for x in
                 (tables, STARTS, lengths.astype(np.int32)))

    o_ref = ops.paged_prefill_attention(q, k, v, t, st, ln, impl="xla")
    o_pal = ops.paged_prefill_attention(q, k, v, t, st, ln, impl="pallas",
                                        interpret=True)
    ops.paged_prefill_attention.clear_cache()   # drop the patched trace
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)
    for s in range(S):
        n = int(CHUNKS[s])
        if n == 0:
            assert np.allclose(np.asarray(o_ref[s]), 0.0)
            continue
        k_lin = np.asarray(paged_gather(k, t[s:s + 1])[0])
        v_lin = np.asarray(paged_gather(v, t[s:s + 1])[0])
        want = _causal_rows_ref(np.asarray(q[s]), k_lin, v_lin,
                                int(STARTS[s]), int(lengths[s]))
        np.testing.assert_allclose(np.asarray(o_ref[s, :n]), want[:n],
                                   rtol=1e-4, atol=1e-4)


def test_mla_prefill_kernel_matches_reference():
    rng = np.random.default_rng(1)
    S, H, r, rd = len(STARTS), 4, 8, 4
    lengths = STARTS + CHUNKS
    tables, used = _tables(lengths, BS, width=4)
    NB = used + 2
    qa = jnp.asarray(rng.standard_normal((S, C, H, r)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((S, C, H, rd)), jnp.float32)
    ckv = jnp.asarray(rng.standard_normal((NB, BS, r)), jnp.float32)
    kro = jnp.asarray(rng.standard_normal((NB, BS, rd)), jnp.float32)
    t, st, ln = (jnp.asarray(x) for x in
                 (tables, STARTS, lengths.astype(np.int32)))
    m_ref = ops.mla_paged_prefill_attention(qa, qr, ckv, kro, t, st, ln,
                                            qk_dim=24, impl="xla")
    m_pal = ops.mla_paged_prefill_attention(qa, qr, ckv, kro, t, st, ln,
                                            qk_dim=24, impl="pallas",
                                            interpret=True)
    np.testing.assert_allclose(np.asarray(m_pal), np.asarray(m_ref),
                               rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(m_ref[int(np.argmin(CHUNKS))]), 0.0)


def test_gqa_prefill_kernel_logit_softcap():
    rng = np.random.default_rng(5)
    S, H, hd = 2, 2, 8
    lengths = np.array([7, 3], np.int32)
    tables, used = _tables(lengths, BS, width=2)
    q = jnp.asarray(rng.standard_normal((S, C, H, hd)) * 4, jnp.float32)
    k = jnp.asarray(rng.standard_normal((used + 1, BS, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((used + 1, BS, H, hd)), jnp.float32)
    t = jnp.asarray(tables)
    st = jnp.asarray(np.array([2, 0], np.int32))
    ln = jnp.asarray(lengths)
    capped_p = ops.paged_prefill_attention(q, k, v, t, st, ln,
                                           logit_cap=10.0, impl="pallas",
                                           interpret=True)
    capped_r = ops.paged_prefill_attention(q, k, v, t, st, ln,
                                           logit_cap=10.0, impl="xla")
    plain = ops.paged_prefill_attention(q, k, v, t, st, ln, impl="xla")
    np.testing.assert_allclose(np.asarray(capped_p), np.asarray(capped_r),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(capped_r), np.asarray(plain))


# ---------------------------------------------------------------------------
# model-level parity (streamed chunks vs dense bucketed prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [GQA_ARCH, MLA_ARCH])
def test_paged_prefill_step_streams_to_dense_parity(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    lens = [13, 6, 1, 9]             # ragged, incl. length-1
    S, max_len = len(lens), 32
    tables, used = _tables([n + 1 for n in lens], BS, width=max_len // BS)
    arena = model.init_paged_arena(used + 1, BS)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    step = jax.jit(model.paged_prefill_step)
    pos = np.zeros(S, np.int32)
    last_logits = [None] * S
    while (pos < np.asarray(lens)).any():
        toks = np.zeros((S, C), np.int32)
        chunk = np.zeros((S,), np.int32)
        for s in range(S):
            n = min(C, lens[s] - int(pos[s]))
            if n <= 0:
                continue                 # finished lane rides along empty
            toks[s, :n] = prompts[s][pos[s]:pos[s] + n]
            chunk[s] = n
        kv = np.where(chunk > 0, pos, 0).astype(np.int32)
        logits, arena = step(params, jnp.asarray(toks), arena,
                             jnp.asarray(tables), jnp.asarray(kv),
                             jnp.asarray(chunk))
        logits = np.asarray(logits)
        for s in range(S):
            if chunk[s] > 0 and pos[s] + chunk[s] >= lens[s]:
                last_logits[s] = logits[s]
        pos += chunk

    caches = []
    for s in range(S):
        toks = jnp.asarray(prompts[s][None])
        ref_logits, cache = model.prefill(params, {"tokens": toks},
                                          model.init_cache(1, max_len))
        caches.append(cache)
        np.testing.assert_allclose(last_logits[s], np.asarray(ref_logits)[0],
                                   rtol=2e-4, atol=2e-4)

    # the arena the chunks filled must now serve paged decode identically
    # to the dense caches
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, 1)), jnp.int32)
    d_logits, _ = jax.vmap(model.decode_step, in_axes=(None, 0, 0))(
        params, nxt[:, None], stacked)
    p_logits, _ = model.paged_decode_step(
        params, nxt, {}, arena, jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), jnp.ones((S,), jnp.int32))
    np.testing.assert_allclose(np.asarray(p_logits),
                               np.asarray(d_logits)[:, 0],
                               rtol=2e-4, atol=2e-4)


def test_paged_prefill_step_empty_batch_leaves_live_pages_untouched():
    """A chunk batch where every lane is empty writes only the trash page."""
    cfg = get_arch(GQA_ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    arena = model.init_paged_arena(5, BS)
    tables = jnp.zeros((2, 2), jnp.int32)
    zeros = jnp.zeros((2,), jnp.int32)
    _, new_arena = model.paged_prefill_step(
        params, jnp.zeros((2, C), jnp.int32), arena, tables, zeros, zeros)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new_arena[name][:, :-1]),
                                      np.asarray(arena[name][:, :-1]))


def test_paged_prefill_step_rejects_unsupported_family():
    cfg = get_arch("internvl2-76b").reduced()      # vlm: frontend rows
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    arena_like = {"k": jnp.zeros((2, BS, 1, 4)), "v": jnp.zeros((2, BS, 1, 4))}
    with pytest.raises(ValueError, match="chunks"):
        model.paged_prefill_step(params, jnp.zeros((1, C), jnp.int32),
                                 arena_like, jnp.zeros((1, 1), jnp.int32),
                                 jnp.zeros((1,), jnp.int32),
                                 jnp.zeros((1,), jnp.int32))


# ---------------------------------------------------------------------------
# engine parity + chunk-quantized admission
# ---------------------------------------------------------------------------

def _serve(cfg, prompts, gens, layout, chunk=None, **kw):
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=3, max_len=48, block_size=8, temperature=0.0,
        max_prefills_per_step=2, kv_layout=layout, prefill_chunk=chunk,
        **kw))
    res = eng.run([Request(f"r{i}", prompts[i], gens[i])
                   for i in range(len(prompts))])
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.num_blocks
    return res, eng


@pytest.mark.parametrize("arch", [GQA_ARCH, MLA_ARCH])
def test_engine_chunked_matches_dense_greedy(arch):
    """Greedy generations agree token-for-token between the chunked paged
    engine and the dense bucketed engine; prompt lengths straddle the
    chunk size (8) and page size (8), including a length-1 prompt, and
    prefill KV writes count exactly the real prompt tokens."""
    cfg = get_arch(arch).reduced()
    rng = np.random.default_rng(2)
    plens = [15, 16, 17, 1, 33]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    gens = [6, 5, 4, 7, 3]
    res_c, eng_c = _serve(cfg, prompts, gens, "paged", chunk=8)
    res_d, _ = _serve(cfg, prompts, gens, "dense")
    for rid in res_d:
        np.testing.assert_array_equal(res_c[rid], res_d[rid])
    s = eng_c.summary()
    assert s["prefill_kv_write_rows"] == sum(plens)
    assert s["prefill_kv_write_rows_padded"] > sum(plens)
    assert s["prefill_kv_write_reduction_x"] > 1.0
    # chunk batches traced under their own registry scope (fixed table
    # width -> exactly one chunk-prefill compilation)
    assert "prefill_chunk" in eng_c.registry.scopes()


def test_engine_chunked_streams_long_prompt_across_steps():
    """A prompt longer than the chunk takes ceil(n/C) chunk steps, and a
    short prompt admitted alongside gets its first token while the long
    one is still streaming (the TTFT motivation)."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(3)
    long_p = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    short_p = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=2, max_len=48, block_size=8, temperature=0.0,
        max_prefills_per_step=2, kv_layout="paged", prefill_chunk=8))
    reqs = [Request("long", long_p, 4), Request("short", short_p, 4)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    # after one step: long is mid-prefill (one chunk in), short is done
    # prefilling and has its first token
    assert reqs[0].prefilling and reqs[0].prefill_pos == 8
    assert not reqs[1].prefilling and len(reqs[1].generated) >= 1
    assert reqs[1].t_first_token >= 0 and reqs[0].t_first_token < 0
    while eng.step():
        pass
    assert eng.metrics.completed == 2
    # steps-clock TTFT: short strictly earlier than long
    assert reqs[1].t_first_token < reqs[0].t_first_token


def test_engine_chunked_max_new_tokens_one_retires_at_prefill():
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 10).astype(np.int32)]
    res_c, eng = _serve(cfg, prompts, [1], "paged", chunk=4)
    res_d, _ = _serve(cfg, prompts, [1], "dense")
    np.testing.assert_array_equal(res_c["r0"], res_d["r0"])
    assert eng.metrics.completed == 1


# ---------------------------------------------------------------------------
# chunk-incremental reservations (satellite bugfix)
# ---------------------------------------------------------------------------

def test_chunked_admission_reserves_first_chunk_only():
    pool = KVBlockPool(8, 4)
    sched = ContinuousScheduler(2, pool, reserve="incremental",
                                prefill_chunk=4)
    req = Request("x", np.zeros(20, np.int32), 6)
    sched.submit(req)
    sched.plan(0.0)
    # 20-token prompt at chunk 4 / page 4: admission takes ONE page, not 5
    assert len(pool.table("x").blocks) == 1
    assert sched.grow(req, 8)
    assert len(pool.table("x").blocks) == 2
    # preempt mid-prefill: exactly the written pages return, state resets
    sched.preempt(req)
    assert pool.num_free == pool.num_blocks
    assert req.prefill_pos == 0 and not req.prefilling and req.slot == -1


def test_engine_chunked_pressure_preempts_and_matches_full_reserve():
    """Tight pool + incremental chunked reservations drive mid-prefill
    stalls and preemptions; outputs still match the full-reserve run."""
    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
               for _ in range(2)]

    def run(**kw):
        eng = ServingEngine(cfg, EngineConfig(
            num_slots=2, max_len=40, block_size=4, temperature=0.0,
            max_prefills_per_step=2, kv_layout="paged", prefill_chunk=4,
            **kw))
        res = eng.run([Request(f"r{i}", prompts[i], 6) for i in range(2)])
        eng.pool.check()
        assert eng.pool.num_free == eng.pool.num_blocks
        return res, eng

    res_tight, eng_tight = run(num_blocks=8, reserve="incremental")
    res_full, _ = run()
    assert eng_tight.metrics.stalls > 0 or eng_tight.metrics.preemptions > 0
    assert eng_tight.metrics.completed == 2
    assert np.all(eng_tight._kv_rows == 0)
    for rid in res_full:
        np.testing.assert_array_equal(res_tight[rid], res_full[rid])


def test_engine_rejects_empty_prompt():
    """A zero-length prompt has no last-token logits; under chunked
    prefill it would livelock (no chunk ever completes), so submit()
    rejects it for every layout."""
    cfg = get_arch(GQA_ARCH).reduced()
    eng = ServingEngine(cfg, EngineConfig(kv_layout="paged",
                                          prefill_chunk=4))
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(Request("r", np.zeros((0,), np.int32), 3))


def test_engine_config_validates_prefill_chunk():
    cfg = get_arch(GQA_ARCH).reduced()
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, EngineConfig(kv_layout="dense", prefill_chunk=8))
    with pytest.raises(ValueError, match=">= 1"):
        ServingEngine(cfg, EngineConfig(kv_layout="paged", prefill_chunk=0))
    vlm = get_arch("internvl2-76b").reduced()
    with pytest.raises(ValueError, match="bucketed"):
        ServingEngine(vlm, EngineConfig(kv_layout="paged", prefill_chunk=8))
