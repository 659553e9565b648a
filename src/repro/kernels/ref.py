"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rsa_gemm_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """f32-accumulated GEMM (all modes compute the same function)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def moe_grouped_gemm_ref(a: jnp.ndarray, b: jnp.ndarray,
                         sizes: jnp.ndarray) -> jnp.ndarray:
    """XLA twin of ``ops.moe_grouped_gemm`` on one layer's bank: a (R, K)
    rows in group order, ``sizes[g]`` rows of group g (padding included),
    each times b[g] (G, K, N); rows past the groups are zero."""
    return jax.lax.ragged_dot(a, b, sizes)


def adaptnetx_ref(ids, emb_m, emb_k, emb_n, w1, b1, w2, b2) -> jnp.ndarray:
    x = jnp.concatenate([emb_m[ids[0]], emb_k[ids[1]], emb_n[ids[2]]], -1)
    h = jnp.maximum(x.astype(jnp.float32) @ w1.astype(jnp.float32)
                    + b1.astype(jnp.float32), 0.0)
    return h @ w2.astype(jnp.float32) + b2.astype(jnp.float32)


# XLA twin of ops.adaptnetx_recommend under its wrapper name, so the
# saralint pallas-contract ops<->ref registry resolves it.
adaptnetx_recommend_ref = adaptnetx_ref


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        kv_len: int | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """Naive f32 softmax attention.  q: (B,Sq,H,hd); k/v: (B,Skv,KVH,hd[_v])."""
    B, Sq, H, hd = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    if kv_len is None:
        kv_len = Skv
    qf = q.astype(jnp.float32).reshape(B, Sq, KVH, G, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    mask = (jnp.arange(Skv) < kv_len)[None, :]
    if causal:
        mask = mask & (jnp.arange(Skv)[None, :] <= jnp.arange(Sq)[:, None])
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Sq, H, vf.shape[-1]).astype(q.dtype)


def paged_gather(arena: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Linearize paged KV: arena (NB, bs, *feat) gathered through per-lane
    block tables (S, W) -> logical rows (S, W*bs, *feat)."""
    g = arena[tables]                     # (S, W, bs, *feat)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_attention_ref(q, k_arena, v_arena, tables, lengths,
                        *, scale: float | None = None,
                        logit_cap: float = 0.0) -> jnp.ndarray:
    """Masked-dense decode attention over gathered pages (f32 softmax).

    q: (S, H, hd) one query token per lane; k_arena: (NB, bs, KVH, hd);
    v_arena: (NB, bs, KVH, hd_v); tables: (S, W) int32; lengths: (S,) int32.
    Returns (S, H, hd_v); empty lanes (length 0) yield zeros.
    """
    S, H, hd = q.shape
    KVH = k_arena.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    k = paged_gather(k_arena, tables).astype(jnp.float32)   # (S, L, KVH, hd)
    v = paged_gather(v_arena, tables).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(S, KVH, G, hd)
    s = jnp.einsum("shgd,slhd->shgl", qf, k) * scale
    if logit_cap > 0.0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    mask = jnp.arange(k.shape[1])[None, :] < lengths[:, None]   # (S, L)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shgl,slhd->shgd", p, v)
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
    return o.reshape(S, H, v.shape[-1]).astype(q.dtype)


def paged_mla_attention_ref(q_abs, q_rope, ckv_arena, krope_arena, tables,
                            lengths, *, scale: float) -> jnp.ndarray:
    """Absorbed-MLA decode over gathered latent pages.

    q_abs: (S, H, r); q_rope: (S, H, rd); ckv_arena: (NB, bs, r);
    krope_arena: (NB, bs, rd).  Returns the latent mix o_lat (S, H, r).
    """
    ckv = paged_gather(ckv_arena, tables).astype(jnp.float32)   # (S, L, r)
    krope = paged_gather(krope_arena, tables).astype(jnp.float32)
    s = (jnp.einsum("shr,slr->shl", q_abs.astype(jnp.float32), ckv) +
         jnp.einsum("shd,sld->shl", q_rope.astype(jnp.float32), krope)) * scale
    mask = jnp.arange(ckv.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shl,slr->shr", p, ckv)
    o = jnp.where((lengths > 0)[:, None, None], o, 0.0)
    return o.astype(q_abs.dtype)


def paged_prefill_attention_ref(q, k_arena, v_arena, tables, starts, lengths,
                                *, scale: float | None = None,
                                logit_cap: float = 0.0) -> jnp.ndarray:
    """Chunked-prefill attention over gathered pages (f32 softmax).

    q: (S, C, H, hd) one prompt chunk per lane (rows already written to the
    arena); tables: (S, W) int32; starts: (S,) absolute position of chunk
    row 0; lengths: (S,) valid tokens including the chunk.  Chunk row r
    attends causally to arena columns ``<= starts + r`` (and ``< lengths``).
    Returns (S, C, H, hd_v); lanes with length 0 yield zeros.
    """
    S, C, H, hd = q.shape
    KVH = k_arena.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    k = paged_gather(k_arena, tables).astype(jnp.float32)   # (S, L, KVH, hd)
    v = paged_gather(v_arena, tables).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(S, C, KVH, G, hd)
    s = jnp.einsum("schgd,slhd->shgcl", qf, k) * scale
    if logit_cap > 0.0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    col = jnp.arange(k.shape[1])
    qpos = starts[:, None] + jnp.arange(C)[None, :]         # (S, C)
    mask = (col[None, None, :] < lengths[:, None, None]) & \
           (col[None, None, :] <= qpos[:, :, None])         # (S, C, L)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shgcl,slhd->schgd", p, v)
    o = jnp.where((lengths > 0)[:, None, None, None, None], o, 0.0)
    return o.reshape(S, C, H, v.shape[-1]).astype(q.dtype)


def paged_mla_prefill_attention_ref(q_abs, q_rope, ckv_arena, krope_arena,
                                    tables, starts, lengths, *,
                                    scale: float) -> jnp.ndarray:
    """Absorbed-MLA chunked prefill over gathered latent pages.

    q_abs: (S, C, H, r); q_rope: (S, C, H, rd); ckv_arena: (NB, bs, r);
    krope_arena: (NB, bs, rd); starts / lengths as in
    :func:`paged_prefill_attention_ref`.  Returns o_lat (S, C, H, r).
    """
    S, C, H, _ = q_abs.shape
    ckv = paged_gather(ckv_arena, tables).astype(jnp.float32)   # (S, L, r)
    krope = paged_gather(krope_arena, tables).astype(jnp.float32)
    s = (jnp.einsum("schr,slr->schl", q_abs.astype(jnp.float32), ckv) +
         jnp.einsum("schd,sld->schl", q_rope.astype(jnp.float32),
                    krope)) * scale
    col = jnp.arange(ckv.shape[1])
    qpos = starts[:, None] + jnp.arange(C)[None, :]
    mask = (col[None, None, :] < lengths[:, None, None]) & \
           (col[None, None, :] <= qpos[:, :, None])         # (S, C, L)
    s = jnp.where(mask[:, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)                          # (S, C, H, L)
    o = jnp.einsum("schl,slr->schr", p, ckv)
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
    return o.astype(q_abs.dtype)


def merge_softmax_states(o_a, m_a, l_a, o_b, m_b, l_b):
    """Combine two *normalized* partial-attention outputs over disjoint key
    sets into the exact softmax over their union.

    ``o_*``: (..., hd_v) normalized partial outputs; ``m_*``: (...) running
    max of the raw scores; ``l_*``: (...) sum of ``exp(score - m)``.  An
    empty state (``l == 0``, ``m == -1e30``) degenerates to the other side;
    two empty states yield zeros.  Returns (o, m, l) of the union.
    """
    o_a, o_b = o_a.astype(jnp.float32), o_b.astype(jnp.float32)
    m = jnp.maximum(m_a, m_b)
    a = l_a * jnp.exp(m_a - m)
    b = l_b * jnp.exp(m_b - m)
    l = a + b
    denom = jnp.maximum(l, 1e-30)
    o = (o_a * a[..., None] + o_b * b[..., None]) / denom[..., None]
    return o, m, l


def paged_attention_lse_ref(q, k_arena, v_arena, tables, lengths,
                            *, scale: float | None = None,
                            logit_cap: float = 0.0):
    """:func:`paged_attention_ref` that also returns the online-softmax
    state, for merging with another phase (shared-prefix cascade decode).

    Returns (o (S, H, hd_v) normalized, m (S, H) f32 running max, l (S, H)
    f32 exp-sum); empty lanes come back as (0, -1e30, 0).
    """
    S, H, hd = q.shape
    KVH = k_arena.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    k = paged_gather(k_arena, tables).astype(jnp.float32)   # (S, L, KVH, hd)
    v = paged_gather(v_arena, tables).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(S, KVH, G, hd)
    s = jnp.einsum("shgd,slhd->shgl", qf, k) * scale
    if logit_cap > 0.0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    mask = jnp.arange(k.shape[1])[None, :] < lengths[:, None]   # (S, L)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)                                 # (S, KVH, G)
    # the explicit mask on p (not just on s) keeps fully-masked lanes at
    # l == 0: with m == -1e30 every masked exp(s - m) would be exp(0) == 1
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("shgl,slhd->shgd", p, v) / \
        jnp.maximum(l, 1e-30)[..., None]
    return (o.reshape(S, H, v.shape[-1]).astype(q.dtype),
            m.reshape(S, H), l.reshape(S, H))


def shared_prefix_attention_ref(q, k_arena, v_arena, prefix_pages,
                                prefix_lens, *, scale: float | None = None,
                                logit_cap: float = 0.0):
    """Partial decode attention over ONE shared page list for every lane.

    q: (S, H, hd); prefix_pages: (P,) int32 physical pages every sharing
    lane's table starts with; prefix_lens: (S,) int32 prefix rows lane s
    attends (0 = lane not in the sharing group -> empty state).  Returns
    (o, m, l) as in :func:`paged_attention_lse_ref`.
    """
    S, H, hd = q.shape
    KVH = k_arena.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    k = k_arena[prefix_pages].astype(jnp.float32)           # (P, bs, KVH, hd)
    v = v_arena[prefix_pages].astype(jnp.float32)
    k = k.reshape((-1,) + k.shape[2:])                      # (P*bs, KVH, hd)
    v = v.reshape((-1,) + v.shape[2:])
    qf = q.astype(jnp.float32).reshape(S, KVH, G, hd)
    s = jnp.einsum("shgd,lhd->shgl", qf, k) * scale
    if logit_cap > 0.0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    mask = jnp.arange(k.shape[0])[None, :] < prefix_lens[:, None]   # (S, L)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("shgl,lhd->shgd", p, v) / \
        jnp.maximum(l, 1e-30)[..., None]
    return (o.reshape(S, H, v.shape[-1]).astype(q.dtype),
            m.reshape(S, H), l.reshape(S, H))


def shared_paged_attention_ref(q, k_arena, v_arena, unique_tables,
                               unique_lens, prefix_pages, prefix_lens,
                               *, scale: float | None = None,
                               logit_cap: float = 0.0) -> jnp.ndarray:
    """Cascade decode oracle — BITWISE equal to :func:`paged_attention_ref`
    over the concatenated page lists.

    Instead of running the prefix and unique phases separately and merging
    online-softmax states (which reassociates the reduction, so greedy
    parity with the plain path held only numerically), each lane's combined
    table is rebuilt gap-free — its prefix pages followed immediately by
    its unique pages, exactly the order the lane's full block table has
    them in — and ONE masked softmax runs over it via
    :func:`paged_attention_ref`.  The only difference from the plain path
    is trailing table padding, and padded columns are exact no-ops: their
    ``-1e30`` scores underflow to 0.0 after ``exp``, leaving every partial
    sum bit-identical.  The two-phase + merge structure survives in the
    Pallas kernel path (``ops.shared_paged_attention``), where streaming
    the shared pages once per group is the point.  Returns (S, H, hd_v).
    """
    S = q.shape[0]
    bs = k_arena.shape[1]
    pw = prefix_pages.shape[0]
    uw = unique_tables.shape[1]
    # pages each lane takes from the shared run (prefix_lens is a whole
    # number of fully-written pages by construction; 0 = not in the group)
    npref = prefix_lens // bs                               # (S,)
    j = jnp.arange(pw + uw)                                 # (W,)
    in_prefix = j[None, :] < npref[:, None]                 # (S, W)
    pref_cols = jnp.broadcast_to(prefix_pages[jnp.clip(j, 0, pw - 1)][None],
                                 (S, pw + uw))
    uniq_idx = jnp.clip(j[None, :] - npref[:, None], 0, uw - 1)
    uniq_cols = jnp.take_along_axis(unique_tables, uniq_idx, axis=1)
    combined = jnp.where(in_prefix, pref_cols, uniq_cols)   # (S, W) int32
    return paged_attention_ref(q, k_arena, v_arena, combined,
                               prefix_lens + unique_lens, scale=scale,
                               logit_cap=logit_cap)


def linear_attn_ref(r, k, v, logw, u) -> jnp.ndarray:
    """Exact sequential recurrence (the definition, O(S) steps).

    r,k,logw: (BH, S, K); v: (BH, S, V); u: (BH, K).
    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k v^T
    """
    BH, S, K = r.shape
    V = v.shape[-1]

    def per_bh(rb, kb, vb, wb, ub):
        def step(h, xs):
            rt, kt, vt, wt = xs
            o = rt @ (h + ub[:, None] * (kt[:, None] * vt[None, :]))
            h = jnp.exp(wt)[:, None] * h + kt[:, None] * vt[None, :]
            return h, o

        h0 = jnp.zeros((K, V), jnp.float32)
        _, o = jax.lax.scan(step, h0, (rb, kb, vb, wb))
        return o

    return jax.vmap(per_bh)(r.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), logw.astype(jnp.float32),
                            u.astype(jnp.float32)).astype(r.dtype)
