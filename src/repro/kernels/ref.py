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
