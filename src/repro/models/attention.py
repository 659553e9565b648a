"""Attention: GQA/MQA, MLA (DeepSeek), RoPE, KV caches, blockwise (flash) attn.

All attention paths use a memory-bounded blockwise ("flash-style") computation
(nested scan over query/kv chunks with running max/sum accumulators) so that
32K-token prefill never materializes an S×S score matrix.  Decode paths attend
over a fixed-capacity KV cache with a length mask.

MLA implements the real DeepSeek-V3 structure: low-rank q projection (or a
direct one where ``q_lora_rank`` is None), compressed KV latent + decoupled
shared RoPE key in DeepSeek's rope layout; decode uses the *absorbed*
formulation and caches only (c_kv, k_rope) — this is what makes
deepseek-v3-671b's decode_32k cell fit (≈70 KB/token instead of ≈8
MB/token).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.modules import apply_rope, dense, dense_init, rmsnorm, softcap
from repro.parallel.hints import hint

Params = Dict[str, Any]
NEG_INF = -2.0e38


class KVCache(NamedTuple):
    k: jnp.ndarray          # (B, S_max, KVH, hd)   [GQA]  or c_kv (B,S_max,r) [MLA]
    v: jnp.ndarray          # (B, S_max, KVH, hd)   [GQA]  or k_rope (B,S_max,rd) [MLA]
    length: jnp.ndarray     # () int32 — tokens currently valid


# ---------------------------------------------------------------------------
# blockwise attention core
# ---------------------------------------------------------------------------

def _chunked_attn(q, k, v, *, causal: bool, q_offset, kv_len, chunk: int,
                  logit_cap: float = 0.0, causal_skip: bool = False):
    """Blockwise attention.

    q: (B, Sq, KVH, G, hd) grouped queries
    k, v: (B, Skv, KVH, hd)
    q_offset: scalar — absolute position of q[0] (for causal masking)
    kv_len: scalar — number of valid kv positions (rest masked)
    causal_skip: iterate only lower-triangular (q,kv) chunk pairs — valid
      when q_offset is statically 0 (training/prefill-from-scratch); halves
      the attention work vs. the masked full grid (§Perf).
    Returns (B, Sq, KVH, G, hd).
    """
    B, Sq, KVH, G, hd = q.shape
    Skv = k.shape[1]
    hd_v = v.shape[-1]          # may differ from hd (MLA: v_dim != qk_dim)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    qc = min(chunk, Sq)
    kc = min(chunk, Skv)
    n_q = -(-Sq // qc)
    n_k = -(-Skv // kc)
    # pad to multiples
    q = jnp.pad(q, ((0, 0), (0, n_q * qc - Sq), (0, 0), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, n_k * kc - Skv), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, n_k * kc - Skv), (0, 0), (0, 0)))

    # keep streams in their compute dtype (bf16 on TPU); fp32 lives only in
    # the per-block softmax + accumulators (flash-attention numerics).
    q = q.reshape(B, n_q, qc, KVH, G, hd)
    k = k.reshape(B, n_k, kc, KVH, hd)
    v = v.reshape(B, n_k, kc, KVH, hd_v)

    q_pos = q_offset + jnp.arange(n_q * qc).reshape(n_q, qc)
    k_pos = jnp.arange(n_k * kc).reshape(n_k, kc)
    kv_valid = k_pos < kv_len                               # (n_k, kc)

    if causal and causal_skip and isinstance(q_offset, int) and q_offset == 0:
        return _chunked_attn_tri(q, k, v, q_pos, k_pos, kv_valid, scale,
                                 logit_cap, B, n_q, qc, n_k, kc, KVH, G,
                                 hd_v)[:, :Sq]

    def q_step(_, qi):
        q_blk = q[:, qi]                                    # (B,qc,KVH,G,hd)
        qp = q_pos[qi]                                      # (qc,)

        def kv_step(carry, kj):
            m, l, acc = carry
            k_blk = k[:, kj]                                # (B,kc,KVH,hd)
            v_blk = v[:, kj]
            # saralint: ok[dispatch-escape] activation-activation attention score; no weight shape for ADAPTNET to tile
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if logit_cap > 0.0:
                s = softcap(s, logit_cap)
            mask = kv_valid[kj][None, :]                    # (1,kc)
            if causal:
                mask = mask & (k_pos[kj][None, :] <= qp[:, None])
            s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            # saralint: ok[dispatch-escape] softmax-weights x values mix, both activations
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, qc, hd_v), jnp.float32)
        # checkpoint the kv step: the (qc, kc) softmax block is recomputed in
        # backward instead of saved per (q-chunk, kv-chunk) pair — this is
        # what keeps 32K-token training inside HBM (flash-attention-style
        # memory, paid for with one extra forward).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), (m0, l0, a0),
                                      jnp.arange(n_k))
        out = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,KVH,G,qc,hd)
        return None, jnp.transpose(out, (0, 3, 1, 2, 4))    # (B,qc,KVH,G,hd)

    _, blocks = jax.lax.scan(jax.checkpoint(q_step), None,
                             jnp.arange(n_q))  # (n_q,B,qc,KVH,G,hd_v)
    out = jnp.transpose(blocks, (1, 0, 2, 3, 4, 5)).reshape(
        B, n_q * qc, KVH, G, hd_v)
    return out[:, :Sq]


def _chunked_attn_tri(q, k, v, q_pos, k_pos, kv_valid, scale, logit_cap,
                      B, n_q, qc, n_k, kc, KVH, G, hd_v):
    """Causal flash attention over the lower-triangular chunk pairs only.

    One scan over the n_q*(n_q+1)/2 (i, j<=i) pairs ordered by i then j;
    the (m, l, acc) accumulator resets at each pair with j==0 and the
    normalized output is emitted on the diagonal (j == i).  Off-diagonal
    pairs need no causal mask at all (every key precedes every query).
    """
    pairs = [(i, j) for i in range(n_q) for j in range(i + 1)]
    I = jnp.array([p[0] for p in pairs])
    J = jnp.array([p[1] for p in pairs])
    is_first = jnp.array([p[1] == 0 for p in pairs])
    last_pos = [i * (i + 1) // 2 + i for i in range(n_q)]

    def pair_step(carry, pij):
        m, l, acc = carry
        qi, kj, first = pij
        m = jnp.where(first, NEG_INF, m)
        l = jnp.where(first, 0.0, l)
        acc = jnp.where(first, 0.0, acc)
        q_blk = q[:, qi]
        k_blk = k[:, kj]
        v_blk = v[:, kj]
        # saralint: ok[dispatch-escape] activation-activation attention score; no weight shape for ADAPTNET to tile
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = softcap(s, logit_cap)
        diag = qi == kj
        mask = kv_valid[kj][None, :] & \
            jnp.where(diag, k_pos[kj][None, :] <= q_pos[qi][:, None], True)
        s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # saralint: ok[dispatch-escape] softmax-weights x values mix, both activations
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        out = acc_new / jnp.maximum(l_new, 1e-30)[..., None]
        return (m_new, l_new, acc_new), out

    m0 = jnp.full((B, KVH, G, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, qc), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, qc, hd_v), jnp.float32)
    _, outs = jax.lax.scan(jax.checkpoint(pair_step), (m0, l0, a0),
                           (I, J, is_first))
    blocks = outs[jnp.array(last_pos)]          # (n_q, B, KVH, G, qc, hd_v)
    out = jnp.transpose(blocks, (1, 0, 4, 2, 3, 5)).reshape(
        B, n_q * qc, KVH, G, hd_v)
    return out


def _flash_pallas_sharded(q, k, v, *, causal: bool, chunk: int):
    """Route to the Pallas flash kernel, per-shard under shard_map.

    Without shard_map, GSPMD would partition the kernel's emulated grid
    loop poorly (all-gathering the sliced operands); with it, each device
    runs the kernel on its local (batch x head) slab.  Batch shards over
    the data axes; heads shard over `model` when both H and KVH divide it
    (falls back to replicated heads — same as the XLA path's behaviour).
    """
    from repro.kernels import ops as kops
    from repro.parallel.hints import current_layout, current_mesh

    kw = dict(causal=causal, block_q=min(chunk, 512), block_k=min(chunk, 512))
    mesh = current_mesh()
    if mesh is None:
        return kops.flash_attention(q, k, v, **kw)

    from jax.sharding import PartitionSpec as P

    def asize(names):
        n = 1
        for a in names:
            n *= mesh.devices.shape[mesh.axis_names.index(a)]
        return n

    B, _, H, _ = q.shape
    KVH = k.shape[2]
    b_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dp_all = current_layout().startswith("dp_all")
    if dp_all:
        b_axes = b_axes + ("model",)
    b_ax = b_axes if B % asize(b_axes) == 0 else None
    m_sz = asize(("model",)) if ("model" in mesh.axis_names
                                 and not dp_all) else 0
    h_ax = "model" if (m_sz and H % m_sz == 0 and KVH % m_sz == 0) else None
    qs = P(b_ax, None, h_ax, None)
    ks = P(b_ax, None, h_ax, None)
    f = jax.shard_map(lambda a, b, c: kops.flash_attention(a, b, c, **kw),
                      mesh=mesh, in_specs=(qs, ks, ks), out_specs=qs,
                      check_vma=False)
    return f(q, k, v)


def multihead_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None,
                        chunk: int = 1024, logit_cap: float = 0.0,
                        causal_skip: bool = False, impl: str = "xla"):
    """q: (B,Sq,H,hd); k: (B,Skv,KVH,hd); v: (B,Skv,KVH,hd_v).
    H must be a multiple of KVH; hd_v may differ from hd (MLA).

    impl="pallas" uses the flash-attention Pallas kernel when the call is
    compatible (full-sequence self/cross attention from position 0, no
    logit softcap); decode and softcapped paths fall back to the XLA
    blockwise scan."""
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    Skv = k.shape[1]
    if kv_len is None:
        kv_len = Skv
    if (impl == "pallas" and logit_cap == 0.0
            and isinstance(q_offset, int) and q_offset == 0
            and isinstance(kv_len, int) and kv_len == Skv and Sq > 1):
        return _flash_pallas_sharded(q, k, v, causal=causal, chunk=chunk)
    qg = q.reshape(B, Sq, KVH, G, hd)
    out = _chunked_attn(qg, k, v, causal=causal, q_offset=q_offset,
                        kv_len=kv_len, chunk=chunk, logit_cap=logit_cap,
                        causal_skip=causal_skip)
    return out.reshape(B, Sq, H, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA / MQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ArchConfig, *, d_in: Optional[int] = None,
             cross: bool = False) -> Params:
    d = d_in if d_in is not None else cfg.d_model
    dt = cfg.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.q_dim, dt),
        "wk": dense_init(k2, d, cfg.kv_dim, dt),
        "wv": dense_init(k3, d, cfg.kv_dim, dt),
        "wo": dense_init(k4, cfg.q_dim, cfg.d_model, dt,
                         scale=1.0 / (cfg.q_dim ** 0.5 * (2 * cfg.num_layers) ** 0.5)),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    return p


def _proj_qkv(params, x, kv_x, cfg: ArchConfig, compute_dtype,
              site: str = "layer.attn"):
    B = x.shape[0]
    q = dense(x, params["wq"], params.get("bq"), compute_dtype,
              site=f"{site}.q")
    k = dense(kv_x, params["wk"], params.get("bk"), compute_dtype,
              site=f"{site}.k")
    v = dense(kv_x, params["wv"], params.get("bv"), compute_dtype,
              site=f"{site}.v")
    q = hint(q.reshape(B, x.shape[1], cfg.num_heads, cfg.head_dim),
             "B", None, "M", None)
    k = hint(k.reshape(B, kv_x.shape[1], cfg.num_kv_heads, cfg.head_dim),
             "B", None, "M", None)
    v = hint(v.reshape(B, kv_x.shape[1], cfg.num_kv_heads, cfg.head_dim),
             "B", None, "M", None)
    return q, k, v


def gqa_self_attention(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                       cfg: ArchConfig, *, cache: Optional[KVCache] = None,
                       update_cache: bool = False, causal: bool = True
                       ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Self-attention for train (cache=None), prefill (update_cache=True with a
    fresh cache) and decode (cache holds history; x is the new token(s)).
    ``causal=False`` gives bidirectional attention (encoder stacks)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    q, k, v = _proj_qkv(params, x, x, cfg, cdt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        start = cache.length
        k_all = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), start, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), start, axis=1)
        kv_len = start + x.shape[1]
        new_cache = KVCache(k_all, v_all, kv_len)
        out = multihead_attention(
            q, k_all.astype(cdt), v_all.astype(cdt), causal=causal,
            q_offset=start, kv_len=kv_len, chunk=cfg.attn_chunk,
            logit_cap=cfg.attn_logit_softcap)
    else:
        out = multihead_attention(q, k, v, causal=causal, q_offset=0,
                                  chunk=cfg.attn_chunk,
                                  logit_cap=cfg.attn_logit_softcap,
                                  causal_skip=cfg.flash_causal_skip,
                                  impl=cfg.attn_impl)
    B, S = x.shape[0], x.shape[1]
    out = hint(out.reshape(B, S, cfg.q_dim), "B", None, "M")
    out = hint(dense(out, params["wo"], None, cdt, site="layer.attn.out"),
               "B", None, None)
    return out, (new_cache if (update_cache or cache is not None) else None)


def gqa_cross_attention(params: Params, x: jnp.ndarray, enc_kv: Tuple[jnp.ndarray, jnp.ndarray],
                        cfg: ArchConfig) -> jnp.ndarray:
    """Cross-attention: K/V precomputed from encoder output (no RoPE)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S = x.shape[0], x.shape[1]
    q = dense(x, params["wq"], params.get("bq"), cdt, site="layer.cross.q")
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k, v = enc_kv
    out = multihead_attention(q, k.astype(cdt), v.astype(cdt), causal=False,
                              chunk=cfg.attn_chunk, impl=cfg.attn_impl)
    out = out.reshape(B, S, cfg.q_dim)
    return dense(out, params["wo"], None, cdt, site="layer.cross.out")


def cross_attention_kv(params: Params, enc_out: jnp.ndarray, cfg: ArchConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S = enc_out.shape[0], enc_out.shape[1]
    k = dense(enc_out, params["wk"], params.get("bk"), cdt,
              site="layer.cross.k")
    v = dense(enc_out, params["wv"], params.get("bv"), cdt,
              site="layer.cross.v")
    return (k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
            v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim))


def init_gqa_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# paged decode (physically paged KV arena; kernels/paged_attn.py)
# ---------------------------------------------------------------------------

def gqa_paged_decode(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                     cfg: ArchConfig, *, k_arena, v_arena, layer, block_tables,
                     kv_lens, write_mask):
    """One-token batched decode through the paged KV arena.

    x: (S, 1, d) — one pending token per lane; positions: (S, 1);
    k_arena/v_arena: (L, NB, bs, KVH, hd) the whole stack's physical pages
    (trailing block is the write-discard scratch), of which this layer is
    ``layer``; block_tables: (S, W) int32 pages in logical order; kv_lens:
    (S,) tokens already in the arena; write_mask: (S,) int32 — 1 writes the
    new token's KV and attends over kv_len+1 tokens, 0 leaves the arena
    unchanged (the lane's output is discarded by the engine).  Returns
    (out (S, 1, d), new_k_arena, new_v_arena), both still stacked.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    q, k, v = _proj_qkv(params, x, x, cfg, cdt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    from repro.kernels import ops as kops
    NB, bs = k_arena.shape[1], k_arena.shape[2]
    # decode is the C=1 case of the chunk write: write_mask doubles as the
    # 0/1 chunk length (masked lanes land in the trash block)
    wm = (write_mask > 0).astype(kv_lens.dtype)
    rows = _paged_chunk_rows(block_tables, kv_lens, wm, 1, bs, NB)
    # saralint: ok[cow-gate] decode appends at row kv_len of the lane's exclusively-owned tail page (or the trash block when masked); shared prefix pages cover only rows < kv_len
    k_arena = _arena_write_chunk(k_arena, layer, rows, k[:, :1])
    v_arena = _arena_write_chunk(v_arena, layer, rows, v[:, :1])
    attn_len = kv_lens + wm
    o = kops.paged_attention(q[:, 0], _arena_layer(k_arena, layer),
                             _arena_layer(v_arena, layer), block_tables,
                             attn_len, logit_cap=cfg.attn_logit_softcap)
    S = x.shape[0]
    out = hint(o.reshape(S, 1, cfg.q_dim), "B", None, "M")
    out = hint(dense(out, params["wo"], None, cdt, site="layer.attn.out"),
               "B", None, None)
    return out, k_arena, v_arena


def _paged_chunk_rows(tables, kv_lens, chunk_lens, num_rows: int,
                      block_size: int, num_blocks: int):
    """Flat arena row for each of a lane's ``num_rows`` chunk positions
    ((S, C) int32).  Chunk row r lands at logical position
    ``kv_lens[lane] + r``; rows at or past a lane's ``chunk_lens`` land in
    row 0 of the trash block — the arena's trailing block, never
    pool-allocated — so ragged lanes (and lanes with no chunk this step)
    cannot corrupt live pages (clamped gather keeps masked lanes' table
    lookups in bounds)."""
    S, W = tables.shape
    pos = kv_lens[:, None] + jnp.arange(num_rows)[None, :]      # (S, C)
    blk = jnp.take_along_axis(tables, jnp.clip(pos // block_size, 0, W - 1),
                              axis=1)
    rows = blk * block_size + pos % block_size
    valid = jnp.arange(num_rows)[None, :] < chunk_lens[:, None]
    return jnp.where(valid, rows, (num_blocks - 1) * block_size)


def _arena_write_chunk(arena: jnp.ndarray, layer, rows: jnp.ndarray,
                       new: jnp.ndarray):
    """Scatter C new rows per lane into layer ``layer`` of the stacked
    (L, NB, bs, *feat) arena, seen flat as (L*NB*bs) rows, so the stack
    is updated in place and never sliced out and written back.
    rows: (S, C) rows within the layer; new: (S, C, *feat).  Masked rows
    all target the trash block's row 0 — colliding writes there are fine,
    it is discard space."""
    L, NB, bs = arena.shape[:3]
    flat = arena.reshape((L * NB * bs,) + arena.shape[3:])
    flat = flat.at[(layer * (NB * bs) + rows).reshape(-1)].set(
        new.reshape((-1,) + new.shape[2:]).astype(arena.dtype))
    return flat.reshape(arena.shape)


def _arena_layer(arena: jnp.ndarray, layer) -> jnp.ndarray:
    """Layer ``layer``'s (NB, bs, *feat) pages of the stacked arena: the
    operand a paged kernel reads."""
    return jax.lax.dynamic_index_in_dim(arena, layer, 0, keepdims=False)


def gqa_paged_prefill(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                      cfg: ArchConfig, *, k_arena, v_arena, layer,
                      block_tables, kv_lens, chunk_lens):
    """Chunked-prefill attention through the paged KV arena.

    x: (S, C, d) — one prompt chunk per lane; positions: (S, C) absolute;
    k_arena/v_arena: (L, NB, bs, KVH, hd) the stack's physical pages
    (trailing block is the write-discard scratch), of which this layer is
    ``layer``; block_tables: (S, W) int32 pages in logical
    order; kv_lens: (S,) rows already committed per lane (the chunk's
    absolute start); chunk_lens: (S,) valid new rows — rows at or past a
    lane's chunk length write to the trash block and their outputs are
    garbage the caller discards (ragged batch: one call serves
    heterogeneous prompt lengths).  The chunk's K/V rows are written into
    the arena *before* attention, so chunk queries see their own keys
    causally.  Returns (out (S, C, d), new_k_arena, new_v_arena), both
    still stacked.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    q, k, v = _proj_qkv(params, x, x, cfg, cdt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    from repro.kernels import ops as kops
    NB, bs = k_arena.shape[1], k_arena.shape[2]
    S, C = x.shape[0], x.shape[1]
    rows = _paged_chunk_rows(block_tables, kv_lens, chunk_lens, C, bs, NB)
    # saralint: ok[cow-gate] chunk rows target pages the engine COW-forked via _cow_chunk_pages before this jitted body runs
    k_arena = _arena_write_chunk(k_arena, layer, rows, k)
    v_arena = _arena_write_chunk(v_arena, layer, rows, v)
    attn_len = kv_lens + chunk_lens
    o = kops.paged_prefill_attention(q, _arena_layer(k_arena, layer),
                                     _arena_layer(v_arena, layer),
                                     block_tables, kv_lens, attn_len,
                                     logit_cap=cfg.attn_logit_softcap)
    out = hint(o.reshape(S, C, cfg.q_dim), "B", None, "M")
    out = hint(dense(out, params["wo"], None, cdt, site="layer.attn.out"),
               "B", None, None)
    return out, k_arena, v_arena


def _mla_absorb_q(q_nope, w_uk, cdt, *, site: str):
    """Absorb W_UK into the queries through the dispatch layer.

    q_nope: (..., S, H, d); w_uk: (r, H, d).  Equivalent to
    ``einsum("...shd,rhd->...shr")`` but expressed as the per-head
    expert-bank GEMM x (..., H, S, d) @ w (H, d, r) so ADAPTNET observes
    the shape and the RSA executes the contraction."""
    from repro import dispatch
    wk = jnp.transpose(w_uk.astype(cdt), (1, 2, 0))        # (H, d, r)
    xq = jnp.moveaxis(q_nope.astype(cdt), -2, -3)          # (..., H, S, d)
    out = dispatch.gemm(xq, wk, site=site)                 # (..., H, S, r)
    return jnp.moveaxis(out, -3, -2)                       # (..., S, H, r)


def _mla_mix_latent(o_lat, w_uv, cdt, *, site: str):
    """Mix attention's latent output up through W_UV via the dispatch
    layer.  o_lat: (..., S, H, r); w_uv: (r, H, d).  Equivalent to
    ``einsum("...shr,rhd->...shd")`` as the per-head expert-bank GEMM."""
    from repro import dispatch
    wv = jnp.transpose(w_uv.astype(cdt), (1, 0, 2))        # (H, r, d)
    xo = jnp.moveaxis(o_lat.astype(cdt), -2, -3)           # (..., H, S, r)
    out = dispatch.gemm(xo, wv, site=site)                 # (..., H, S, d)
    return jnp.moveaxis(out, -3, -2)                       # (..., S, H, d)


def mla_paged_prefill(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                      cfg: ArchConfig, *, ckv_arena, krope_arena, layer,
                      block_tables, kv_lens, chunk_lens):
    """Absorbed-MLA chunked prefill through the paged latent arena.

    The arena stores the compressed (c_kv, k_rope) rows only; chunk queries
    are absorbed through W_UK before the kernel and the latent mix goes
    through W_UV/W_O after — the same formulation as
    :func:`mla_paged_decode`, widened to C causal rows per lane.  Shapes as
    in :func:`gqa_paged_prefill` with ckv_arena (L, NB, bs, kv_lora_rank)
    and krope_arena (L, NB, bs, qk_rope_head_dim).
    """
    m = cfg.mla
    cdt = jnp.dtype(cfg.compute_dtype)
    S, C = x.shape[0], x.shape[1]
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(params, x, positions, cfg, cdt)    # (S,C,H,*)
    c_kv, k_rope = _mla_ckv(params, x, positions, cfg, cdt)    # (S,C,r/rd)

    from repro.kernels import ops as kops
    NB, bs = ckv_arena.shape[1], ckv_arena.shape[2]
    rows = _paged_chunk_rows(block_tables, kv_lens, chunk_lens, C, bs, NB)
    # saralint: ok[cow-gate] chunk rows target pages the engine COW-forked via _cow_chunk_pages before this jitted body runs
    ckv_arena = _arena_write_chunk(ckv_arena, layer, rows, c_kv)
    krope_arena = _arena_write_chunk(krope_arena, layer, rows, k_rope)

    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_abs = _mla_absorb_q(q_nope, w_uk, cdt, site="layer.mla.q_absorb")
    attn_len = kv_lens + chunk_lens
    o_lat = kops.mla_paged_prefill_attention(
        q_abs, q_rope, _arena_layer(ckv_arena, layer),
        _arena_layer(krope_arena, layer), block_tables, kv_lens, attn_len,
        qk_dim=m.qk_nope_head_dim + m.qk_rope_head_dim)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = _mla_mix_latent(o_lat, w_uv, cdt, site="layer.mla.v_mix")
    out = out.reshape(S, C, H * m.v_head_dim)
    out = dense(out, params["wo"], None, cdt, site="layer.mla.out")
    return out, ckv_arena, krope_arena


def mla_paged_decode(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                     cfg: ArchConfig, *, ckv_arena, krope_arena, layer,
                     block_tables, kv_lens, write_mask):
    """Absorbed-MLA batched decode through the paged latent arena.

    The arena stores the compressed (c_kv, k_rope) rows only (the same
    ~70 KB/token layout as the dense absorbed path); queries are absorbed
    through W_UK before the kernel and the latent mix goes through W_UV/W_O
    after.  Shapes as in :func:`gqa_paged_decode` with ckv_arena
    (L, NB, bs, kv_lora_rank) and krope_arena (L, NB, bs, qk_rope_head_dim).
    """
    m = cfg.mla
    cdt = jnp.dtype(cfg.compute_dtype)
    S = x.shape[0]
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(params, x, positions, cfg, cdt)    # (S,1,H,*)
    c_kv, k_rope = _mla_ckv(params, x, positions, cfg, cdt)    # (S,1,r/rd)

    from repro.kernels import ops as kops
    NB, bs = ckv_arena.shape[1], ckv_arena.shape[2]
    wm = (write_mask > 0).astype(kv_lens.dtype)
    rows = _paged_chunk_rows(block_tables, kv_lens, wm, 1, bs, NB)
    # saralint: ok[cow-gate] decode appends at row kv_len of the lane's exclusively-owned tail page (or the trash block when masked); shared prefix pages cover only rows < kv_len
    ckv_arena = _arena_write_chunk(ckv_arena, layer, rows, c_kv[:, :1])
    krope_arena = _arena_write_chunk(krope_arena, layer, rows,
                                     k_rope[:, :1])

    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_abs = _mla_absorb_q(q_nope, w_uk, cdt,
                          site="layer.mla.q_absorb")[:, 0]
    attn_len = kv_lens + wm
    o_lat = kops.mla_paged_attention(
        q_abs, q_rope[:, 0], _arena_layer(ckv_arena, layer),
        _arena_layer(krope_arena, layer), block_tables, attn_len,
        qk_dim=m.qk_nope_head_dim + m.qk_rope_head_dim)       # (S, H, r)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = _mla_mix_latent(o_lat[:, None], w_uv, cdt,
                          site="layer.mla.v_mix")[:, 0]
    out = out.reshape(S, 1, H * m.v_head_dim)
    out = dense(out, params["wo"], None, cdt, site="layer.mla.out")
    return out, ckv_arena, krope_arena


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig) -> Params:
    m = cfg.mla
    dt = cfg.param_dtype
    ks = jax.random.split(key, 6)
    H = cfg.num_heads
    q_out = H * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    if m.q_lora_rank is None:
        q = {"w_q": dense_init(ks[0], cfg.d_model, q_out, dt)}
    else:
        q = {"w_dq": dense_init(ks[0], cfg.d_model, m.q_lora_rank, dt),
             "q_norm": jnp.zeros((m.q_lora_rank,), dt),
             "w_uq": dense_init(ks[1], m.q_lora_rank, q_out, dt)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], cfg.d_model,
                            m.kv_lora_rank + m.qk_rope_head_dim, dt),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dt),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, H * m.qk_nope_head_dim, dt),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim, dt),
        "wo": dense_init(ks[5], H * m.v_head_dim, cfg.d_model, dt,
                         scale=1.0 / ((H * m.v_head_dim) ** 0.5
                                      * (2 * cfg.num_layers) ** 0.5)),
    }


def _mla_rope(x, positions, theta: float):
    """DeepSeek's rope on the decoupled rope dims, as its
    ``modeling_deepseek.py`` applies it: the dims are first de-interleaved
    (even then odd: ``view(..., d // 2, 2).transpose``), then rotated in
    halves.  x: (..., S, heads, rd)."""
    rd = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (rd // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (rd,))
    return apply_rope(x, positions, theta)


def _mla_q(params, x, positions, cfg: ArchConfig, cdt):
    m = cfg.mla
    B, S = x.shape[0], x.shape[1]
    H = cfg.num_heads
    if "w_q" in params:
        q = dense(x, params["w_q"], None, cdt, site="layer.mla.q_proj")
    else:
        cq = rmsnorm(dense(x, params["w_dq"], None, cdt,
                           site="layer.mla.q_down"), params["q_norm"],
                     cfg.norm_eps)
        q = dense(cq, params["w_uq"], None, cdt, site="layer.mla.q_up")
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = _mla_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(params, x, positions, cfg: ArchConfig, cdt):
    m = cfg.mla
    dkv = dense(x, params["w_dkv"], None, cdt, site="layer.mla.kv_down")
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = _mla_rope(k_rope[:, :, None, :], positions,
                       cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_self_attention(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                       cfg: ArchConfig, *, cache: Optional[KVCache] = None,
                       update_cache: bool = False
                       ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    m = cfg.mla
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S = x.shape[0], x.shape[1]
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(params, x, positions, cfg, cdt)
    c_kv, k_rope = _mla_ckv(params, x, positions, cfg, cdt)

    if cache is None:
        # expanded (train/prefill-without-cache) path: standard flash attention
        # over per-head keys (nope ++ shared rope) and values.
        k_nope = dense(c_kv, params["w_uk"], None, cdt,
                       site="layer.mla.k_up").reshape(
            B, S, H, m.qk_nope_head_dim)
        v = dense(c_kv, params["w_uv"], None, cdt,
                  site="layer.mla.v_up").reshape(B, S, H, m.v_head_dim)
        k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                    (B, S, H, m.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        # scale by full qk dim to match the absorbed path
        out = multihead_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                                  causal_skip=cfg.flash_causal_skip,
                                  impl=cfg.attn_impl)
        out = out.reshape(B, S, H * m.v_head_dim)
        out = dense(out, params["wo"], None, cdt, site="layer.mla.out")
        new_cache = None
        if update_cache:
            raise ValueError("prefill with cache must pass an initialized cache")
        return out, new_cache

    # absorbed path — attend in the compressed latent space; cache stores
    # (c_kv, k_rope) only.
    start = cache.length
    ckv_all = jax.lax.dynamic_update_slice_in_dim(
        cache.k, c_kv.astype(cache.k.dtype), start, axis=1)
    krope_all = jax.lax.dynamic_update_slice_in_dim(
        cache.v, k_rope.astype(cache.v.dtype), start, axis=1)
    kv_len = start + S
    new_cache = KVCache(ckv_all, krope_all, kv_len)

    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    # absorb W_UK into q:  q_abs[b,s,h,r] = sum_d q_nope[b,s,h,d] * w_uk[r,h,d]
    q_abs = _mla_absorb_q(q_nope, w_uk, cdt, site="layer.mla.q_absorb")
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim + m.qk_rope_head_dim))
    # saralint: ok[dispatch-escape] latent attention scores against the cached activations, not a weight
    s_nope = jnp.einsum("bshr,btr->bhst", q_abs, ckv_all.astype(cdt))
    # saralint: ok[dispatch-escape] decoupled-rope scores against the cached activations, not a weight
    s_rope = jnp.einsum("bshd,btd->bhst", q_rope, krope_all.astype(cdt))
    s = (s_nope + s_rope) * scale
    t_pos = jnp.arange(ckv_all.shape[1])
    mask = (t_pos[None, :] <= (start + jnp.arange(S))[:, None]) & \
           (t_pos[None, :] < kv_len)
    s = jnp.where(mask[None, None, :, :], s.astype(jnp.float32), NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(cdt)
    # saralint: ok[dispatch-escape] softmax-weights x cached latent rows, both activations
    o_lat = jnp.einsum("bhst,btr->bshr", p, ckv_all.astype(cdt))
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = _mla_mix_latent(o_lat, w_uv, cdt, site="layer.mla.v_mix")
    out = out.reshape(B, S, H * m.v_head_dim)
    out = dense(out, params["wo"], None, cdt, site="layer.mla.out")
    return out, new_cache


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    m = cfg.mla
    return KVCache(
        jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        jnp.zeros((), jnp.int32))
