"""Paged flash-attention Pallas kernels (GQA + absorbed-MLA): decode + prefill.

Attention kernels for a *physically paged* KV cache: K/V live in a block
arena ``(num_blocks, block_size, ...)`` shared by every lane, and each lane
reads only the pages its block table names.  The masked-dense decode path
(models/attention.py) streams ``num_slots * max_len`` KV rows per step
regardless of how many tokens are actually live; here the split-K grid
walks a lane's block table, so per-step traffic is ``sum_lane ceil(kv_len /
block_size) * block_size`` rows — attention cost scales with live tokens,
not slot capacity (the SARA size-to-the-workload argument applied to the
serving hot path).

Two kernel families share the structure:

* **GQA** (``paged_gqa_pallas``) — ``C`` query tokens per lane attending
  *causally* through the lane's table: chunk row ``r`` sits at absolute
  position ``starts[lane] + r`` and sees keys at positions ``<= starts[lane]
  + r``.  Chunked prefill runs it at ``C = chunk``; decode is the ``C = 1``
  case with ``starts = lengths - 1``.  Per-lane ``starts`` / ``lengths``
  make the batch ragged: lanes whose chunk is empty (``lengths[lane] ==
  0``) skip every block, which is how one batch carries heterogeneous
  prompt lengths.
* **absorbed MLA** (``paged_mla_decode_pallas`` /
  ``paged_mla_prefill_pallas``) — see below.

Grid layout: ``(lanes, table_width)`` — for GQA ``(lanes, query row
blocks, table_width)`` — table width innermost.  The block
table and per-lane scalars ride in scalar prefetch (PrefetchScalarGridSpec)
so the K/V BlockSpec index maps resolve ``table[lane, j]`` before the body
runs — that indirection IS the paging.  One K/V block is a whole page
``(1, block_size, KVH, hd)``: the TPU lowering needs a block's last two
dims to be (8, 128)-aligned or whole, so the page is fetched for every KV
head at once and the body loops over heads.  Per (lane, head) the (m, l,
acc) online-softmax state lives in VMEM scratch, reset at ``j == 0`` and
emitted on the last table column.  Callers pad dead table columns with the
lane's last live block id: Pallas elides the DMA when consecutive grid
steps map to the same block, and ``pl.when`` skips the compute, so padded
columns cost (almost) nothing.

Absorbed MLA attends in the compressed latent space: queries arrive
pre-absorbed (q @ W_UK) plus the shared-rope query, the arena stores
(c_kv, k_rope) rows, and the output is the latent mix ``p @ c_kv`` — the
caller applies W_UV/W_O outside (models/attention.py::mla_paged_decode /
mla_paged_prefill).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# query rows per grid step of the GQA kernel: longer chunks split into row
# blocks (a (KVH, 2048, 64) block already overflows v5e's scoped VMEM)
ROW_BLOCK = 512


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def _fold_page(q, k, v, live, h, m_scr, l_scr, acc_scr, *, scale,
               logit_cap):
    """Fold one page of keys into KV head ``h``'s running softmax state.

    q: (R, hd) query rows of the head; k: (bs, hd); v: (bs, hd_v); live:
    (R, bs) bool.  The mask on p — not just on s — keeps fully-masked rows
    at l == 0: with m == NEG every masked exp(s - m) would be exp(0)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap > 0.0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    s = jnp.where(live, s, NEG)
    m_prev, l_prev = m_scr[h], l_scr[h]                    # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[h] = m_new
    l_scr[h] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)


def _gqa_kernel(tables, starts, lengths, q_ref, k_ref, v_ref, o_ref, m_scr,
                l_scr, acc_scr, *, bs, n_bt, group, scale, logit_cap):
    lane = pl.program_id(0)
    row0 = pl.program_id(1) * q_ref.shape[2]   # first flat row of the block
    j = pl.program_id(2)
    kv_len = lengths[lane]          # rows valid AFTER this chunk's write
    q0 = starts[lane]               # absolute position of chunk row 0

    @pl.when(j == 0)
    def _reset():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < kv_len)
    def _accumulate():
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h]                                # (C*G, hd)
            shape = (q.shape[0], bs)
            col = j * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            # flat row i is chunk row i // G at absolute position q0 + i // G;
            # the causal mask makes each chunk query see only keys at or
            # before its own position (block 0 always has col 0 <= q0 + row,
            # so every live row accumulates a finite max there)
            qpos = q0 + (row0 + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0)) // group
            _fold_page(q, k_ref[0, :, h, :], v_ref[0, :, h, :],
                       (col < kv_len) & (col <= qpos), h, m_scr, l_scr,
                       acc_scr, scale=scale, logit_cap=logit_cap)

    @pl.when(j == n_bt - 1)
    def _emit():
        # rows that never accumulated keep l == 0 and come out as zeros
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).reshape(o_ref.shape).astype(o_ref.dtype)


def paged_gqa_pallas(q, k_arena, v_arena, tables, starts, lengths,
                     scale: float, interpret: bool, *, group: int,
                     logit_cap: float = 0.0):
    """q: (S, KVH, C*G, hd) one chunk of queries per lane and KV head, flat
    row ``c * G + g`` for chunk row c and query g of the head's group
    (``group`` = G); k_arena: (NB, bs, KVH, hd); v_arena: (NB, bs, KVH,
    hd_v); tables: (S, W) int32 physical block ids in logical order
    (tail-pad with the last live id); starts: (S,) int32 absolute position
    of each lane's chunk row 0; lengths: (S,) int32 valid tokens
    *including* the chunk (``starts + chunk_len``).  The chunk's own K/V
    rows must already be in the arena.  Returns o (S, KVH, C*G, hd_v)
    normalized; rows past a lane's chunk are garbage the caller discards,
    lanes with length 0 yield zeros."""
    S, KVH, R, hd = q.shape
    bs = k_arena.shape[1]
    hd_v = v_arena.shape[-1]
    W = tables.shape[1]
    rb = min(R, ROW_BLOCK)
    pad = (-R) % rb
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))

    def row_block(width):
        return pl.BlockSpec((1, KVH, rb, width),
                            lambda s, r, j, t, st, ln: (s, 0, r, 0))

    def page_block(width):
        return pl.BlockSpec((1, bs, KVH, width),
                            lambda s, r, j, t, st, ln: (t[s, j], 0, 0, 0))

    rows = R + pad
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, bs=bs, n_bt=W, group=group,
                          scale=scale, logit_cap=logit_cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, rows // rb, W),
            in_specs=[row_block(hd), page_block(hd), page_block(hd_v)],
            out_specs=row_block(hd_v),
            scratch_shapes=[pltpu.VMEM((KVH, rb, 1), jnp.float32),
                            pltpu.VMEM((KVH, rb, 1), jnp.float32),
                            pltpu.VMEM((KVH, rb, hd_v), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, KVH, rows, hd_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, starts, lengths, q, k_arena, v_arena)
    return out[:, :, :R]


# ---------------------------------------------------------------------------
# absorbed MLA (latent-space attention; shared keys across heads)
# ---------------------------------------------------------------------------

def _mla_kernel(tables, lengths, qa_ref, qr_ref, ckv_ref, krope_ref, o_ref,
                m_scr, l_scr, acc_scr, *, bs, n_bt, scale):
    lane = pl.program_id(0)
    j = pl.program_id(1)
    kv_len = lengths[lane]

    @pl.when(j == 0)
    def _reset():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < kv_len)
    def _accumulate():
        qa = qa_ref[0]                                     # (H, r)
        qr = qr_ref[0]                                     # (H, rd)
        ckv = ckv_ref[0]                                   # (bs, r)
        krope = krope_ref[0]                               # (bs, rd)
        s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) +
             jax.lax.dot_general(qr, krope, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)) * scale
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < kv_len, s, NEG)
        m_prev, l_prev = m_scr[0], l_scr[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[0] = m_new
        l_scr[0] = l_prev * corr + jnp.sum(p, axis=-1)

    @pl.when(j == n_bt - 1)
    def _emit():
        l = jnp.maximum(l_scr[0], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_mla_decode_pallas(q_abs, q_rope, ckv_arena, krope_arena, tables,
                            lengths, scale: float,
                            interpret: bool) -> jnp.ndarray:
    """q_abs: (S, H, r) pre-absorbed queries; q_rope: (S, H, rd); ckv_arena:
    (NB, bs, r); krope_arena: (NB, bs, rd); tables: (S, W) int32; lengths:
    (S,) int32.  Returns the latent mix o_lat: (S, H, r)."""
    S, H, r = q_abs.shape
    rd = q_rope.shape[-1]
    NB, bs = ckv_arena.shape[0], ckv_arena.shape[1]
    W = tables.shape[1]

    grid = (S, W)
    out = pl.pallas_call(
        functools.partial(_mla_kernel, bs=bs, n_bt=W, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, r), lambda s, j, t, ln: (s, 0, 0)),
                pl.BlockSpec((1, H, rd), lambda s, j, t, ln: (s, 0, 0)),
                pl.BlockSpec((1, bs, r), lambda s, j, t, ln: (t[s, j], 0, 0)),
                pl.BlockSpec((1, bs, rd), lambda s, j, t, ln: (t[s, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, r), lambda s, j, t, ln: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, H), jnp.float32),
                            pltpu.VMEM((1, H), jnp.float32),
                            pltpu.VMEM((H, r), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, r), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q_abs, q_rope, ckv_arena, krope_arena)
    return out


def _mla_prefill_kernel(tables, starts, lengths, qa_ref, qr_ref, ckv_ref,
                        krope_ref, o_ref, m_scr, l_scr, acc_scr, *, bs, n_bt,
                        scale):
    lane = pl.program_id(0)
    j = pl.program_id(1)
    kv_len = lengths[lane]
    q0 = starts[lane]
    C, H = qa_ref.shape[1], qa_ref.shape[2]
    CH = C * H

    @pl.when(j == 0)
    def _reset():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < kv_len)
    def _accumulate():
        qa = qa_ref[0].reshape(CH, qa_ref.shape[-1])       # (C*H, r)
        qr = qr_ref[0].reshape(CH, qr_ref.shape[-1])       # (C*H, rd)
        ckv = ckv_ref[0]                                   # (bs, r)
        krope = krope_ref[0]                               # (bs, rd)
        s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) +
             jax.lax.dot_general(qr, krope, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)) * scale
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // H
        s = jnp.where((col < kv_len) & (col <= qpos), s, NEG)
        m_prev, l_prev = m_scr[0], l_scr[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[0] = m_new
        l_scr[0] = l_prev * corr + jnp.sum(p, axis=-1)

    @pl.when(j == n_bt - 1)
    def _emit():
        l = jnp.maximum(l_scr[0], 1e-30)
        o = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0] = o.reshape(C, H, o_ref.shape[-1])


def paged_mla_prefill_pallas(q_abs, q_rope, ckv_arena, krope_arena, tables,
                             starts, lengths, scale: float,
                             interpret: bool) -> jnp.ndarray:
    """q_abs: (S, C, H, r) pre-absorbed chunk queries; q_rope: (S, C, H, rd);
    ckv_arena: (NB, bs, r); krope_arena: (NB, bs, rd); tables: (S, W) int32;
    starts / lengths: (S,) int32 as in :func:`paged_gqa_pallas`.
    Returns the latent mix o_lat: (S, C, H, r)."""
    S, C, H, r = q_abs.shape
    rd = q_rope.shape[-1]
    bs = ckv_arena.shape[1]
    W = tables.shape[1]

    grid = (S, W)
    out = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, bs=bs, n_bt=W, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, C, H, r),
                             lambda s, j, t, st, ln: (s, 0, 0, 0)),
                pl.BlockSpec((1, C, H, rd),
                             lambda s, j, t, st, ln: (s, 0, 0, 0)),
                pl.BlockSpec((1, bs, r),
                             lambda s, j, t, st, ln: (t[s, j], 0, 0)),
                pl.BlockSpec((1, bs, rd),
                             lambda s, j, t, st, ln: (t[s, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, C, H, r),
                                   lambda s, j, t, st, ln: (s, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, C * H), jnp.float32),
                            pltpu.VMEM((1, C * H), jnp.float32),
                            pltpu.VMEM((C * H, r), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, C, H, r), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, starts, lengths, q_abs, q_rope, ckv_arena, krope_arena)
    return out
