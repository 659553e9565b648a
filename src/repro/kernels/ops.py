"""Public jit'd wrappers for the Pallas kernels.

Pad-to-block handling, dtype plumbing, and the interpret switch live here.
The interpret default is backend-aware: ``interpret=None`` resolves to
compiled execution on TPU and Python interpret mode everywhere else, so
the same call sites run the real kernel on TPU with no flag plumbing.
Pass an explicit bool to override.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.hw import OS
from repro.kernels.adaptnetx import adaptnetx_pallas
from repro.kernels.flash_attn import flash_attention_pallas
from repro.kernels.linear_attn import linear_attn_pallas
from repro.kernels.rsa_gemm import rsa_gemm_pallas


def default_interpret() -> bool:
    """Compiled Pallas on TPU; interpret mode on every other backend."""
    return jax.default_backend() != "tpu"


def _interpret(flag: Optional[bool]) -> bool:
    return default_interpret() if flag is None else flag


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "mode", "interpret"))
def rsa_gemm(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int = 128,
             block_n: int = 128, block_k: int = 256, mode: int = OS,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """(M, K) @ (K, N) with SARA-configurable tiling; arbitrary shapes."""
    M, N = a.shape[0], b.shape[1]
    a2 = _pad_to(_pad_to(a, 0, block_m), 1, block_k)
    b2 = _pad_to(_pad_to(b, 0, block_k), 1, block_n)
    out = rsa_gemm_pallas(a2, b2, block_m=block_m, block_n=block_n,
                          block_k=block_k, mode=mode,
                          interpret=_interpret(interpret))
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def adaptnetx_recommend(ids: jnp.ndarray, params: dict, *,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """One fused recommendation query.  ids: (3,) int32 -> logits."""
    return adaptnetx_pallas(
        ids, params["emb_m"], params["emb_k"], params["emb_n"],
        params["w1"], params["b1"], params["w2"], params["b2"],
        interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: Optional[bool] = None):
    """Flash attention with arbitrary Sq/Skv (pads to block multiples).

    q: (B, Sq, H, hd); k: (B, Skv, KVH, hd); v: (B, Skv, KVH, hd_v)
    -> (B, Sq, H, hd_v).  Differentiable (custom-vjp Pallas backward).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    bq = min(block_q, max(Sq, 1))
    bk = min(block_k, max(Skv, 1))
    scale = 1.0 / (hd ** 0.5)
    q2 = _pad_to(q, 1, bq)
    k2 = _pad_to(k, 1, bk)
    v2 = _pad_to(v, 1, bk)
    o = flash_attention_pallas(q2, k2, v2, causal, scale, Skv, bq, bk,
                               _interpret(interpret))
    return o[:, :Sq]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_attn(r, k, v, logw, u, *, chunk: int = 64,
                interpret: Optional[bool] = None):
    """Chunked linear attention; pads S to the chunk multiple.

    r,k,logw: (BH, S, K); v: (BH, S, V); u: (BH, K) -> (BH, S, V).
    """
    S = r.shape[1]
    rr = _pad_to(r, 1, chunk)
    kk = _pad_to(k, 1, chunk)
    vv = _pad_to(v, 1, chunk)
    ww = _pad_to(logw, 1, chunk)
    o = linear_attn_pallas(rr, kk, vv, ww, u, chunk=chunk,
                           interpret=_interpret(interpret))
    return o[:, :S]


def default_paged_impl() -> str:
    """Compiled Pallas paged kernel on TPU; jitted XLA gather elsewhere
    (mirrors dispatch ``execute="auto"`` — interpret-mode Pallas in the
    per-step decode hot loop would be pure Python overhead off-TPU)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _paged_impl(impl: Optional[str]) -> str:
    return default_paged_impl() if impl is None else impl


def _paged_gqa(q, k_arena, v_arena, tables, starts, lengths, scale,
               interpret, logit_cap, lse=False):
    """Run the GQA paged kernel on (S, C, H, hd) chunk queries: regroup the
    query heads under their KV head, one (C*G, hd) row block per head, and
    back.  Returns o (S, C, H, hd_v), or with ``lse`` (o, m (S, C, H) f32,
    l (S, C, H) f32)."""
    from repro.kernels.paged_attn import paged_gqa_pallas
    S, C, H, hd = q.shape
    KVH = k_arena.shape[2]
    G = H // KVH
    qg = q.reshape(S, C, KVH, G, hd).transpose(0, 2, 1, 3, 4)
    out = paged_gqa_pallas(qg.reshape(S, KVH, C * G, hd), k_arena, v_arena,
                           tables, starts, lengths, scale, interpret,
                           group=G, logit_cap=logit_cap, lse=lse)

    def back(x):
        x = x.reshape(S, KVH, C, G, x.shape[-1]).transpose(0, 2, 1, 3, 4)
        return x.reshape(S, C, H, x.shape[-1])

    if not lse:
        return back(out)
    o, m, l = out
    return back(o), back(m)[..., 0], back(l)[..., 0]


@functools.partial(jax.jit, static_argnames=("logit_cap", "impl",
                                             "interpret"))
def paged_attention(q, k_arena, v_arena, tables, lengths, *,
                    logit_cap: float = 0.0,
                    impl: Optional[str] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Paged flash-decode (GQA/MQA): each lane attends only to the KV pages
    its block table names.

    q: (S, H, hd) one query token per lane; k_arena: (NB, bs, KVH, hd);
    v_arena: (NB, bs, KVH, hd_v); tables: (S, W) int32 physical block ids
    in logical order (tail-pad with the last live id); lengths: (S,) int32.
    Returns (S, H, hd_v); lanes with length 0 yield zeros.
    """
    hd = q.shape[-1]
    scale = 1.0 / (hd ** 0.5)
    if _paged_impl(impl) == "xla":
        from repro.kernels.ref import paged_attention_ref
        return paged_attention_ref(q, k_arena, v_arena, tables, lengths,
                                   scale=scale, logit_cap=logit_cap)
    o = _paged_gqa(q[:, None], k_arena, v_arena, tables, lengths - 1,
                   lengths, scale, _interpret(interpret), logit_cap)
    return o[:, 0]


@functools.partial(jax.jit, static_argnames=("logit_cap", "impl",
                                             "interpret"))
def shared_paged_attention(q, k_arena, v_arena, unique_tables, unique_lens,
                           prefix_pages, prefix_lens, *,
                           logit_cap: float = 0.0,
                           impl: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Cascade decode for shared prefixes: one softmax pass over a lane's
    shared-prefix rows (streamed ONCE for every sharing lane via
    ``prefix_pages``) plus one over its unique suffix rows (per-lane
    ``unique_tables``).  Mathematically equal to :func:`paged_attention`
    over the concatenated page lists.  The XLA reference rebuilds each
    lane's combined table and runs ONE masked softmax, so it is BITWISE
    equal to the plain path (greedy cascade parity is asserted, not
    approximate); the Pallas path keeps the two-phase online-softmax
    merge — streaming the shared pages once per group is its point — and
    matches numerically.

    q: (S, H, hd) one query token per lane; prefix_pages: (P,) int32 pages
    every sharing lane's table starts with (tail-pad with the last id);
    prefix_lens: (S,) int32 prefix rows lane s attends (0 = lane not in
    the sharing group); unique_tables: (S, W) int32 each lane's pages PAST
    the prefix (its full table shifted left; non-members keep their whole
    table here); unique_lens: (S,) int32 valid suffix rows.  Returns
    (S, H, hd_v); lanes empty in both phases yield zeros.
    """
    S, H, hd = q.shape
    KVH = k_arena.shape[2]
    scale = 1.0 / (hd ** 0.5)
    if _paged_impl(impl) == "xla":
        from repro.kernels.ref import shared_paged_attention_ref
        return shared_paged_attention_ref(
            q, k_arena, v_arena, unique_tables, unique_lens, prefix_pages,
            prefix_lens, scale=scale, logit_cap=logit_cap)
    from repro.kernels.paged_attn import paged_gqa_prefix_pallas
    from repro.kernels.ref import merge_softmax_states
    G = H // KVH
    itp = _interpret(interpret)
    # prefix phase: every lane's queries stacked per KV head
    qp = q.reshape(S, KVH, G, hd).transpose(1, 0, 2, 3).reshape(KVH, S * G,
                                                                 hd)
    o_p, m_p, l_p = paged_gqa_prefix_pallas(
        qp, k_arena, v_arena, prefix_pages, prefix_lens, scale, itp,
        group=G, logit_cap=logit_cap)

    def lanes_first(x):
        x = x.reshape(KVH, S, G, x.shape[-1]).transpose(1, 0, 2, 3)
        return x.reshape(S, H, x.shape[-1])

    o_u, m_u, l_u = _paged_gqa(q[:, None], k_arena, v_arena, unique_tables,
                               unique_lens - 1, unique_lens, scale, itp,
                               logit_cap, lse=True)
    o, _, _ = merge_softmax_states(
        lanes_first(o_p), lanes_first(m_p)[..., 0], lanes_first(l_p)[..., 0],
        o_u[:, 0], m_u[:, 0], l_u[:, 0])
    return o.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("qk_dim", "impl", "interpret"))
def mla_paged_attention(q_abs, q_rope, ckv_arena, krope_arena, tables,
                        lengths, *, qk_dim: int,
                        impl: Optional[str] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Paged flash-decode for absorbed MLA: attend in the compressed latent
    space through the block table; ``qk_dim`` is the full per-head query-key
    dim (nope + rope) setting the softmax scale.  Returns o_lat (S, H, r).
    """
    scale = 1.0 / (qk_dim ** 0.5)
    if _paged_impl(impl) == "xla":
        from repro.kernels.ref import paged_mla_attention_ref
        return paged_mla_attention_ref(q_abs, q_rope, ckv_arena, krope_arena,
                                       tables, lengths, scale=scale)
    from repro.kernels.paged_attn import paged_mla_decode_pallas
    return paged_mla_decode_pallas(q_abs, q_rope, ckv_arena, krope_arena,
                                   tables, lengths, scale,
                                   _interpret(interpret))


@functools.partial(jax.jit, static_argnames=("logit_cap", "impl",
                                             "interpret"))
def paged_prefill_attention(q, k_arena, v_arena, tables, starts, lengths, *,
                            logit_cap: float = 0.0,
                            impl: Optional[str] = None,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Chunked paged prefill (GQA/MQA): each lane's prompt chunk attends
    causally through its block table to every page written so far,
    including the chunk's own rows (which the caller wrote before calling).

    q: (S, C, H, hd) one chunk of queries per lane; k_arena: (NB, bs, KVH,
    hd); v_arena: (NB, bs, KVH, hd_v); tables: (S, W) int32 physical block
    ids in logical order (tail-pad with the last live id); starts: (S,)
    int32 absolute position of chunk row 0; lengths: (S,) int32 valid
    tokens including the chunk.  Returns (S, C, H, hd_v); rows at or past
    a lane's chunk length are garbage the caller discards, and lanes with
    length 0 yield zeros.
    """
    hd = q.shape[-1]
    scale = 1.0 / (hd ** 0.5)
    if _paged_impl(impl) == "xla":
        from repro.kernels.ref import paged_prefill_attention_ref
        return paged_prefill_attention_ref(q, k_arena, v_arena, tables,
                                           starts, lengths, scale=scale,
                                           logit_cap=logit_cap)
    return _paged_gqa(q, k_arena, v_arena, tables, starts, lengths, scale,
                      _interpret(interpret), logit_cap)


@functools.partial(jax.jit, static_argnames=("qk_dim", "impl", "interpret"))
def mla_paged_prefill_attention(q_abs, q_rope, ckv_arena, krope_arena,
                                tables, starts, lengths, *, qk_dim: int,
                                impl: Optional[str] = None,
                                interpret: Optional[bool] = None
                                ) -> jnp.ndarray:
    """Chunked paged prefill for absorbed MLA: attend in the compressed
    latent space through the block table with causal chunk masking;
    ``qk_dim`` is the full per-head query-key dim (nope + rope) setting the
    softmax scale.  Shapes as in :func:`paged_prefill_attention` with
    q_abs (S, C, H, r) / q_rope (S, C, H, rd).  Returns o_lat (S, C, H, r).
    """
    scale = 1.0 / (qk_dim ** 0.5)
    if _paged_impl(impl) == "xla":
        from repro.kernels.ref import paged_mla_prefill_attention_ref
        return paged_mla_prefill_attention_ref(
            q_abs, q_rope, ckv_arena, krope_arena, tables, starts, lengths,
            scale=scale)
    from repro.kernels.paged_attn import paged_mla_prefill_pallas
    return paged_mla_prefill_pallas(q_abs, q_rope, ckv_arena, krope_arena,
                                    tables, starts, lengths, scale,
                                    _interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def wkv_attention(r, k, v, logw, u, state0, chunk: int = 64,
                  interpret: Optional[bool] = None):
    """RWKV6/GLA chunked linear attention, Pallas fwd + reference-VJP bwd.

    r, k, logw: (B, S, H, K); v: (B, S, H, V); u: (H, K);
    state0: (B, H, K, V) -> (o: (B, S, H, V), state: (B, H, K, V)).
    Backward recomputes through the pure-jnp chunked scan (models/ssm.py),
    so train cells stay differentiable; the fwd-only prefill/decode path is
    the §Perf target the kernel accelerates.
    """
    return _wkv_fwd_impl(r, k, v, logw, u, state0, chunk, interpret)


def _wkv_fwd_impl(r, k, v, logw, u, state0, chunk, interpret):
    from repro.kernels.linear_attn import linear_attn_bshk_pallas
    S = r.shape[1]
    rr = _pad_to(r, 1, chunk)
    kk = _pad_to(k, 1, chunk)
    vv = _pad_to(v, 1, chunk)
    ww = _pad_to(logw, 1, chunk)
    o, sf = linear_attn_bshk_pallas(rr, kk, vv, ww, u, state0, chunk=chunk,
                                    interpret=_interpret(interpret))
    return o[:, :S], sf


def _wkv_vjp_fwd(r, k, v, logw, u, state0, chunk, interpret):
    out = _wkv_fwd_impl(r, k, v, logw, u, state0, chunk, interpret)
    return out, (r, k, v, logw, u, state0)


def _wkv_vjp_bwd(chunk, interpret, res, cts):
    from repro.models.ssm import _wkv_chunked
    r, k, v, logw, u, state0 = res
    _, vjp = jax.vjp(
        lambda r_, k_, v_, w_, u_, s_: _wkv_chunked(r_, k_, v_, w_, u_, s_,
                                                    chunk),
        r, k, v, logw, u, state0)
    return vjp(cts)


wkv_attention.defvjp(_wkv_vjp_fwd, _wkv_vjp_bwd)
