"""Chunked linear attention (RWKV6/GLA-class) Pallas kernel.

Computes, per (batch*head), the data-dependent-decay linear attention

  o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T),   S_t = diag(w_t) S_{t-1} + k_t v_t^T

with the chunked closed form of models/ssm.py: the recurrent state S lives
in VMEM scratch and is carried across the (sequential) chunk grid dimension
— HBM sees only the chunk inputs and outputs, never the (lc, lc) decay
block.  This kernel is the hot spot of the rwkv6-1.6b / zamba2-7b cells
(the §Perf memory-bound term).

Grid: (BH, n_chunks) — chunk axis innermost/sequential; state resets at
chunk 0 of each (batch, head).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *,
            lc: int):
    @pl.when(pl.program_id(1) == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, 0].astype(jnp.float32)         # (lc, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)         # (lc, V)
    lw = lw_ref[0, 0].astype(jnp.float32)       # (lc, K) log decays (<= 0)
    u = u_ref[0].astype(jnp.float32)            # (K,) bonus

    cs = jnp.cumsum(lw, axis=0)                 # inclusive
    cs_prev = cs - lw
    h = state_ref[...]

    # inter-chunk
    o = (r * jnp.exp(cs_prev)) @ h              # (lc, V)
    # intra-chunk (strictly lower triangular)
    diff = cs_prev[:, None, :] - cs[None, :, :]             # (t, j, K)
    tri = jnp.tril(jnp.ones((lc, lc), jnp.bool_), k=-1)
    a = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)
    A = jnp.einsum("tk,jk,tjk->tj", r, k, a)
    o = o + A @ v
    # bonus diagonal
    o = o + jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update
    wsum = cs[-1]                               # (K,)
    kdec = k * jnp.exp(wsum[None, :] - cs)
    state_ref[...] = jnp.exp(wsum)[:, None] * h + kdec.T @ v


def _kernel_bshk(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sf_ref,
                 state_scr, *, lc: int, n: int):
    """Native (B, S, H, K) layout WKV kernel with carried state io.

    Grid (B, H, n_chunks); the recurrent (K, V) state lives in VMEM scratch,
    seeded from s0 at chunk 0 and emitted to sf at the last chunk.
    """
    @pl.when(pl.program_id(2) == 0)
    def _seed():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0, :].astype(jnp.float32)       # (lc, K)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)       # (lc, V)
    lw = lw_ref[0, :, 0, :].astype(jnp.float32)     # (lc, K) log decays
    u = u_ref[0].astype(jnp.float32)                # (K,)

    cs = jnp.cumsum(lw, axis=0)
    cs_prev = cs - lw
    h = state_scr[...]

    o = (r * jnp.exp(cs_prev)) @ h                  # inter-chunk
    diff = cs_prev[:, None, :] - cs[None, :, :]     # (t, j, K)
    tri = jnp.tril(jnp.ones((lc, lc), jnp.bool_), k=-1)
    a = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)
    A = jnp.einsum("tk,jk,tjk->tj", r, k, a)
    o = o + A @ v                                   # intra-chunk
    o = o + jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    o_ref[0, :, 0, :] = o.astype(o_ref.dtype)

    wsum = cs[-1]
    kdec = k * jnp.exp(wsum[None, :] - cs)
    state_scr[...] = jnp.exp(wsum)[:, None] * h + kdec.T @ v

    @pl.when(pl.program_id(2) == n - 1)
    def _emit():
        sf_ref[0, 0] = state_scr[...].astype(sf_ref.dtype)


def linear_attn_bshk_pallas(r, k, v, logw, u, state0, *, chunk: int = 64,
                            interpret: bool = True):
    """r, k, logw: (B, S, H, K); v: (B, S, H, V); u: (H, K);
    state0: (B, H, K, V).  S must be a multiple of `chunk` (padded k/logw
    rows must be zero: k=0 contributes nothing, logw=0 preserves state).
    Returns (o: (B, S, H, V), final_state: (B, H, K, V))."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    assert S % chunk == 0
    n = S // chunk
    o, sf = pl.pallas_call(
        functools.partial(_kernel_bshk, lc=chunk, n=n),
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, V), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, V), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, H, V), r.dtype),
                   jax.ShapeDtypeStruct((B, H, K, V), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u, state0)
    return o, sf


def linear_attn_pallas(r, k, v, logw, u, *, chunk: int = 64,
                       interpret: bool = True):
    """r,k,logw: (BH, S, K); v: (BH, S, V); u: (BH, K).
    S must be a multiple of `chunk` (ops.linear_attn pads).
    Returns (o: (BH, S, V), final_state: (BH, K, V))."""
    BH, S, K = r.shape
    V = v.shape[-1]
    assert S % chunk == 0
    n = S // chunk

    def reshape(x):
        return x.reshape(BH, n, chunk, x.shape[-1])

    rr, kk, vv, ww = map(reshape, (r, k, v, logw))

    o = pl.pallas_call(
        functools.partial(_kernel, lc=chunk),
        grid=(BH, n),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, V), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, K), lambda b, c: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, V), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, n, chunk, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, ww, u)
    return o.reshape(BH, S, V)
