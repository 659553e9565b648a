"""Flash attention Pallas kernel (fwd + bwd), causal or full, GQA-aware.

The TPU adaptation of the paper's operand-reuse argument (DESIGN.md §2.2):
the (block_q, block_k) score/probability tiles live ONLY in VMEM — HBM sees
q/k/v/o blocks, never an S x S intermediate.  The XLA blockwise-scan path
(models/attention.py `_chunked_attn`) materializes every score block at a
fusion boundary; this kernel is the §Perf lever that removes that traffic.

Block scheduling uses a *pair list* prefetched as scalars (PrefetchScalarGrid):
the grid's last dimension enumerates exactly the (q-block, kv-block) pairs
that matter — lower-triangular for causal attention — so causal skip is a
real traffic reduction, not masked compute.  The (m, l, acc) running softmax
state lives in VMEM scratch, reset at each row start and emitted on the
row's last pair (same revisiting discipline as kernels/linear_attn.py).

Backward follows the standard two-kernel flash decomposition:
  dq : i-major pair order (same as fwd), accumulate ds @ k over kv blocks.
  dkv: j-major pair order, accumulate p^T do / ds^T q over q blocks,
       per q-head; the G group heads are reduced outside.
using the saved lse = m + log(l) and delta = rowsum(do * o).

Layouts are model-native (B, S, H, hd) — no transposes at the call site.
All shapes must be pre-padded to block multiples (kernels/ops.py pads and
masks with kv_len).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _pairs(n_q: int, n_k: int, bq: int, bk: int, causal: bool,
           order: str) -> np.ndarray:
    """(4, n_pairs) int32: q-block i, kv-block j, start flag, emit flag.

    Causal enumerates only (i, j) block pairs that overlap the lower
    triangle: some (row, col) with row >= col, i.e. (i+1)*bq - 1 >= j*bk.
    """
    def overlap(i: int, j: int) -> bool:
        return (not causal) or ((i + 1) * bq - 1 >= j * bk)

    if order == "i":      # i-major (fwd, dq): row i accumulates over j
        ps = [(i, j) for i in range(n_q) for j in range(n_k) if overlap(i, j)]
        key = 0
    else:                 # j-major (dkv): column j accumulates over i
        ps = []
        for j in range(n_k):
            js = [(i, j) for i in range(n_q) if overlap(i, j)]
            # a kv block past every q row (padded kv): visit once, fully
            # masked, so its dk/dv output block is written (= zeros)
            ps.extend(js if js else [(n_q - 1, j)])
        key = 1
    start = [t == 0 or ps[t][key] != ps[t - 1][key] for t in range(len(ps))]
    emit = [t == len(ps) - 1 or ps[t][key] != ps[t + 1][key]
            for t in range(len(ps))]
    return np.array([[p[0] for p in ps], [p[1] for p in ps],
                     [int(s) for s in start], [int(e) for e in emit]],
                    dtype=np.int32)


def _mask(s, i, j, bq, bk, kv_len: int, causal: bool):
    """Apply kv-validity and causal masking to an (bq, bk) score tile."""
    row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = col < kv_len
    if causal:
        m = m & (col <= row)
    return jnp.where(m, s, NEG)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(ij, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, bq, bk, kv_len, causal, scale):
    p = pl.program_id(2)
    i, j = ij[0, p], ij[1, p]

    @pl.when(ij[2, p] == 1)
    def _reset():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :]                                 # (bq, hd)
    k = k_ref[0, :, 0, :]                                 # (bk, hd)
    v = v_ref[0, :, 0, :]                                 # (bk, hd_v)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask(s, i, j, bq, bk, kv_len, causal)

    m_prev, l_prev = m_scr[0], l_scr[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    pexp = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(pexp, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[0], l_scr[0] = m_new, l_new

    @pl.when(ij[3, p] == 1)
    def _emit():
        l = jnp.maximum(l_scr[0], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :] = m_scr[0] + jnp.log(l)


def _flash_fwd(q, k, v, *, causal: bool, scale: float, kv_len: int,
               bq: int, bk: int, interpret: bool
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, Sq, H, hd = q.shape
    _, Skv, KVH, hd_v = v.shape
    G = H // KVH
    n_q, n_k = Sq // bq, Skv // bk
    ij = jnp.asarray(_pairs(n_q, n_k, bq, bk, causal, "i"))

    grid = (B, H, ij.shape[1])
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, kv_len=kv_len,
                          causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, 1, hd),
                             lambda b, h, p, ij: (b, ij[0, p], h, 0)),
                pl.BlockSpec((1, bk, 1, hd),
                             lambda b, h, p, ij: (b, ij[1, p], h // G, 0)),
                pl.BlockSpec((1, bk, 1, hd_v),
                             lambda b, h, p, ij: (b, ij[1, p], h // G, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, 1, hd_v),
                             lambda b, h, p, ij: (b, ij[0, p], h, 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, h, p, ij: (b, h, ij[0, p])),
            ],
            scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32),
                            pltpu.VMEM((1, bq), jnp.float32),
                            pltpu.VMEM((bq, hd_v), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H, hd_v), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ij, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(ij, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_scr, *, bq, bk, kv_len, causal, scale):
    p = pl.program_id(2)
    i, j = ij[0, p], ij[1, p]

    @pl.when(ij[2, p] == 1)
    def _reset():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :]
    k = k_ref[0, :, 0, :]
    v = v_ref[0, :, 0, :]
    do = do_ref[0, :, 0, :].astype(jnp.float32)           # (bq, hd_v)
    lse = lse_ref[0, 0, :]                                # (bq,)
    delta = dl_ref[0, 0, :]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask(s, i, j, bq, bk, kv_len, causal)
    pexp = jnp.exp(s - lse[:, None])                      # (bq, bk)
    dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = pexp * (dp - delta[:, None]) * scale             # (bq, bk)
    acc_scr[...] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ij[3, p] == 1)
    def _emit():
        dq_ref[0, :, 0, :] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(ij, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, bq, bk, kv_len, causal,
                scale):
    p = pl.program_id(2)
    i, j = ij[0, p], ij[1, p]

    @pl.when(ij[2, p] == 1)
    def _reset():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, :, 0, :]
    k = k_ref[0, :, 0, :]
    v = v_ref[0, :, 0, :]
    do = do_ref[0, :, 0, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :]
    delta = dl_ref[0, 0, :]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask(s, i, j, bq, bk, kv_len, causal)
    pexp = jnp.exp(s - lse[:, None])                      # (bq, bk)
    dv_scr[...] += jax.lax.dot_general(pexp.astype(do.dtype), do,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = pexp * (dp - delta[:, None]) * scale             # (bq, bk)
    dk_scr[...] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(ij[3, p] == 1)
    def _emit():
        dk_ref[0, :, 0, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, :, 0, :] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, *, causal: bool, scale: float,
               kv_len: int, bq: int, bk: int, interpret: bool):
    B, Sq, H, hd = q.shape
    _, Skv, KVH, hd_v = v.shape
    G = H // KVH
    n_q, n_k = Sq // bq, Skv // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)           # (B, H, Sq)

    common = dict(bq=bq, bk=bk, kv_len=kv_len, causal=causal, scale=scale)
    in_specs = [
        pl.BlockSpec((1, bq, 1, hd), lambda b, h, p, ij: (b, ij[0, p], h, 0)),
        pl.BlockSpec((1, bk, 1, hd),
                     lambda b, h, p, ij: (b, ij[1, p], h // G, 0)),
        pl.BlockSpec((1, bk, 1, hd_v),
                     lambda b, h, p, ij: (b, ij[1, p], h // G, 0)),
        pl.BlockSpec((1, bq, 1, hd_v),
                     lambda b, h, p, ij: (b, ij[0, p], h, 0)),
        pl.BlockSpec((1, 1, bq), lambda b, h, p, ij: (b, h, ij[0, p])),
        pl.BlockSpec((1, 1, bq), lambda b, h, p, ij: (b, h, ij[0, p])),
    ]

    ij_i = jnp.asarray(_pairs(n_q, n_k, bq, bk, causal, "i"))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, ij_i.shape[1]),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, bq, 1, hd),
                                    lambda b, h, p, ij: (b, ij[0, p], h, 0))],
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H, hd), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ij_i, q, k, v, do, lse, delta)[0]

    ij_j = jnp.asarray(_pairs(n_q, n_k, bq, bk, causal, "j"))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, ij_j.shape[1]),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bk, 1, hd),
                             lambda b, h, p, ij: (b, ij[1, p], h, 0)),
                pl.BlockSpec((1, bk, 1, hd_v),
                             lambda b, h, p, ij: (b, ij[1, p], h, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, hd_v), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Skv, H, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, Skv, H, hd_v), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ij_j, q, k, v, do, lse, delta)

    if G > 1:   # reduce the per-q-head dk/dv over each kv head's group
        dk = dk.reshape(B, Skv, KVH, G, hd).sum(axis=3)
        dv = dv.reshape(B, Skv, KVH, G, hd_v).sum(axis=3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_pallas(q, k, v, causal: bool, scale: float, kv_len: int,
                           bq: int, bk: int, interpret: bool):
    """q: (B, Sq, H, hd); k: (B, Skv, KVH, hd); v: (B, Skv, KVH, hd_v).
    Sq % bq == 0, Skv % bk == 0 (kernels/ops.py pads); kv positions >= kv_len
    are masked.  Returns (B, Sq, H, hd_v)."""
    o, _ = _flash_fwd(q, k, v, causal=causal, scale=scale, kv_len=kv_len,
                      bq=bq, bk=bk, interpret=interpret)
    return o


def _vjp_fwd(q, k, v, causal, scale, kv_len, bq, bk, interpret):
    o, lse = _flash_fwd(q, k, v, causal=causal, scale=scale, kv_len=kv_len,
                        bq=bq, bk=bk, interpret=interpret)
    return o, (q, k, v, o, lse)

def _vjp_bwd(causal, scale, kv_len, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, causal=causal, scale=scale,
                            kv_len=kv_len, bq=bq, bk=bk, interpret=interpret)
    return dq, dk, dv


flash_attention_pallas.defvjp(_vjp_fwd, _vjp_bwd)
