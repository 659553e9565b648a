"""Mixture-of-Experts feed-forward with expert parallelism.

Design (GShard/Switch-style, adapted for pjit-global semantics):

- Routing is computed per *group* (= one batch row), so the top-k sort stays
  local to the data shard that owns the row — no global sort collective.
- Dispatch is sort-based (argsort of expert ids), not one-hot-einsum based:
  memory is O(S·k) per row instead of O(S·E·C).
- Expert buffers have shape (B, E, C, d): B sharded over `data`, E over
  `model` (expert parallelism).  XLA lowers the (B-sharded -> B,E-sharded)
  scatter into the all-to-all this dataflow implies.
- Routed experts are padded up to a multiple of the EP axis so every device
  owns the same number of experts; the router assigns padding experts -inf.
- Capacity per row C = ceil(S·k/E_real · capacity_factor); overflow tokens are
  dropped (their contribution is 0, residual carries them — standard).
- Shared experts (qwen2-moe, deepseek-v3) are an always-on dense GLU applied
  to every token and summed with the routed output.
- The router (``_route``) scores in float32 with a softmax (qwen2-moe) or a
  sigmoid (deepseek-v3), selects on the scores or, under ``noaux_tc``, on
  score + ``e_score_correction_bias``, and weights each chosen expert by
  its unbiased score (normalised over the k, times the scaling factor).

Aux losses: load-balance (Switch) + router-z, returned for logging.

Serving (``moe_serve``, the paged step programs) drops nothing: the live
rows of the whole step route together, are sorted by expert into segments
padded to the grouped GEMM's row tile, and each expert bank runs as one
grouped GEMM (``dispatch.grouped_gemm``) over a buffer sized so that every
routed row has a slot whatever the routing.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import dispatch
from repro.configs.base import ArchConfig
from repro.models.modules import activation, dense, dense_init
from repro.parallel.hints import hint

Params = Dict[str, Any]


def padded_num_experts(cfg: ArchConfig, ep_axis: int = 16) -> int:
    e = cfg.moe.num_experts
    return int(math.ceil(e / ep_axis) * ep_axis)


def row_capacity(seq: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    cap = int(math.ceil(seq * m.experts_per_token / m.num_experts
                        * m.capacity_factor))
    return max(cap, 4)


def init_moe(key, cfg: ArchConfig, ep_axis: int = 16) -> Params:
    m = cfg.moe
    dt = cfg.param_dtype
    E = padded_num_experts(cfg, ep_axis)
    d, f = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 5)

    def expert_bank(k, d_in, d_out, scale=None):
        keys = jax.random.split(k, E)
        w = jnp.stack([dense_init(kk, d_in, d_out, dt, scale=scale) for kk in keys])
        return w                                           # (E, d_in, d_out)

    p = {
        "router": dense_init(ks[0], d, E, "float32", scale=0.02),
        **({"e_score_correction_bias": jnp.zeros((E,), jnp.float32)}
           if m.topk_method == "noaux_tc" else {}),
        "w_gate": expert_bank(ks[1], d, f),
        "w_up": expert_bank(ks[2], d, f),
        "w_down": expert_bank(ks[3], f, d,
                              scale=1.0 / (f ** 0.5 * (2 * cfg.num_layers) ** 0.5)),
    }
    if m.num_shared_experts > 0:
        fs = f * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, d, fs, dt),
            "w_up": dense_init(k2, d, fs, dt),
            "w_down": dense_init(k3, fs, d,
                                 scale=1.0 / (fs ** 0.5 * (2 * cfg.num_layers) ** 0.5)),
        }
    return p


def _route(params: Params, x: jnp.ndarray, cfg: ArchConfig):
    """Top-k experts of each row of x (..., d) and their combine weights.

    Scores in float32, with the router GEMM pinned to XLA so the top-k
    decision is bit-stable across execution backends.  Returns (idx (...,
    k) int32, weights (..., k) f32, scores (..., E_pad) f32 over the real
    experts, router logits)."""
    m = cfg.moe
    E_pad = params["router"].shape[-1]
    logits = dispatch.gemm(x.astype(jnp.float32),
                           params["router"].astype(jnp.float32),
                           site="moe.router", backend="xla")
    real = jnp.arange(E_pad) < m.num_experts
    if m.scoring_func == "softmax":
        logits = jnp.where(real, logits, -1e30)
        scores = jax.nn.softmax(logits, axis=-1)
    elif m.scoring_func == "sigmoid":
        scores = jnp.where(real, jax.nn.sigmoid(logits), 0.0)
    else:
        raise ValueError(f"unknown scoring_func {m.scoring_func!r}")
    if m.topk_method == "noaux_tc":
        choice = jnp.where(real, scores + params["e_score_correction_bias"],
                           -jnp.inf)
        _, idx = jax.lax.top_k(choice, m.experts_per_token)
        weights = jnp.take_along_axis(scores, idx, axis=-1)
    elif m.topk_method == "greedy":
        weights, idx = jax.lax.top_k(scores, m.experts_per_token)
    else:
        raise ValueError(f"unknown topk_method {m.topk_method!r}")
    if m.norm_topk_prob:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        weights = weights * m.routed_scaling_factor
    return idx, weights, scores, logits


def _shared_experts(params: Params, x, cfg: ArchConfig, act, cdt):
    sp = params["shared"]
    gs = act(dense(x, sp["w_gate"], None, cdt, site="moe.shared.gate"))
    us = dense(x, sp["w_up"], None, cdt, site="moe.shared.up")
    return dense(gs * us, sp["w_down"], None, cdt, site="moe.shared.down")


def moe_apply(params: Params, x: jnp.ndarray, cfg: ArchConfig
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (B, S, d), aux metrics."""
    m = cfg.moe
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    E_pad = params["router"].shape[-1]
    E = m.num_experts
    k = m.experts_per_token
    C = row_capacity(S, cfg)

    # ---- routing -----------------------------------------------------------
    top_idx, gate_vals, probs, logits = _route(params, x, cfg)

    # ---- sort-based dispatch, vmapped over rows ---------------------------
    def dispatch_row(xr, idxr, gater):
        # xr: (S,d); idxr: (S,k); gater: (S,k)
        flat_e = idxr.reshape(-1)                            # (S*k,)
        flat_g = gater.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(S), k)
        order = jnp.argsort(flat_e, stable=True)
        se, sg, st = flat_e[order], flat_g[order], flat_tok[order]
        # position within each expert's run
        pos = jnp.arange(S * k) - jnp.searchsorted(se, se, side="left")
        keep = pos < C
        dest = jnp.where(keep, se * C + pos, E_pad * C)      # overflow -> dropped
        buf = jnp.zeros((E_pad * C + 1, d), cdt)
        buf = buf.at[dest].add(xr[st].astype(cdt) * keep[:, None].astype(cdt))
        return buf[:-1].reshape(E_pad, C, d), dest, st, sg, keep

    buf, dest, st, sg, keep = jax.vmap(dispatch_row)(x, top_idx, gate_vals)
    buf = hint(buf, "B", "E", None, None)     # EP: experts over `model`
    # buf: (B, E_pad, C, d)

    # ---- expert computation (EP: E sharded over `model`); the expert-bank
    # GEMMs go through the dispatch layer (one RSA GEMM per expert) ---------
    act = activation(cfg.mlp_activation)
    g = dispatch.gemm(buf, params["w_gate"].astype(cdt),
                      site="moe.expert.gate")
    u = dispatch.gemm(buf, params["w_up"].astype(cdt), site="moe.expert.up")
    h = act(g) * u
    out_buf = hint(dispatch.gemm(h, params["w_down"].astype(cdt),
                                 site="moe.expert.down"),
                   "B", "E", None, None)

    # ---- combine back ------------------------------------------------------
    def combine_row(out_b, dest_r, st_r, sg_r, keep_r):
        flat = out_b.reshape(E_pad * C, d)
        gathered = flat[jnp.minimum(dest_r, E_pad * C - 1)]
        contrib = gathered * (sg_r * keep_r)[:, None].astype(cdt)
        y = jnp.zeros((S, d), cdt).at[st_r].add(contrib)
        return y

    y = hint(jax.vmap(combine_row)(out_buf, dest, st, sg, keep),
             "B", None, None)

    # ---- shared experts ----------------------------------------------------
    if "shared" in params:
        y = y + _shared_experts(params, x, cfg, act, cdt)

    # ---- aux losses --------------------------------------------------------
    # load-balance: E * sum_e f_e * p_e   (Switch), over real experts
    me = jnp.mean(probs[..., :E].reshape(-1, E), axis=0)
    one_hot_top1 = jax.nn.one_hot(top_idx[..., 0], E_pad)[..., :E]
    fe = jnp.mean(one_hot_top1.reshape(-1, E), axis=0)
    lb_loss = E * jnp.sum(me * fe)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
           "moe_drop_frac": drop_frac}
    return y.astype(x.dtype), aux


def group_rows(rows: int, num_experts: int, block_m: int) -> int:
    """Static rows of the serving MoE's grouped buffer: each expert's
    segment is its routed rows rounded up to the row tile, so the segments
    of ``rows`` routed rows over ``num_experts`` experts take at most
    ``rows + num_experts * (block_m - 1)`` rows, whatever the routing."""
    need = rows + num_experts * (block_m - 1)
    return -(-need // block_m) * block_m


def moe_serve(params: Params, x: jnp.ndarray, cfg: ArchConfig,
              live: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The serving MoE over every row of a step, with no capacity limit.

    x: (..., d); live: (...) bool, the rows that route (a masked decode
    lane or a chunk row past its lane's length routes to no expert and
    gets only the shared experts' output).  The live rows' (row, expert)
    pairs are sorted by expert into segments padded to the grouped GEMM's
    row tile, each expert bank runs once over them
    (``dispatch.grouped_gemm``), and each row sums its experts' outputs
    with its combine weights.  Returns (y (..., d), load (2, E_pad) int32:
    the rows routed to each expert, and the rows its segment takes in the
    grouped GEMM, padding included).

    Where ``params`` holds ``bank_layer`` (the paged layer scan,
    ``models/serving.py`` ``_scan_paged_layers``), the banks are the
    whole (L, E, ...) stacks, already in the compute dtype (so the casts
    below emit nothing), and the grouped GEMMs read layer
    ``bank_layer``'s banks in place, with no copy."""
    m = cfg.moe
    cdt = jnp.dtype(cfg.compute_dtype)
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    T, k = x2.shape[0], m.experts_per_token
    E_pad = params["router"].shape[-1]
    idx, weights, _, _ = _route(params, x2, cfg)

    bm = dispatch.group_block_m(T * k, m.num_experts, d, m.d_ff_expert, cdt)
    R = group_rows(T * k, E_pad, bm)
    live_pair = jnp.broadcast_to(live.reshape(T, 1), (T, k)).reshape(-1)
    expert = jnp.where(live_pair, idx.reshape(-1), E_pad)   # E_pad: none
    # pairs sorted by expert (stably, so token order holds in a segment);
    # each pair's row is its expert's segment start + its rank there
    order = jnp.argsort(expert, stable=True)
    e_sorted = expert[order]
    first = jnp.searchsorted(e_sorted, jnp.arange(E_pad + 1), side="left")
    load = jnp.diff(first).astype(jnp.int32)                 # routed rows
    sizes = (load + bm - 1) // bm * bm                       # padded segments
    ends = jnp.cumsum(sizes)
    e_safe = jnp.minimum(e_sorted, E_pad - 1)
    row_sorted = jnp.where(
        e_sorted < E_pad,
        ends[e_safe] - sizes[e_safe] + jnp.arange(T * k) - first[e_safe], R)
    row = jnp.zeros((T * k,), jnp.int32).at[order].set(row_sorted)
    # the buffer gathers its rows (row T of x is zeros: padding)
    src = jnp.full((R,), T, jnp.int32).at[row_sorted].set(order // k,
                                                           mode="drop")
    xs = jnp.concatenate([x2.astype(cdt), jnp.zeros((1, d), cdt)])[src]

    # tile t holds rows of the expert whose segment covers row t * bm; the
    # tiles past the used ones repeat the last used tile's expert
    used = ends[-1] // bm
    tile_first = jnp.arange(R // bm) * bm
    group = jnp.searchsorted(ends, tile_first, side="right")
    last = jnp.searchsorted(ends, jnp.maximum(used - 1, 0) * bm,
                            side="right")
    group = jnp.minimum(jnp.where(tile_first < ends[-1], group, last),
                        E_pad - 1)
    layout = dispatch.GroupLayout(
        group.astype(jnp.int32), used.reshape(1).astype(jnp.int32),
        sizes.astype(jnp.int32), bm,
        dispatch.segment_rows(T * k, m.num_experts))

    act = activation(cfg.mlp_activation)
    layer = params.get("bank_layer")
    g = dispatch.grouped_gemm(xs, params["w_gate"].astype(cdt), layout,
                              site="moe.expert.gate", layer=layer)
    u = dispatch.grouped_gemm(xs, params["w_up"].astype(cdt), layout,
                              site="moe.expert.up", layer=layer)
    o = dispatch.grouped_gemm(act(g) * u, params["w_down"].astype(cdt),
                              layout, site="moe.expert.down", layer=layer)
    out = o[jnp.minimum(row, R - 1)].astype(jnp.float32).reshape(T, k, d)
    keep = (row < R).reshape(T, k, 1)
    y = jnp.sum(jnp.where(keep, out * weights[..., None], 0.0), axis=1)
    y = y.astype(cdt)
    if "shared" in params:
        y = y + _shared_experts(params, x2, cfg, act, cdt)
    return y.reshape(x.shape).astype(x.dtype), jnp.stack([load, sizes])
