"""SSM blocks: RWKV6 (Finch) time/channel mixing and Mamba2 (SSD).

Both use the same *chunked parallel scan* structure for train/prefill:
sequence is split into chunks; within a chunk the recurrence is evaluated in
closed form (O(Lc^2) masked einsum — this is the part the Pallas
`linear_attn` kernel accelerates on TPU), across chunks a `lax.scan` carries
the recurrent state.  Decode is the exact one-step recurrence on a carried
state, so "KV cache" size is O(1) in sequence length — this is what makes the
long_500k cells runnable for rwkv6-1.6b / zamba2-7b.

Numerical notes:
- decays are handled in log space; intra-chunk decay differences are
  evaluated inside a masked (Lc, Lc) block so no exp() of a positive sum of
  logs ever occurs (stable for arbitrary chunk length).
- RWKV6 follows the Finch formulation o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T),
  S_t = diag(w_t) S_{t-1} + k_t v_t^T with data-dependent w_t produced by a
  low-rank (LoRA) head on the token-shifted input.  We use first-order token
  shift mixing (RWKV5-style mu) + the LoRA decay head; the higher-order DDLerp
  data-dependence on the *mix* coefficients is simplified away (documented in
  DESIGN.md §2.1 — it does not change dataflow shape or cost).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.modules import dense, dense_init
from repro.parallel.hints import hint

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _shift(x: jnp.ndarray, prev: jnp.ndarray) -> jnp.ndarray:
    """Token shift: returns the previous token's features.

    x: (B, S, d); prev: (B, d) — feature vector of the token before x[:, 0].
    """
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _chunk(x: jnp.ndarray, lc: int) -> Tuple[jnp.ndarray, int, int]:
    """(B, S, ...) -> (B, n, lc, ...) with zero padding."""
    B, S = x.shape[0], x.shape[1]
    n = -(-S // lc)
    pad = n * lc - S
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    return x.reshape((B, n, lc) + x.shape[2:]), n, S


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

class RWKVState(NamedTuple):
    wkv: jnp.ndarray       # (B, H, K, V)
    shift_t: jnp.ndarray   # (B, d) time-mix shift
    shift_c: jnp.ndarray   # (B, d) channel-mix shift


def rwkv_num_heads(cfg: ArchConfig) -> int:
    return cfg.d_model // cfg.ssm.head_dim


def init_rwkv_block(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    dt = cfg.param_dtype
    H = rwkv_num_heads(cfg)
    K = cfg.ssm.head_dim
    lora = max(32, d // 32)
    ks = jax.random.split(key, 12)
    return {
        "mu_r": jnp.full((d,), 0.5, dt), "mu_k": jnp.full((d,), 0.5, dt),
        "mu_v": jnp.full((d,), 0.5, dt), "mu_w": jnp.full((d,), 0.5, dt),
        "mu_g": jnp.full((d,), 0.5, dt),
        "w_r": dense_init(ks[0], d, d, dt),
        "w_k": dense_init(ks[1], d, d, dt),
        "w_v": dense_init(ks[2], d, d, dt),
        "w_g": dense_init(ks[3], d, d, dt),
        "w_o": dense_init(ks[4], d, d, dt,
                          scale=1.0 / (d ** 0.5 * (2 * cfg.num_layers) ** 0.5)),
        # data-dependent decay LoRA head: d -> lora -> d
        "w_decay_a": dense_init(ks[5], d, lora, dt),
        "w_decay_b": dense_init(ks[6], lora, d, dt, scale=0.01),
        "decay_base": jnp.full((d,), -6.0, dt),   # w = exp(-exp(.)) ~ 0.9975
        "bonus_u": jnp.zeros((H, K), dt),
        "ln_scale": jnp.ones((H, K), dt),         # per-head groupnorm
        "ln_bias": jnp.zeros((H, K), dt),
        # channel mix
        "mu_ck": jnp.full((d,), 0.5, dt), "mu_cr": jnp.full((d,), 0.5, dt),
        "w_ck": dense_init(ks[7], d, cfg.d_ff, dt),
        "w_cv": dense_init(ks[8], cfg.d_ff, d, dt,
                           scale=1.0 / (cfg.d_ff ** 0.5 * (2 * cfg.num_layers) ** 0.5)),
        "w_cr": dense_init(ks[9], d, d, dt),
    }


def _wkv_chunked(r, k, v, logw, u, state0, lc: int):
    """Chunked RWKV6 linear attention.

    r,k: (B,S,H,K); v: (B,S,H,V); logw: (B,S,H,K) (negative log decays);
    u: (H,K); state0: (B,H,K,V).  Returns (out (B,S,H,V), state (B,H,K,V)).
    """
    B, S, H, K = r.shape
    V = v.shape[-1]
    lc = min(lc, S)
    rc, n, S0 = _chunk(r, lc)
    kc, _, _ = _chunk(k, lc)
    vc, _, _ = _chunk(v, lc)
    wc, _, _ = _chunk(logw, lc)

    # mask padded positions: decay 1 (log 0), k=0 so they do not contribute
    if n * lc != S0:
        valid = (jnp.arange(n * lc) < S0).reshape(1, n, lc, 1, 1)
        kc = kc * valid
        wc = wc * valid

    cs = jnp.cumsum(wc, axis=2)                      # (B,n,lc,H,K) inclusive
    cs_prev = cs - wc                                 # exclusive cumsum

    def step(h, inputs):
        rcb, kcb, vcb, csb, csb_prev, wsum = inputs   # (B,lc,H,K) etc
        # inter-chunk: o_t += (r_t * exp(cs_prev_t)) @ h
        r_dec = rcb * jnp.exp(csb_prev)
        # saralint: ok[dispatch-escape] WKV recurrence readout against the running state, all activations
        o_inter = jnp.einsum("blhk,bhkv->blhv", r_dec, h)
        # intra-chunk: A[t,j] = sum_k r[t,k] k[j,k] exp(cs_prev[t,k]-cs[j,k]), j<t
        diff = csb_prev[:, :, None] - csb[:, None, :, :, :]   # (B,t,j,H,K)
        tri = jnp.tril(jnp.ones((lc, lc), bool), k=-1)
        diff = jnp.where(tri[None, :, :, None, None], diff, -1e30)
        # saralint: ok[dispatch-escape] intra-chunk decay-weighted receptance x key, all activations
        A = jnp.einsum("bthk,bjhk,btjhk->bthj",
                       rcb, kcb, jnp.exp(diff))
        # saralint: ok[dispatch-escape] intra-chunk mix against values, all activations
        o_intra = jnp.einsum("bthj,bjhv->bthv", A, vcb)
        # bonus diagonal: o_t += (r_t * u * k_t) . v_t
        # saralint: ok[dispatch-escape] elementwise diagonal bonus reduction, not a GEMM site
        diag = jnp.einsum("blhk,blhk->blh", rcb * u[None, None], kcb)
        o_diag = diag[..., None] * vcb
        # state update: h' = exp(wsum) h + sum_j exp(wsum - cs_j) k_j v_j^T
        kdec = kcb * jnp.exp(wsum[:, None] - csb)
        # saralint: ok[dispatch-escape] WKV state update (key x value outer product), all activations
        kv_outer = jnp.einsum("blhk,blhv->bhkv", kdec, vcb)
        h_new = jnp.exp(wsum)[:, :, :, None] * h + kv_outer
        return h_new, o_inter + o_intra + o_diag

    wsum = cs[:, :, -1]                               # (B,n,H,K)
    inputs = (jnp.moveaxis(rc, 1, 0), jnp.moveaxis(kc, 1, 0),
              jnp.moveaxis(vc, 1, 0), jnp.moveaxis(cs, 1, 0),
              jnp.moveaxis(cs_prev, 1, 0), jnp.moveaxis(wsum, 1, 0))
    # remat the chunk body: the (B,lc,lc,H,K) decay tensor is recomputed in
    # backward instead of being saved for every chunk.
    state, out = jax.lax.scan(jax.checkpoint(step), state0, inputs)
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * lc, H, V)[:, :S0]
    return out, state


def _wkv_pallas_sharded(r, k, v, logw, u, state0, cfg: ArchConfig):
    """Route the WKV scan through the Pallas kernel, per-shard.

    Heads shard over `model` when divisible (rwkv6-1.6b: 32 heads / 16 = 2
    per device); batch over the data axes.  The kernel's VMEM-resident
    (lc, lc) decay block is the §Perf lever for the rwkv prefill cells.
    """
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as kops
    from repro.parallel.hints import current_layout, current_mesh

    S = r.shape[1]
    chunk = min(cfg.ssm.chunk, S)
    kw = dict(chunk=chunk)
    mesh = current_mesh()
    if mesh is None:
        return kops.wkv_attention(r, k, v, logw, u, state0, **kw)

    def asize(names):
        n = 1
        for a in names:
            n *= mesh.devices.shape[mesh.axis_names.index(a)]
        return n

    B, _, H, _ = r.shape
    b_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if current_layout().startswith("dp_all"):
        b_axes = b_axes + ("model",)
    b_ax = b_axes if B % asize(b_axes) == 0 else None
    m_sz = asize(("model",)) if ("model" in mesh.axis_names
                                 and current_layout() == "tp") else 0
    h_ax = "model" if (m_sz and H % m_sz == 0) else None
    seq = P(b_ax, None, h_ax, None)
    f = _jax.shard_map(
        lambda r_, k_, v_, w_, u_, s_: kops.wkv_attention(r_, k_, v_, w_,
                                                          u_, s_, **kw),
        mesh=mesh, in_specs=(seq, seq, seq, seq, P(h_ax, None),
                             P(b_ax, h_ax, None, None)),
        out_specs=(seq, P(b_ax, h_ax, None, None)), check_vma=False)
    return f(r, k, v, logw, u, state0)


def rwkv_block_apply(params: Params, x: jnp.ndarray, cfg: ArchConfig,
                     state: RWKVState) -> Tuple[jnp.ndarray, RWKVState]:
    """Full RWKV6 block (time mix + channel mix), pre-norm residuals handled
    by the caller.  x: (B,S,d) normalized input for time-mix."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    H = rwkv_num_heads(cfg)
    K = cfg.ssm.head_dim
    x = x.astype(cdt)

    xx = _shift(x, state.shift_t.astype(cdt))

    def mix(mu):
        return x + (xx - x) * mu.astype(cdt)

    xr, xk, xv, xw, xg = (mix(params[m]) for m in
                          ("mu_r", "mu_k", "mu_v", "mu_w", "mu_g"))
    r = hint(dense(xr, params["w_r"], None, cdt, site="ssm.r").reshape(B, S, H, K),
             "B", None, "M", None)
    k = hint(dense(xk, params["w_k"], None, cdt, site="ssm.k").reshape(B, S, H, K),
             "B", None, "M", None)
    v = hint(dense(xv, params["w_v"], None, cdt, site="ssm.v").reshape(B, S, H, K),
             "B", None, "M", None)
    g = jax.nn.silu(dense(xg, params["w_g"], None, cdt, site="ssm.g"))

    # data-dependent decay (log space, always <= -exp(-10) < 0)
    lora = jnp.tanh(dense(xw, params["w_decay_a"], None, cdt, site="ssm.decay_a"))
    dec = dense(lora, params["w_decay_b"], None, cdt, site="ssm.decay_b") + \
        params["decay_base"].astype(cdt)
    logw = -jnp.exp(jnp.clip(dec, -12.0, 1.0)).astype(jnp.float32)  # (B,S,d)
    logw = logw.reshape(B, S, H, K)

    wkv_args = (r.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), logw,
                params["bonus_u"].astype(jnp.float32),
                hint(state.wkv.astype(jnp.float32), "B", "M", None, None))
    if cfg.ssm_impl == "pallas":
        out, wkv_state = _wkv_pallas_sharded(*wkv_args, cfg)
    else:
        out, wkv_state = _wkv_chunked(*wkv_args, cfg.ssm.chunk)
    out = hint(out, "B", None, "M", None)

    # per-head groupnorm
    mu = jnp.mean(out, axis=-1, keepdims=True)
    var = jnp.var(out, axis=-1, keepdims=True)
    out = (out - mu) * jax.lax.rsqrt(var + 64e-5)
    out = out * params["ln_scale"].astype(jnp.float32) + \
        params["ln_bias"].astype(jnp.float32)
    out = (out.reshape(B, S, d).astype(cdt)) * g
    y_time = dense(out, params["w_o"], None, cdt, site="ssm.out")

    # ---- channel mix ------------------------------------------------------
    xc = x + y_time           # pre-norm simplification: mix on residual stream
    xxc = _shift(xc, state.shift_c.astype(cdt))
    xck = xc + (xxc - xc) * params["mu_ck"].astype(cdt)
    xcr = xc + (xxc - xc) * params["mu_cr"].astype(cdt)
    kk = jnp.square(jax.nn.relu(dense(xck, params["w_ck"], None, cdt, site="ssm.channel_k")))
    vv = dense(kk, params["w_cv"], None, cdt, site="ssm.channel_v")
    rr = jax.nn.sigmoid(dense(xcr, params["w_cr"], None, cdt, site="ssm.channel_r"))
    y = y_time + rr * vv

    new_state = RWKVState(
        wkv=wkv_state.astype(state.wkv.dtype),
        shift_t=x[:, -1, :].astype(state.shift_t.dtype),
        shift_c=xc[:, -1, :].astype(state.shift_c.dtype))
    return y.astype(x.dtype), new_state


def init_rwkv_state(cfg: ArchConfig, batch: int, dtype) -> RWKVState:
    H = rwkv_num_heads(cfg)
    K = cfg.ssm.head_dim
    return RWKVState(
        wkv=jnp.zeros((batch, H, K, K), jnp.float32),
        shift_t=jnp.zeros((batch, cfg.d_model), dtype),
        shift_c=jnp.zeros((batch, cfg.d_model), dtype))


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------

class MambaState(NamedTuple):
    ssm: jnp.ndarray        # (B, H, P, N)
    conv: jnp.ndarray       # (B, W-1, conv_channels)


def mamba_dims(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = s.num_heads or d_inner // s.head_dim
    return d_inner, H, s.head_dim, s.state_dim


def init_mamba_block(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    dt = cfg.param_dtype
    d_inner, H, P, N = mamba_dims(cfg)
    conv_ch = d_inner + 2 * N       # x ++ B ++ C  (n_groups = 1)
    ks = jax.random.split(key, 4)
    return {
        "w_in": dense_init(ks[0], d, 2 * d_inner + 2 * N + H, dt),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm.conv_width, conv_ch),
                                     jnp.float32) * 0.1).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(dt),
        "dt_bias": jnp.full((H,), -2.0, dt),
        "D": jnp.ones((H,), dt),
        "norm_scale": jnp.zeros((d_inner,), dt),
        "w_out": dense_init(ks[3], d_inner, d, dt,
                            scale=1.0 / (d_inner ** 0.5 * (2 * cfg.num_layers) ** 0.5)),
    }


def _ssd_chunked(xh, Bm, Cm, loga, state0, lc: int):
    """Chunked SSD scan.

    xh: (B,S,H,P) — dt-scaled inputs;  Bm, Cm: (B,S,N);  loga: (B,S,H) (<=0);
    state0: (B,H,P,N).  Returns (y (B,S,H,P), state).
    """
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    lc = min(lc, S)
    xc, n, S0 = _chunk(xh, lc)
    bc, _, _ = _chunk(Bm, lc)
    cc, _, _ = _chunk(Cm, lc)
    ac, _, _ = _chunk(loga, lc)
    if n * lc != S0:
        valid = (jnp.arange(n * lc) < S0).reshape(1, n, lc)
        xc = xc * valid[..., None, None]
        ac = ac * valid[..., None]

    cs = jnp.cumsum(ac, axis=2)                       # (B,n,lc,H) inclusive
    cs_prev = cs - ac

    def step(h, inputs):
        xb, bb, cb, csb, csb_prev, asum = inputs
        # inter: y_t += exp(cs_prev_t) * C_t . h     -- careful: state h already
        # includes decay up to chunk start; token t sees h decayed by cs_prev_t
        # PLUS its own a_t?  Recurrence h_t = exp(a_t) h_{t-1} + x_t B_t^T means
        # y_t = C_t . h_t, so h_0 is decayed by cs_t (inclusive).
        # saralint: ok[dispatch-escape] SSD recurrence readout against the running state, all activations
        y_inter = jnp.einsum("bln,bhpn,blh->blhp", cb, h, jnp.exp(csb))
        # intra: y_t += sum_{j<=t} exp(cs_t - cs_j) (C_t.B_j) x_j
        diff = csb[:, :, None] - csb[:, None, :, :]   # (B,t,j,H)
        tri = jnp.tril(jnp.ones((lc, lc), bool))
        diff = jnp.where(tri[None, :, :, None], diff, -1e30)
        # saralint: ok[dispatch-escape] intra-chunk C.B interaction, all activations
        G = jnp.einsum("btn,bjn->btj", cb, bb)        # (B,t,j)
        M = G[:, :, :, None] * jnp.exp(diff)          # (B,t,j,H)
        # saralint: ok[dispatch-escape] intra-chunk mix against inputs, all activations
        y_intra = jnp.einsum("btjh,bjhp->bthp", M, xb)
        # state: h' = exp(asum) h + sum_j exp(asum - cs_j) x_j B_j^T
        dec = jnp.exp(asum[:, None] - csb)            # (B,lc,H)
        # saralint: ok[dispatch-escape] SSD state update (input x B outer product), all activations
        xb_outer = jnp.einsum("blhp,bln,blh->bhpn", xb, bb, dec)
        h_new = jnp.exp(asum)[:, :, None, None] * h + xb_outer
        return h_new, y_inter + y_intra

    asum = cs[:, :, -1]
    inputs = (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(bc, 1, 0),
              jnp.moveaxis(cc, 1, 0), jnp.moveaxis(cs, 1, 0),
              jnp.moveaxis(cs_prev, 1, 0), jnp.moveaxis(asum, 1, 0))
    state, y = jax.lax.scan(jax.checkpoint(step), state0, inputs)
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * lc, H, P)[:, :S0]
    return y, state


def mamba_block_apply(params: Params, x: jnp.ndarray, cfg: ArchConfig,
                      state: MambaState) -> Tuple[jnp.ndarray, MambaState]:
    """x: (B,S,d) normalized input.  Returns (y, new_state)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    d_inner, H, P, N = mamba_dims(cfg)
    W = cfg.ssm.conv_width
    x = x.astype(cdt)

    zxbcdt = hint(dense(x, params["w_in"], None, cdt, site="ssm.in_proj"), "B", None, None)
    z, xBC, dt_raw = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * N], axis=-1)

    # causal depthwise conv over (x ++ B ++ C)
    conv_in = jnp.concatenate([state.conv.astype(cdt), xBC], axis=1)
    new_conv = conv_in[:, -(W - 1):, :] if W > 1 else state.conv
    wts = params["conv_w"].astype(cdt)
    xBC = sum(conv_in[:, i:i + S, :] * wts[i][None, None, :] for i in range(W))
    xBC = jax.nn.silu(xBC + params["conv_b"].astype(cdt))

    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt_h = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                           params["dt_bias"].astype(jnp.float32))   # (B,S,H)
    loga = -jnp.exp(params["A_log"].astype(jnp.float32))[None, None, :] * dt_h
    xh = xs.astype(jnp.float32) * dt_h[..., None]

    xh = hint(xh, "B", None, "M", None)
    y, new_ssm = _ssd_chunked(xh, Bm.astype(jnp.float32),
                              Cm.astype(jnp.float32), loga,
                              hint(state.ssm.astype(jnp.float32),
                                   "B", "M", None, None), cfg.ssm.chunk)
    y = hint(y, "B", None, "M", None)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] * \
        xs.astype(jnp.float32)
    y = y.reshape(B, S, d_inner).astype(cdt)

    # normalized gating (mamba2): rmsnorm(y) * silu(z)
    y32 = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    y = (y32 * jax.lax.rsqrt(var + cfg.norm_eps) *
         (1.0 + params["norm_scale"].astype(jnp.float32))).astype(cdt)
    y = y * jax.nn.silu(z)
    out = dense(y, params["w_out"], None, cdt, site="ssm.out_proj")

    new_state = MambaState(ssm=new_ssm.astype(state.ssm.dtype),
                           conv=new_conv.astype(state.conv.dtype))
    return out.astype(x.dtype), new_state


def init_mamba_state(cfg: ArchConfig, batch: int, dtype) -> MambaState:
    d_inner, H, P, N = mamba_dims(cfg)
    conv_ch = d_inner + 2 * N
    return MambaState(
        ssm=jnp.zeros((batch, H, P, N), jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm.conv_width - 1, conv_ch), dtype))
