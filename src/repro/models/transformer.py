"""Unified model assembly for all assigned architecture families.

Families:
  dense   — pre-norm decoder: GQA/MQA attention + gated MLP
  moe     — attention + (shared + routed top-k) MoE FFN
  ssm     — RWKV6 blocks (attention-free)
  hybrid  — Mamba2 blocks with one weight-shared attention block every
            `shared_attn_every` slots (zamba2-style)
  encdec  — bidirectional encoder + causal decoder with cross-attention
            (audio frontend stub feeds the encoder)
  vlm     — decoder LM with vision-patch embeddings (stub) prepended

All homogeneous layer stacks run under ``jax.lax.scan`` over stacked
parameters (O(1) HLO size — essential for 512-device dry-run compiles), with
optional per-block remat.  Caches for decode are stacked along the layer axis
and scanned in lock-step with the parameters.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (KVCache, cross_attention_kv,
                                    gqa_cross_attention, gqa_self_attention,
                                    init_gqa, init_gqa_cache, init_mla,
                                    init_mla_cache, mla_self_attention)
from repro.models.mlp import init_mlp, mlp_apply
from repro.models.moe import init_moe, moe_apply, moe_serve
from repro.models.modules import (dense, dense_init, embed_init, rmsnorm,
                                  stack_layer_params)
from repro.parallel.hints import hint

Params = Dict[str, Any]

AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_drop_frac")


def _zero_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _is_moe_layer(cfg: ArchConfig, layer_idx: int) -> bool:
    if cfg.moe is None:
        return False
    if layer_idx < cfg.first_dense_layers:
        return False
    return (layer_idx - cfg.first_dense_layers) % cfg.moe_every == 0


def init_decoder_layer(key, cfg: ArchConfig, layer_idx: int,
                       cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    p: Params = {"ln1": jnp.zeros((cfg.d_model,), dt),
                 "ln2": jnp.zeros((cfg.d_model,), dt)}
    if cfg.attention_type == "mla":
        p["attn"] = init_mla(ks[0], cfg)
    else:
        p["attn"] = init_gqa(ks[0], cfg)
    if _is_moe_layer(cfg, layer_idx):
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg)
    if cross:
        p["ln_cross"] = jnp.zeros((cfg.d_model,), dt)
        p["cross"] = init_gqa(ks[2], cfg, d_in=cfg.d_model, cross=True)
    return p


def decoder_layer_apply(p: Params, x, positions, cfg: ArchConfig, *,
                        cache: Optional[KVCache] = None,
                        update_cache: bool = False,
                        enc_kv=None) -> Tuple[jnp.ndarray, Optional[KVCache],
                                              Dict[str, jnp.ndarray]]:
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.attention_type == "mla":
        a, new_cache = mla_self_attention(p["attn"], h, positions, cfg,
                                          cache=cache, update_cache=update_cache)
    else:
        a, new_cache = gqa_self_attention(p["attn"], h, positions, cfg,
                                          cache=cache, update_cache=update_cache)
    x = x + a.astype(x.dtype)
    if enc_kv is not None:
        hc = rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        x = x + gqa_cross_attention(p["cross"], hc, enc_kv, cfg).astype(x.dtype)
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    aux = _zero_aux()
    if "moe" in p:
        f, moe_aux = moe_apply(p["moe"], h2, cfg)
        aux.update(moe_aux)
    else:
        f = mlp_apply(p["mlp"], h2, cfg)
    x = x + f.astype(x.dtype)
    return x, new_cache, aux


def _paged_ffn(p: Params, h, cfg: ArchConfig, live):
    """The feed-forward of a paged step: the serving MoE, which routes the
    ``live`` rows of the whole step with no capacity limit and returns its
    (2, E) expert load (``moe_serve``), or the dense MLP (load None)."""
    if "moe" in p:
        return moe_serve(p["moe"], h, cfg, live)
    return mlp_apply(p["mlp"], h, cfg), None


def paged_decoder_layer_apply(p: Params, x, positions, cfg: ArchConfig, *,
                              k_arena, v_arena, layer, block_tables, kv_lens,
                              write_mask, enc_kv=None):
    """One decoder layer's batched single-token decode through the paged KV
    arena (mirrors :func:`decoder_layer_apply`; see
    models/attention.py::gqa_paged_decode for the arena contract: the
    stacked arena, of which this is layer ``layer``).
    Returns (x, new_k_arena, new_v_arena)."""
    from repro.models.attention import gqa_paged_decode, mla_paged_decode

    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    paged = dict(layer=layer, block_tables=block_tables, kv_lens=kv_lens,
                 write_mask=write_mask)
    if cfg.attention_type == "mla":
        a, nk, nv = mla_paged_decode(p["attn"], h, positions, cfg,
                                     ckv_arena=k_arena, krope_arena=v_arena,
                                     **paged)
    else:
        a, nk, nv = gqa_paged_decode(p["attn"], h, positions, cfg,
                                     k_arena=k_arena, v_arena=v_arena,
                                     **paged)
    x = x + a.astype(x.dtype)
    if enc_kv is not None:
        hc = rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        x = x + gqa_cross_attention(p["cross"], hc, enc_kv, cfg).astype(x.dtype)
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    f, load = _paged_ffn(p, h2, cfg, (write_mask > 0)[:, None])
    x = x + f.astype(x.dtype)
    return x, nk, nv, load


def paged_prefill_layer_apply(p: Params, x, positions, cfg: ArchConfig, *,
                              k_arena, v_arena, layer, block_tables, kv_lens,
                              chunk_lens):
    """One decoder layer's chunked-prefill pass through the paged KV arena
    (mirrors :func:`paged_decoder_layer_apply` widened to C causal rows per
    lane; see models/attention.py::gqa_paged_prefill for the arena
    contract).  Rows at or past a lane's ``chunk_lens`` route to no expert.
    Returns (x, new_k_arena, new_v_arena, expert load or None)."""
    from repro.models.attention import gqa_paged_prefill, mla_paged_prefill

    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    paged = dict(layer=layer, block_tables=block_tables, kv_lens=kv_lens,
                 chunk_lens=chunk_lens)
    if cfg.attention_type == "mla":
        a, nk, nv = mla_paged_prefill(p["attn"], h, positions, cfg,
                                      ckv_arena=k_arena, krope_arena=v_arena,
                                      **paged)
    else:
        a, nk, nv = gqa_paged_prefill(p["attn"], h, positions, cfg,
                                      k_arena=k_arena, v_arena=v_arena,
                                      **paged)
    x = x + a.astype(x.dtype)
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    live = jnp.arange(x.shape[1])[None, :] < chunk_lens[:, None]
    f, load = _paged_ffn(p, h2, cfg, live)
    x = x + f.astype(x.dtype)
    return x, nk, nv, load


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig) -> Params:
    dt = cfg.param_dtype
    keys = jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 8)
    p: Params = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "ln_f": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dt)

    if cfg.frontend.kind == "audio_frames":
        p["frontend"] = {
            "proj": dense_init(keys[2], cfg.frontend.feature_dim, cfg.d_model, dt)}
    elif cfg.frontend.kind == "vision_patches":
        k1, k2 = jax.random.split(keys[2])
        p["frontend"] = {   # 2-layer MLP projector (InternVL-style)
            "proj1": dense_init(k1, cfg.frontend.feature_dim, cfg.d_model, dt),
            "proj2": dense_init(k2, cfg.d_model, cfg.d_model, dt),
        }

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        nd = cfg.first_dense_layers if cfg.moe is not None else 0
        if cfg.moe is not None and cfg.moe_every != 1:
            raise NotImplementedError("moe_every != 1 (stacks must be "
                                      "homogeneous for scan)")
        if nd:
            p["dense_layers"] = stack_layer_params(
                [init_decoder_layer(keys[8 + i], cfg, i) for i in range(nd)])
        layers = [init_decoder_layer(keys[8 + i], cfg, i)
                  for i in range(nd, cfg.num_layers)]
        p["layers"] = stack_layer_params(layers)
    elif fam == "ssm":
        layers = [{"ln1": jnp.zeros((cfg.d_model,), dt),
                   **{"blk": ssm_mod.init_rwkv_block(keys[8 + i], cfg)}}
                  for i in range(cfg.num_layers)]
        p["layers"] = stack_layer_params(layers)
    elif fam == "hybrid":
        n_m, n_groups, per_group, rem = hybrid_layout(cfg)
        layers = [{"ln1": jnp.zeros((cfg.d_model,), dt),
                   "blk": ssm_mod.init_mamba_block(keys[8 + i], cfg)}
                  for i in range(n_m)]
        p["layers"] = stack_layer_params(layers)
        p["shared_attn"] = init_decoder_layer(keys[4], cfg, layer_idx=-1)
    elif fam == "encdec":
        enc = [init_encoder_layer(keys[8 + i], cfg)
               for i in range(cfg.encoder_layers)]
        dec = [init_decoder_layer(keys[8 + cfg.encoder_layers + i], cfg, i,
                                  cross=True)
               for i in range(cfg.num_layers)]
        p["enc_layers"] = stack_layer_params(enc)
        p["layers"] = stack_layer_params(dec)
        p["ln_enc"] = jnp.zeros((cfg.d_model,), dt)
    else:
        raise ValueError(f"unknown family {fam}")
    return p


def init_encoder_layer(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 2)
    dt = cfg.param_dtype
    return {
        "ln1": jnp.zeros((cfg.d_model,), dt),
        "ln2": jnp.zeros((cfg.d_model,), dt),
        "attn": init_gqa(ks[0], cfg),
        "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg),
    }


def hybrid_layout(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    """(num_mamba_layers, num_groups, mamba_per_group, remainder).

    Layer slots: every `shared_attn_every`-th slot is the shared attention
    block; the rest are Mamba2 blocks.  num_layers counts all slots.
    """
    k = cfg.shared_attn_every
    n_groups = cfg.num_layers // k
    per_group = k - 1
    rem = cfg.num_layers - n_groups * k
    n_m = n_groups * per_group + rem
    return n_m, n_groups, per_group, rem


# ---------------------------------------------------------------------------
# forward passes (train: no cache)
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _embed(params, tokens, cfg: ArchConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    return hint(params["embed"][tokens].astype(cdt), "B", None, None)


def _frontend_embed(params, feats, cfg: ArchConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.frontend.kind == "audio_frames":
        return dense(feats, params["frontend"]["proj"], None, cdt,
                     site="frontend.proj")
    h = dense(feats, params["frontend"]["proj1"], None, cdt,
              site="frontend.proj1")
    return dense(jax.nn.gelu(h), params["frontend"]["proj2"], None, cdt,
                 site="frontend.proj2")


def _scan_decoder(params, x, positions, cfg: ArchConfig, enc_kv=None):
    """Scan homogeneous decoder layers (dense/moe/vlm/encdec-decoder).

    MoE models with leading dense layers (deepseek-v3) carry them as a
    second homogeneous stack under params["dense_layers"]."""

    def body(carry, layer_p):
        h, aux = carry
        h = hint(h, "B", None, None)
        h, _, a = decoder_layer_apply(layer_p, h, positions, cfg, enc_kv=enc_kv)
        aux = {k: aux[k] + a[k] for k in AUX_KEYS}
        return (h, aux), None

    body = _maybe_remat(body, cfg)
    carry = (x, _zero_aux())
    if "dense_layers" in params:
        carry, _ = jax.lax.scan(body, carry, params["dense_layers"])
    (x, aux), _ = jax.lax.scan(body, carry, params["layers"])
    return x, aux


def _scan_rwkv(params, x, cfg: ArchConfig, states):
    def body(carry, xs):
        h = carry
        layer_p, st = xs
        hn = rmsnorm(h, layer_p["ln1"], cfg.norm_eps)
        y, new_st = ssm_mod.rwkv_block_apply(layer_p["blk"], hn, cfg, st)
        return h + y.astype(h.dtype), new_st

    body = _maybe_remat(body, cfg)
    x, new_states = jax.lax.scan(body, x, (params["layers"], states))
    return x, new_states


def _scan_mamba_span(layer_params, x, cfg: ArchConfig, states):
    def body(carry, xs):
        h = carry
        layer_p, st = xs
        hn = rmsnorm(h, layer_p["ln1"], cfg.norm_eps)
        y, new_st = ssm_mod.mamba_block_apply(layer_p["blk"], hn, cfg, st)
        return h + y.astype(h.dtype), new_st

    body = _maybe_remat(body, cfg)
    x, new_states = jax.lax.scan(body, x, (layer_params, states))
    return x, new_states


def _hybrid_forward(params, x, positions, cfg: ArchConfig, states,
                    attn_caches=None, update_cache: bool = False):
    """zamba2-style: groups of (per_group mamba) + shared attn; remainder."""
    n_m, n_groups, per_group, rem = hybrid_layout(cfg)
    lp = params["layers"]

    def take(tree, lo, hi):
        return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)

    def reshape_groups(tree, lo, hi):
        return jax.tree_util.tree_map(
            lambda a: a[lo:hi].reshape((n_groups, per_group) + a.shape[1:]),
            tree)

    grouped_p = reshape_groups(lp, 0, n_groups * per_group)
    grouped_s = reshape_groups(states, 0, n_groups * per_group)
    shared_p = params["shared_attn"]

    # outer scan over groups; shared attention params enter via closure.
    def body(carry, xs):
        h, aux = carry
        if attn_caches is not None:
            g_params, g_states, a_cache = xs
        else:
            g_params, g_states = xs
            a_cache = None
        h, new_g_states = _scan_mamba_span(g_params, h, cfg, g_states)
        h, new_a_cache, a = decoder_layer_apply(
            shared_p, h, positions, cfg, cache=a_cache,
            update_cache=update_cache)
        aux = {k: aux[k] + a[k] for k in AUX_KEYS}
        outs = (new_g_states, new_a_cache) if attn_caches is not None \
            else (new_g_states, None)
        return (h, aux), outs

    body = _maybe_remat(body, cfg)
    xs = (grouped_p, grouped_s, attn_caches) if attn_caches is not None \
        else (grouped_p, grouped_s)
    (x, aux), (new_grouped_s, new_attn_caches) = jax.lax.scan(
        body, (x, _zero_aux()), xs)

    new_states_flat = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups * per_group,) + a.shape[2:]),
        new_grouped_s)
    if rem:
        rem_p = take(lp, n_m - rem, n_m)
        rem_s = take(states, n_m - rem, n_m)
        x, new_rem_s = _scan_mamba_span(rem_p, x, cfg, rem_s)
        new_states_flat = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            new_states_flat, new_rem_s)
    return x, aux, new_states_flat, new_attn_caches


def forward(params: Params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """Training/eval forward (no cache).

    Returns (hidden_states (B,S,d) AFTER final norm, aux, loss_mask (B,S)).
    Logits are NOT materialized here — the loss computes them chunked
    (vocab-parallel + seq-chunked CE); use `logits()` for small-scale eval.
    """
    fam = cfg.family
    tokens = batch["tokens"]
    B = tokens.shape[0]

    if fam == "encdec":
        enc_in = _frontend_embed(params, batch["src_features"], cfg)
        enc_pos = jnp.arange(enc_in.shape[1])[None, :]

        def enc_body(h, layer_p):
            hn = rmsnorm(h, layer_p["ln1"], cfg.norm_eps)
            a, _ = gqa_self_attention(layer_p["attn"], hn, enc_pos, cfg,
                                      causal=False)   # bidirectional encoder
            h = h + a.astype(h.dtype)
            h2 = rmsnorm(h, layer_p["ln2"], cfg.norm_eps)
            return h + mlp_apply(layer_p["mlp"], h2, cfg).astype(h.dtype), None

        enc_body = _maybe_remat(enc_body, cfg)
        enc_out, _ = jax.lax.scan(enc_body, enc_in, params["enc_layers"])
        enc_out = rmsnorm(enc_out, params["ln_enc"], cfg.norm_eps)

        x = _embed(params, tokens[:, :-1], cfg)
        positions = jnp.arange(x.shape[1])[None, :]

        def dec_body(carry, layer_p):
            h, aux = carry
            enc_kv = cross_attention_kv(layer_p["cross"], enc_out, cfg)
            h, _, a = decoder_layer_apply(layer_p, h, positions, cfg,
                                          enc_kv=enc_kv)
            aux = {k: aux[k] + a[k] for k in AUX_KEYS}
            return (h, aux), None

        dec_body = _maybe_remat(dec_body, cfg)
        (x, aux), _ = jax.lax.scan(dec_body, (x, _zero_aux()), params["layers"])
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        mask = jnp.ones(tokens[:, 1:].shape, jnp.float32)
        return x, aux, mask

    if fam == "vlm":
        img = _frontend_embed(params, batch["patch_embeds"], cfg)
        txt = _embed(params, tokens[:, :-1], cfg)
        x = jnp.concatenate([img, txt], axis=1)
        n_img = img.shape[1]
        positions = jnp.arange(x.shape[1])[None, :]
        x, aux = _scan_decoder(params, x, positions, cfg)
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        x = x[:, n_img:]     # predictions only over text positions
        mask = jnp.ones(tokens[:, 1:].shape, jnp.float32)
        return x, aux, mask

    x = _embed(params, tokens[:, :-1], cfg)
    positions = jnp.arange(x.shape[1])[None, :]
    if fam in ("dense", "moe"):
        x, aux = _scan_decoder(params, x, positions, cfg)
    elif fam == "ssm":
        states = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape),
            ssm_mod.init_rwkv_state(cfg, B, x.dtype))
        x, _ = _scan_rwkv(params, x, cfg, states)
        aux = _zero_aux()
    elif fam == "hybrid":
        n_m, _, _, _ = hybrid_layout(cfg)
        states = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_m,) + a.shape),
            ssm_mod.init_mamba_state(cfg, B, x.dtype))
        x, aux, _, _ = _hybrid_forward(params, x, positions, cfg, states)
    else:
        raise ValueError(fam)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    mask = jnp.ones(tokens[:, 1:].shape, jnp.float32)
    return x, aux, mask


# ---------------------------------------------------------------------------
# loss: vocab-parallel, sequence-chunked cross-entropy (never materializes
# the full (B,S,V) logits tensor; each chunk is rematerialized in backward)
# ---------------------------------------------------------------------------

def _unembed_weight(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"].T           # (d, V)
    return params["unembed"]


def chunked_ce_loss(params, hidden, labels, mask, cfg: ArchConfig):
    """hidden: (B,S,d); labels: (B,S) int32; mask: (B,S)."""
    w = _unembed_weight(params, cfg)
    B, S, d = hidden.shape
    c = min(cfg.loss_chunk, S)
    n = -(-S // c)
    pad = n * c - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hidden = hidden.reshape(B, n, c, d).swapaxes(0, 1)     # (n,B,c,d)
    labels = labels.reshape(B, n, c).swapaxes(0, 1)
    mask = mask.reshape(B, n, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(h, y, m):
        logits = dense(h, w, None, jnp.float32, site="loss.unembed")
        logits = hint(logits, "B", None, "M")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * m), jnp.sum(m)

    def body(carry, xs):
        tot, cnt = carry
        h, y, m = xs
        l, k = chunk_loss(h, y, m)
        return (tot + l, cnt + k), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)),
                                 (hidden, labels, mask))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, batch, cfg: ArchConfig,
            moe_loss_weight: float = 0.01, z_loss_weight: float = 1e-4):
    hidden, aux, mask = forward(params, batch, cfg)
    labels = batch["tokens"][:, 1:]
    loss = chunked_ce_loss(params, hidden, labels, mask, cfg)
    total = loss
    if cfg.moe is not None:
        total = total + moe_loss_weight * aux["moe_lb_loss"] + \
            z_loss_weight * aux["moe_z_loss"]
    metrics = {"ce_loss": loss, **aux}
    return total, metrics


def logits(params, batch, cfg: ArchConfig):
    """Full logits for small-scale eval/tests only."""
    hidden, _, _ = forward(params, batch, cfg)
    w = _unembed_weight(params, cfg)
    return dense(hidden, w, None, jnp.float32, site="unembed")
