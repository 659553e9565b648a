"""Prefill / decode with per-family caches.

Cache layout (a plain pytree, so it shards/checkpoints like params):

  dense/moe/vlm : {"pos", "layers": KVCache stacked (L, B, S_max, KVH, hd)}
  ssm (rwkv6)   : {"pos", "layers": RWKVState stacked (L, ...)}   — O(1) in S
  hybrid        : {"pos", "layers": MambaState stacked (n_mamba, ...),
                   "attn": KVCache stacked (n_groups, B, S_max, KVH, hd)}
  encdec        : {"pos", "layers": self-attn KVCache stacked,
                   "cross_k"/"cross_v": (L, B, S_src, KVH, hd)}

MLA caches store (c_kv, k_rope) — the compressed latent — via the absorbed
decode path in attention.py.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (KVCache, cross_attention_kv,
                                    init_gqa_cache, init_mla_cache)
from repro.models.transformer import (_embed, _frontend_embed, _maybe_remat,
                                      _scan_mamba_span, _unembed_weight,
                                      decoder_layer_apply, hybrid_layout,
                                      paged_decoder_layer_apply,
                                      paged_prefill_layer_apply, Params)
from repro.models.modules import dense, rmsnorm

Cache = Dict[str, Any]

# Families whose decode KV can live in the physically paged arena: a single
# homogeneous self-attention stack per step.  encdec pages its self-attn KV
# only (the fixed-length cross K/V stays dense per slot); ssm/hybrid keep
# the dense slot layout — their recurrent state is O(1) in sequence length,
# so there is nothing to page.
PAGED_FAMILIES = ("dense", "moe", "vlm", "encdec")

# Families whose *prefill* can stream through the arena in chunks
# (paged_prefill_step): pure text-token causal self-attention stacks.  vlm
# prepends frontend rows that are not tokens and encdec needs the encoder
# pass + cross K/V capture, so both keep the single-shot bucketed prefill
# whose scratch dense cache is scattered into pages (paged_prefill_write).
CHUNKED_PREFILL_FAMILIES = ("dense", "moe")


def _stack_cache(proto, n: int):
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy()
        if hasattr(a, "shape") else a, proto)


def _layer_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype):
    if cfg.attention_type == "mla":
        return init_mla_cache(cfg, batch, max_len, dtype)
    return init_gqa_cache(cfg, batch, max_len, dtype)


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               src_len: int = 0) -> Cache:
    dt = jnp.dtype(cfg.compute_dtype)
    fam = cfg.family
    cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
    if fam in ("dense", "moe", "vlm", "encdec"):
        proto = _layer_kv_cache(cfg, batch, max_len, dt)
        cache["layers"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(),
            proto)
        if fam == "encdec":
            kv_shape = (cfg.num_layers, batch, src_len, cfg.num_kv_heads,
                        cfg.head_dim)
            cache["cross_k"] = jnp.zeros(kv_shape, dt)
            cache["cross_v"] = jnp.zeros(kv_shape, dt)
    elif fam == "ssm":
        proto = ssm_mod.init_rwkv_state(cfg, batch, dt)
        cache["layers"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(),
            proto)
    elif fam == "hybrid":
        n_m, n_groups, _, _ = hybrid_layout(cfg)
        proto = ssm_mod.init_mamba_state(cfg, batch, dt)
        cache["layers"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_m,) + a.shape).copy(), proto)
        a_proto = _layer_kv_cache(cfg, batch, max_len, dt)
        cache["attn"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape).copy(),
            a_proto)
    else:
        raise ValueError(fam)
    return cache


# ---------------------------------------------------------------------------
# paged KV arena (physical pages; consumed by paged_decode_step)
# ---------------------------------------------------------------------------

def init_paged_arena(cfg: ArchConfig, num_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    """Per-layer physical KV pages for the attention stack.

    Leaves are ``(num_layers, num_blocks, block_size, *feat)``: ``k``/``v``
    rows for GQA, the compressed ``(c_kv, k_rope)`` latent rows for MLA
    (mirroring the dense KVCache's k/v slots).  The caller decides how many
    blocks to allocate; the serving engine passes pool blocks + 1 and uses
    the trailing block as write-discard scratch for masked lanes.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(f"family {cfg.family!r} has no paged KV arena")
    dt = jnp.dtype(cfg.compute_dtype)
    L = cfg.num_layers
    if cfg.attention_type == "mla":
        m = cfg.mla
        return {"k": jnp.zeros((L, num_blocks, block_size, m.kv_lora_rank),
                               dt),
                "v": jnp.zeros((L, num_blocks, block_size,
                                m.qk_rope_head_dim), dt)}
    shape = (L, num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_paged_state(cfg: ArchConfig, num_slots: int,
                     src_len: int = 0) -> Dict[str, Any]:
    """Slot-stacked per-lane state that stays dense under the paged layout
    (currently only the encdec cross-attention K/V; positions are implied
    by the per-lane kv_lens the engine tracks)."""
    st: Dict[str, Any] = {}
    if cfg.family == "encdec":
        dt = jnp.dtype(cfg.compute_dtype)
        shape = (cfg.num_layers, num_slots, src_len, cfg.num_kv_heads,
                 cfg.head_dim)
        st["cross_k"] = jnp.zeros(shape, dt)
        st["cross_v"] = jnp.zeros(shape, dt)
    return st


def paged_prefill_write(arena: Dict[str, Any], layers_cache: KVCache,
                        block_ids: jnp.ndarray) -> Dict[str, Any]:
    """Commit a freshly prefilled batch=1 dense cache into arena pages.

    ``block_ids``: (nblk,) int32 physical pages in logical order.  The copy
    happens at bucket granularity — the first ``nblk * block_size`` rows of
    the dense cache are reshaped into pages and scattered, so the padded-
    bucket prefill itself is untouched; rows past the true length are
    bucket padding that decode masks (and overwrites as tokens arrive).
    """
    nblk = block_ids.shape[0]

    def put(leaf, dense_leaf):
        bs = leaf.shape[2]
        rows = dense_leaf[:, 0, :nblk * bs]
        rows = rows.reshape((dense_leaf.shape[0], nblk, bs) +
                            dense_leaf.shape[3:])
        return leaf.at[:, block_ids].set(rows.astype(leaf.dtype))

    return {"k": put(arena["k"], layers_cache.k),
            "v": put(arena["v"], layers_cache.v)}


def _scan_paged_layers(layer_fn, x, params: Params, arena: Dict[str, Any],
                       extra: Tuple = ()):
    """Scan a decoder stack's layers with the stacked arena in the carry.

    ``layer_fn(h, layer_p, k, v, layer, *extra_l) -> (h, k, v)`` takes the
    whole (L, NB, bs, ...) leaves and its layer index, scatters the step's
    new rows into them at ``layer`` and returns them, so the arena is
    updated in place through the loop (and, donated by the caller's jit,
    aliases the program's output) instead of being sliced per layer and
    stacked back.  A leading dense stack (deepseek-v3) runs first on the
    same carry, so the layer index runs on across both stacks.  The main
    stack's routed expert banks (``moe.w_gate``, ``w_up``, ``w_down``)
    stay out of the scan's xs: each layer's ``moe`` gets the whole
    (L, E, ...) stacks, in the activations' dtype, and ``bank_layer``, its
    index among them, and the grouped GEMM reads its bank in place
    (``models/moe.py`` ``moe_serve``).  ``extra``: per-layer xs of the
    main stack (encdec's cross K/V).  Returns (x, {"k": k, "v": v});
    where ``layer_fn`` returns a fourth value (each MoE layer's expert
    load), the main stack's, stacked, come third."""

    def body(carry, xs):
        h, k, v, layer = carry
        out = layer_fn(h, xs[0], k, v, layer, *xs[1:])
        return (*out[:3], layer + 1), out[3:]

    carry = (x, arena["k"], arena["v"], jnp.zeros((), jnp.int32))
    n_dense = 0
    if "dense_layers" in params:
        carry, _ = jax.lax.scan(body, carry, (params["dense_layers"],))
        n_dense = jax.tree_util.tree_leaves(
            params["dense_layers"])[0].shape[0]
    layers, banks = _split_banks(params["layers"], x.dtype)

    def main_body(carry, xs):
        if banks:
            moe = dict(xs[0]["moe"], **banks, bank_layer=carry[3] - n_dense)
            xs = (dict(xs[0], moe=moe),) + xs[1:]
        return body(carry, xs)

    (x, k, v, _), ys = jax.lax.scan(main_body, carry,
                                    (layers,) + tuple(extra))
    return (x, {"k": k, "v": v}) + tuple(ys)


_EXPERT_BANKS = ("w_gate", "w_up", "w_down")


def _split_banks(layers: Params, dtype) -> Tuple[Params, Dict[str, Any]]:
    """(the stacked layer tree less its routed expert banks, the banks'
    (L, E, ...) stacks cast to ``dtype``); a tree without ``moe`` banks
    comes back whole, with no banks."""
    moe = layers.get("moe", {})
    if not all(name in moe for name in _EXPERT_BANKS):
        return layers, {}
    rest = {n: w for n, w in moe.items() if n not in _EXPERT_BANKS}
    return (dict(layers, moe=rest),
            {n: moe[n].astype(dtype) for n in _EXPERT_BANKS})


def paged_decode_step(params: Params, tokens: jnp.ndarray, cfg: ArchConfig,
                      state: Dict[str, Any], arena: Dict[str, Any],
                      block_tables: jnp.ndarray, kv_lens: jnp.ndarray,
                      write_mask: jnp.ndarray, moe_loads: bool = False
                      ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One batched decode step over every lane through the paged KV arena.

    tokens: (S, 1) int32 — one pending token per lane; block_tables:
    (S, W) int32; kv_lens: (S,) rows already committed per lane (this IS
    each lane's position — vlm frontend rows included); write_mask: (S,)
    int32 — lanes with 0 (stalled / empty slots) leave the arena untouched
    and their logits are discarded by the caller, so there is nothing to
    snapshot or roll back; they route to no expert.  Returns ((S, V)
    logits, new arena); with ``moe_loads`` the first element is (logits,
    the MoE layers' (L, 2, E) int32 expert loads: routed rows and
    grouped-GEMM rows per expert, ``models/moe.py`` ``moe_serve``).
    """
    fam = cfg.family
    if fam not in PAGED_FAMILIES:
        raise ValueError(f"family {fam!r} cannot decode through the paged "
                         "arena (recurrent state keeps the dense layout)")
    x = _embed(params, tokens, cfg)
    positions = kv_lens[:, None]
    wm = write_mask.astype(jnp.int32)

    def layer_fn(h, layer_p, ak, av, layer, *enc_kv):
        out = paged_decoder_layer_apply(
            layer_p, h, positions, cfg, k_arena=ak, v_arena=av, layer=layer,
            block_tables=block_tables, kv_lens=kv_lens, write_mask=wm,
            enc_kv=enc_kv or None)
        return out if moe_loads else out[:3]

    extra = ((state["cross_k"], state["cross_v"]) if fam == "encdec"
             else ())
    x, new_arena, *loads = _scan_paged_layers(_maybe_remat(layer_fn, cfg),
                                              x, params, arena, extra)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(params, x[:, -1, :], cfg)
    return ((logits, *loads) if moe_loads else logits), new_arena


def paged_prefill_step(params: Params, tokens: jnp.ndarray, cfg: ArchConfig,
                       arena: Dict[str, Any], block_tables: jnp.ndarray,
                       kv_lens: jnp.ndarray, chunk_lens: jnp.ndarray,
                       moe_loads: bool = False, every_row: bool = False
                       ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One chunked-prefill step over every lane through the paged KV arena.

    tokens: (S, C) int32 — one prompt chunk per lane, right-padded;
    block_tables: (S, W) int32; kv_lens: (S,) rows already committed per
    lane (the chunk's absolute start position); chunk_lens: (S,) valid
    tokens in each lane's chunk — 0 skips the lane entirely (its padded
    rows write to the trash block and its logits row is garbage the caller
    ignores).  Each layer writes the chunk's K/V rows directly into the
    lane's pages, then attends causally over everything written so far —
    no dense scratch cache, no bucket-granularity copy, so prefill KV
    traffic is exactly the chunk's real tokens, and only those rows route
    to experts.  Returns ((S, V) logits at each lane's last valid chunk
    row, new arena); ``moe_loads`` as in :func:`paged_decode_step`.

    ``every_row`` makes this the speculative-decode verifier: tokens
    carry each lane's pending token followed by its draft proposals, and
    the LM head runs over all C rows, because row i's logits accept or
    correct draft token i+1.  The logits are then (S, C, V); rows past
    ``chunk_lens`` are garbage the caller ignores.
    """
    fam = cfg.family
    if fam not in CHUNKED_PREFILL_FAMILIES:
        raise ValueError(f"family {fam!r} cannot prefill through the paged "
                         f"arena in chunks (supported: "
                         f"{CHUNKED_PREFILL_FAMILIES})")
    S, C = tokens.shape
    x = _embed(params, tokens, cfg)
    positions = kv_lens[:, None] + jnp.arange(C)[None, :]

    def layer_fn(h, layer_p, ak, av, layer):
        out = paged_prefill_layer_apply(
            layer_p, h, positions, cfg, k_arena=ak, v_arena=av, layer=layer,
            block_tables=block_tables, kv_lens=kv_lens,
            chunk_lens=chunk_lens)
        return out if moe_loads else out[:3]

    x, new_arena, *loads = _scan_paged_layers(_maybe_remat(layer_fn, cfg),
                                              x, params, arena)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    if not every_row:
        last = jnp.clip(chunk_lens - 1, 0, C - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0, :]
    logits = _lm_head(params, x, cfg)
    return ((logits, *loads) if moe_loads else logits), new_arena


# ---------------------------------------------------------------------------
# decoder-stack step (shared by prefill and decode; S is the step width)
# ---------------------------------------------------------------------------

def _run_decoder_stack(params: Params, x, positions, cfg: ArchConfig, cache,
                       cross=False):
    """Scan decoder layers threading per-layer KV caches."""

    def body(h, xs):
        layer_p, layer_c = xs
        if cross:
            enc_kv = (layer_c["ck"], layer_c["cv"])
            h, new_c, _ = decoder_layer_apply(
                layer_p, h, positions, cfg, cache=layer_c["kv"], enc_kv=enc_kv)
            return h, {"kv": new_c}
        h, new_c, _ = decoder_layer_apply(layer_p, h, positions, cfg,
                                          cache=layer_c)
        return h, new_c

    body = _maybe_remat(body, cfg)
    if cross:
        xs = (params["layers"], {"kv": cache["layers"],
                                 "ck": cache["cross_k"],
                                 "cv": cache["cross_v"]})
        x, new = jax.lax.scan(body, x, xs)
        return x, new["kv"]
    layer_caches = cache["layers"]
    if "dense_layers" in params:
        # leading dense stack (deepseek-v3): split the homogeneous cache
        nd = jax.tree_util.tree_leaves(params["dense_layers"])[0].shape[0]
        head = jax.tree_util.tree_map(lambda a: a[:nd], layer_caches)
        tail = jax.tree_util.tree_map(lambda a: a[nd:], layer_caches)
        x, new_head = jax.lax.scan(body, x, (params["dense_layers"], head))
        x, new_tail = jax.lax.scan(body, x, (params["layers"], tail))
        new_layers = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), new_head, new_tail)
        return x, new_layers
    x, new_layers = jax.lax.scan(body, x, (params["layers"], layer_caches))
    return x, new_layers


def _run_ssm_stack(params: Params, x, cfg: ArchConfig, states):
    def body(h, xs):
        layer_p, st = xs
        hn = rmsnorm(h, layer_p["ln1"], cfg.norm_eps)
        y, new_st = ssm_mod.rwkv_block_apply(layer_p["blk"], hn, cfg, st)
        return h + y.astype(h.dtype), new_st

    body = _maybe_remat(body, cfg)
    return jax.lax.scan(body, x, (params["layers"], states))


def _run_hybrid_stack(params: Params, x, positions, cfg: ArchConfig, cache):
    n_m, n_groups, per_group, rem = hybrid_layout(cfg)
    lp, states = params["layers"], cache["layers"]

    def reshape_groups(tree):
        return jax.tree_util.tree_map(
            lambda a: a[:n_groups * per_group].reshape(
                (n_groups, per_group) + a.shape[1:]), tree)

    grouped_p = reshape_groups(lp)
    grouped_s = reshape_groups(states)
    shared_p = params["shared_attn"]

    def body(h, xs):
        g_params, g_states, a_cache = xs
        h, new_g = _scan_mamba_span(g_params, h, cfg, g_states)
        h, new_a, _ = decoder_layer_apply(shared_p, h, positions, cfg,
                                          cache=a_cache)
        return h, (new_g, new_a)

    body = _maybe_remat(body, cfg)
    x, (new_grouped, new_attn) = jax.lax.scan(
        body, x, (grouped_p, grouped_s, cache["attn"]))
    new_states = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups * per_group,) + a.shape[2:]), new_grouped)
    if rem:
        rem_p = jax.tree_util.tree_map(lambda a: a[n_m - rem:], lp)
        rem_s = jax.tree_util.tree_map(lambda a: a[n_m - rem:], states)
        x, new_rem = _scan_mamba_span(rem_p, x, cfg, rem_s)
        new_states = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), new_states, new_rem)
    return x, new_states, new_attn


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def _lm_head(params, h_last, cfg: ArchConfig):
    w = _unembed_weight(params, cfg)
    return dense(h_last, w, None, jnp.float32, site="unembed")


def prefill(params: Params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig,
            cache: Cache, length=None) -> Tuple[jnp.ndarray, Cache]:
    """Run the prompt through the model, filling `cache`.

    Returns (logits for the last position (B, V), updated cache).

    ``length`` (scalar int, may be traced) marks the number of valid prompt
    tokens when ``batch["tokens"]`` is right-padded to a bucket shape: logits
    come from position ``length - 1``, the cache position advances by
    ``length``, and every KV-cache length is corrected so later decode steps
    never attend to the padded keys (causal masking already hides them from
    the real prompt positions during prefill).  One compilation per bucket
    shape serves every prompt length in the bucket.  Attention families only:
    ssm/hybrid recurrent state integrates every input token, so padded
    prefill would corrupt it — callers must pass exact-length prompts there.
    """
    fam = cfg.family
    tokens = batch["tokens"]
    pos0 = cache["pos"]
    if length is None:
        length = tokens.shape[1]
    pad = tokens.shape[1] - length

    if fam == "encdec":
        # encoder pass + cross-kv capture
        enc_in = _frontend_embed(params, batch["src_features"], cfg)
        enc_pos = jnp.arange(enc_in.shape[1])[None, :]
        from repro.models.attention import gqa_self_attention
        from repro.models.mlp import mlp_apply

        def enc_body(h, layer_p):
            hn = rmsnorm(h, layer_p["ln1"], cfg.norm_eps)
            a, _ = gqa_self_attention(layer_p["attn"], hn, enc_pos, cfg,
                                      causal=False)
            h = h + a.astype(h.dtype)
            h2 = rmsnorm(h, layer_p["ln2"], cfg.norm_eps)
            return h + mlp_apply(layer_p["mlp"], h2, cfg).astype(h.dtype), None

        enc_out, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), enc_in,
                                  params["enc_layers"])
        enc_out = rmsnorm(enc_out, params["ln_enc"], cfg.norm_eps)

        def kv_body(_, layer_p):
            k, v = cross_attention_kv(layer_p["cross"], enc_out, cfg)
            return None, (k, v)

        _, (ck, cv) = jax.lax.scan(kv_body, None, params["layers"])
        cache = dict(cache)
        cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
        cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
        x = _embed(params, tokens, cfg)
        positions = pos0 + jnp.arange(tokens.shape[1])[None, :]
        x, new_layers = _run_decoder_stack(params, x, positions, cfg, cache,
                                           cross=True)
    elif fam == "vlm":
        img = _frontend_embed(params, batch["patch_embeds"], cfg)
        txt = _embed(params, tokens, cfg)
        x = jnp.concatenate([img, txt], axis=1)
        positions = pos0 + jnp.arange(x.shape[1])[None, :]
        x, new_layers = _run_decoder_stack(params, x, positions, cfg, cache)
    elif fam in ("dense", "moe"):
        x = _embed(params, tokens, cfg)
        positions = pos0 + jnp.arange(tokens.shape[1])[None, :]
        x, new_layers = _run_decoder_stack(params, x, positions, cfg, cache)
    elif fam == "ssm":
        x = _embed(params, tokens, cfg)
        x, new_layers = _run_ssm_stack(params, x, cfg, cache["layers"])
    elif fam == "hybrid":
        x = _embed(params, tokens, cfg)
        positions = pos0 + jnp.arange(tokens.shape[1])[None, :]
        x, new_layers, new_attn = _run_hybrid_stack(params, x, positions,
                                                    cfg, cache)
    else:
        raise ValueError(fam)

    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    # the stack counted the padded width into every KVCache length
    if fam in ("dense", "moe", "vlm", "encdec"):
        new_cache["layers"] = new_layers._replace(
            length=new_layers.length - pad)
    elif fam == "hybrid":
        new_cache["attn"] = new_attn._replace(length=new_attn.length - pad)
    step = length if fam != "vlm" else length + \
        batch["patch_embeds"].shape[1]
    new_cache["pos"] = pos0 + step
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    last = step - 1
    h_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)[:, 0, :]
    return _lm_head(params, h_last, cfg), new_cache


def decode_step(params: Params, tokens: jnp.ndarray, cfg: ArchConfig,
                cache: Cache) -> Tuple[jnp.ndarray, Cache]:
    """One decode step.  tokens: (B, 1) int32.  Returns ((B, V) logits, cache)."""
    fam = cfg.family
    pos0 = cache["pos"]
    x = _embed(params, tokens, cfg)
    positions = pos0 + jnp.arange(tokens.shape[1])[None, :]

    if fam in ("dense", "moe", "vlm"):
        x, new_layers = _run_decoder_stack(params, x, positions, cfg, cache)
        new_attn = None
    elif fam == "encdec":
        x, new_layers = _run_decoder_stack(params, x, positions, cfg, cache,
                                           cross=True)
        new_attn = None
    elif fam == "ssm":
        x, new_layers = _run_ssm_stack(params, x, cfg, cache["layers"])
        new_attn = None
    elif fam == "hybrid":
        x, new_layers, new_attn = _run_hybrid_stack(params, x, positions,
                                                    cfg, cache)
    else:
        raise ValueError(fam)

    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    if new_attn is not None:
        new_cache["attn"] = new_attn
    new_cache["pos"] = pos0 + tokens.shape[1]
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _lm_head(params, x[:, -1, :], cfg), new_cache
