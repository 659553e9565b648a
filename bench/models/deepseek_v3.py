"""The DeepSeek-V3 family (Hugging Face ``DeepseekV3ForCausalLM``,
``model_type`` "deepseek_v3"): multi-head latent attention (MLA) in every
layer, a leading stack of dense SiLU-gated MLP layers, then MoE layers of
routed experts chosen by a sigmoid router with a selection bias, plus
always-on shared experts.  The interface is ``bench/models/llama.py``'s.

What is mapped: queries projected directly (``q_lora_rank`` null, as in
Moonlight and DeepSeek-V2-Lite), one expert group (``n_group`` =
``topk_group`` = 1, so group-limited top-k is plain top-k), no rope
scaling, every expert of a layer on this chip (``ep_size`` 1).  A config
outside that is refused by ``arch_config``.

Every weight is uniform with a fan-in scale from ``weights.draw``, keyed by
(seed, leaf, layer) with the layer counted across both stacks.  The router
is drawn at fan-in scale, so its logits spread about one unit, as a
trained router's do; ``e_score_correction_bias`` is U(-BIAS_SPREAD,
BIAS_SPREAD), wide enough to change some of the selections that the
scores alone would make.  ``make_params`` makes the served tree in one
jitted call; ``make_layer`` makes one layer's float32 leaves for the
reference from the same keys.

The reference is the published forward pass in float32 ``jax.numpy`` at
``highest`` matmul precision, one sequence at a time and layer by layer,
with no kernels, cache or batching:

- attention is the expanded form: per-head keys ``c_kv @ W_UK`` with the
  shared rope key appended, values ``c_kv @ W_UV``, causal softmax scaled
  by 1/sqrt(qk_nope + qk_rope) (the program runs the absorbed form over
  the latent rows);
- rope on the decoupled dims follows DeepSeek's layout as its
  ``modeling_deepseek.py`` ``apply_rotary_pos_emb`` applies it: the dims
  are de-interleaved (``view(..., d // 2, 2).transpose(4, 3)``, even
  then odd) and then rotated in halves;
- the router scores are ``sigmoid(x @ W_r)`` in float32; the k experts
  with the largest score + bias are chosen; their weights are the
  unbiased scores, over their sum (+1e-20), times
  ``routed_scaling_factor``;
- the MoE is plain and drops nothing: each expert is applied to every row
  and weighted by that row's combine weight for it, which is zero where
  the row did not choose it, and the shared experts (one GLU of width
  ``n_shared_experts * moe_intermediate_size``) are added to every row.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from costs import attended_keys
from reference import BUCKET, causal_attention, mm, rms_norm, rope
from weights import NORM_SPREAD, draw, seed_key

BIAS_SPREAD = 0.05        # e_score_correction_bias is U(-0.05, 0.05)

# leaf name -> index of its key stream; layer leaves fold in the layer too
ATTN_LEAVES = ("w_q", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo", "ln1", "ln2")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("router", "bias", "e_gate", "e_up", "e_down", "s_gate", "s_up",
              "s_down")
HEAD_LEAVES = ("embed", "unembed", "ln_f")
STREAMS = ATTN_LEAVES + DENSE_LEAVES + MOE_LEAVES + HEAD_LEAVES

# the paged kernels' custom calls, named after their jitted wrappers
# (``kernels/ops.py``)
PAGED_ATTN_KERNELS = ("%mla_paged_attention", "%mla_paged_prefill_attention")


def arch_config(conf: Dict):
    """The program's ``ArchConfig`` for a DeepSeek-V3-architecture config."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig
    if conf.get("hidden_act") != "silu":
        raise ValueError("only the SiLU-gated MLP is mapped")
    if conf.get("rope_scaling") or conf.get("attention_bias"):
        raise ValueError("rope scaling and attention bias are not mapped")
    if conf.get("q_lora_rank") is not None:
        raise ValueError("the low-rank q projection is not mapped")
    if conf.get("n_group", 1) != 1 or conf.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not mapped")
    if (conf.get("scoring_func"), conf.get("topk_method")) != (
            "sigmoid", "noaux_tc"):
        raise ValueError("only the sigmoid router with its selection bias "
                         "(noaux_tc) is mapped")
    if conf.get("moe_layer_freq", 1) != 1 or conf.get("ep_size", 1) != 1:
        raise ValueError("only every layer after the dense stack MoE, with "
                         "all experts on the chip, is mapped")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    nope, rd = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        conf["torch_dtype"]]
    return ArchConfig(
        name=conf.get("name", "bench"), family="moe",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"], head_dim=nope + rd,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attention_type="mla",
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_head_dim=nope, qk_rope_head_dim=rd,
                      v_head_dim=conf["v_head_dim"]),
        moe=MoEConfig(num_experts=conf["n_routed_experts"],
                      num_shared_experts=conf["n_shared_experts"],
                      experts_per_token=conf["num_experts_per_tok"],
                      d_ff_expert=conf["moe_intermediate_size"],
                      scoring_func="sigmoid", topk_method="noaux_tc",
                      norm_topk_prob=bool(conf["norm_topk_prob"]),
                      routed_scaling_factor=float(
                          conf["routed_scaling_factor"])),
        first_dense_layers=conf["first_k_dense_replace"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        mlp_activation="silu", param_dtype=dtype, compute_dtype=dtype)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """(shape, half-width of the uniform) of each leaf; a norm's half-width
    is its spread around 1."""
    m, e = cfg.mla, cfg.moe
    d, H, L = cfg.d_model, cfg.num_heads, cfg.num_layers
    r, rd, nope, vd = (m.kv_lora_rank, m.qk_rope_head_dim,
                       m.qk_nope_head_dim, m.v_head_dim)
    E, f, fs = e.num_experts, e.d_ff_expert, e.d_ff_expert * \
        e.num_shared_experts

    def fan(n_in, extra=1.0):
        return math.sqrt(3.0 / (n_in * extra))   # uniform with std 1/sqrt(.)

    return {
        "w_q": ((d, H * (nope + rd)), fan(d)),
        "w_dkv": ((d, r + rd), fan(d)),
        "kv_norm": ((r,), NORM_SPREAD),
        "w_uk": ((r, H * nope), fan(r)), "w_uv": ((r, H * vd), fan(r)),
        "wo": ((H * vd, d), fan(H * vd, 2 * L)),
        "ln1": ((d,), NORM_SPREAD), "ln2": ((d,), NORM_SPREAD),
        "w_gate": ((d, cfg.d_ff), fan(d)), "w_up": ((d, cfg.d_ff), fan(d)),
        "w_down": ((cfg.d_ff, d), fan(cfg.d_ff, 2 * L)),
        "router": ((d, E), fan(d)), "bias": ((E,), BIAS_SPREAD),
        "e_gate": ((E, d, f), fan(d)), "e_up": ((E, d, f), fan(d)),
        "e_down": ((E, f, d), fan(f, 2 * L)),
        "s_gate": ((d, fs), fan(d)), "s_up": ((d, fs), fan(d)),
        "s_down": ((fs, d), fan(fs, 2 * L)),
        "embed": ((cfg.vocab_size, d), math.sqrt(3.0)),
        "unembed": ((d, cfg.vocab_size), fan(d)),
        "ln_f": ((d,), NORM_SPREAD),
    }


def _draw(key, name: str, layer, shape, half):
    return draw(key, STREAMS.index(name), layer, shape, half)


def _is_norm(name: str) -> bool:
    return name.startswith("ln") or name == "kv_norm"


def _layer_leaves(cfg, layer: int) -> Tuple[str, ...]:
    ffn = DENSE_LEAVES if layer < cfg.first_dense_layers else MOE_LEAVES
    return ATTN_LEAVES + ffn


def make_layer(key, cfg, layer) -> Dict[str, jnp.ndarray]:
    """Float32 leaves of one layer, flat, each first rounded to
    ``cfg.param_dtype`` as the served weights are (the bias stays float32,
    as the checkpoint keeps it); norm weights as the HF checkpoint stores
    them (1 + scale)."""
    shapes, dt = _leaf_shapes(cfg), jnp.dtype(cfg.param_dtype)
    out = {}
    for name in _layer_leaves(cfg, layer):
        shape, half = shapes[name]
        w = _draw(key, name, layer, shape, half)
        if name != "bias":
            w = w.astype(dt).astype(jnp.float32)
        out[name] = 1.0 + w if _is_norm(name) else w
    return out


def make_head(key, cfg) -> Dict[str, jnp.ndarray]:
    """Float32 embedding, unembedding and final norm weight (1 + scale)."""
    shapes, dt = _leaf_shapes(cfg), jnp.dtype(cfg.param_dtype)
    out = {}
    for name in HEAD_LEAVES:
        shape, half = shapes[name]
        w = _draw(key, name, 0, shape, half).astype(dt).astype(jnp.float32)
        out[name] = 1.0 + w if name == "ln_f" else w
    return out


def make_params(seed: int, cfg):
    """The program's parameter tree, made on the device in one jitted call
    and in ``cfg.param_dtype`` (the selection bias in float32).  The
    program stores a norm weight as ``scale`` and applies ``1 + scale``;
    its experts are banks (E, d_in, d_out)."""
    dt = jnp.dtype(cfg.param_dtype)
    shapes = _leaf_shapes(cfg)
    nd, L = cfg.first_dense_layers, cfg.num_layers

    def build(key):
        def leaf(name, lo, hi, dtype=dt):
            shape, half = shapes[name]
            # one layer at a time, so no float32 stack of a bank is held
            return jax.lax.map(
                lambda i: _draw(key, name, i, shape, half).astype(dtype),
                jnp.arange(lo, hi))

        def attn(lo, hi):
            return {"ln1": leaf("ln1", lo, hi), "ln2": leaf("ln2", lo, hi),
                    "attn": {n: leaf(n, lo, hi) for n in
                             ("w_q", "w_dkv", "kv_norm", "w_uk", "w_uv",
                              "wo")}}

        def head(name):
            shape, half = shapes[name]
            return _draw(key, name, 0, shape, half).astype(dt)

        moe = {"router": leaf("router", nd, L),
               "e_score_correction_bias": leaf("bias", nd, L, jnp.float32),
               "w_gate": leaf("e_gate", nd, L), "w_up": leaf("e_up", nd, L),
               "w_down": leaf("e_down", nd, L),
               "shared": {"w_gate": leaf("s_gate", nd, L),
                          "w_up": leaf("s_up", nd, L),
                          "w_down": leaf("s_down", nd, L)}}
        tree = {"embed": head("embed"), "unembed": head("unembed"),
                "ln_f": head("ln_f"), "layers": {**attn(nd, L), "moe": moe}}
        if nd:
            tree["dense_layers"] = {
                **attn(0, nd),
                "mlp": {n: leaf(n, 0, nd) for n in DENSE_LEAVES}}
        return tree

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def ds_rope(x, theta: float):
    """DeepSeek's rope on x (T, heads, rd) at positions 0..T-1: de-interleave
    the dims (even, then odd), then rotate in halves as HF Llama does."""
    T, heads, rd = x.shape
    x = x.reshape(T, heads, rd // 2, 2).swapaxes(-1, -2).reshape(T, heads, rd)
    return rope(x, theta)


def _attention(h, w, conf_key, precision):
    H, nope, rd, vd, r, theta, eps = conf_key[:7]
    T = h.shape[0]
    x = rms_norm(h, w["ln1"], eps)
    q = mm(x, w["w_q"], precision).reshape(T, H, nope + rd)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = mm(x, w["w_dkv"], precision)
    c = rms_norm(kv[:, :r], w["kv_norm"], eps)
    k_pe = ds_rope(kv[:, None, r:], theta)
    k_nope = mm(c, w["w_uk"], precision).reshape(T, H, nope)
    v = mm(c, w["w_uv"], precision).reshape(T, H, vd)
    q = jnp.concatenate([q_nope, ds_rope(q_pe, theta)], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, H, rd))],
                        axis=-1)
    o = causal_attention(q, k, v, precision).reshape(T, H * vd)
    return h + mm(o, w["wo"], precision)


def _glu(x, g, u, dn, precision):
    return mm(jax.nn.silu(mm(x, g, precision)) * mm(x, u, precision), dn,
              precision)


def route(x, w, conf_key, precision):
    """(chosen experts (T, k), combine weights (T, k)) of the normed rows
    x (T, d)."""
    k, scale, norm = conf_key[7:10]
    scores = jax.nn.sigmoid(mm(x, w["router"], precision))
    _, idx = jax.lax.top_k(scores + w["bias"], k)
    wts = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20)
    return idx, wts * scale


@functools.partial(jax.jit, static_argnames=("conf_key", "precision"))
def _moe_layer(h, w, conf_key, precision):
    eps = conf_key[6]
    h = _attention(h, w, conf_key, precision)
    x = rms_norm(h, w["ln2"], eps)
    idx, wts = route(x, w, conf_key, precision)
    T, E = x.shape[0], w["router"].shape[1]
    combine = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].set(wts)

    def expert(acc, e):
        y = _glu(x, w["e_gate"][e], w["e_up"][e], w["e_down"][e], precision)
        return acc + combine[:, e, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return h + routed + _glu(x, w["s_gate"], w["s_up"], w["s_down"],
                             precision)


@functools.partial(jax.jit, static_argnames=("conf_key", "precision"))
def _dense_layer(h, w, conf_key, precision):
    h = _attention(h, w, conf_key, precision)
    x = rms_norm(h, w["ln2"], conf_key[6])
    return h + _glu(x, w["w_gate"], w["w_up"], w["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(h, ln_f, unembed, eps, precision):
    return mm(rms_norm(h, ln_f, eps), unembed, precision)


_make_layer = jax.jit(make_layer, static_argnums=(1, 2))
_make_head = jax.jit(make_head, static_argnums=(1,))


def _conf_key(cfg) -> Tuple:
    m, e = cfg.mla, cfg.moe
    return (cfg.num_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim, m.kv_lora_rank, float(cfg.rope_theta),
            float(cfg.norm_eps), e.experts_per_token,
            float(e.routed_scaling_factor), bool(e.norm_topk_prob))


def served_logits(seed: int, cfg, seqs: Sequence[np.ndarray],
                  rows: Sequence[np.ndarray],
                  precision: str = "f32") -> List[np.ndarray]:
    """For each token sequence, float32 logits at the positions ``rows``
    (each (n,) int): ``out[i][j]`` is the distribution over the token that
    follows ``seqs[i][rows[i][j]]``.  ``precision="fp8"`` is the control
    (``reference.to_fp8``, the router's operands too).  Sequences are
    padded at the end to a multiple of ``reference.BUCKET``: causal
    attention and the per-row MoE leave earlier positions unchanged."""
    key = seed_key(seed)
    conf_key = _conf_key(cfg)
    head = _make_head(key, cfg)
    hs = []
    for s in seqs:
        T = -(-len(s) // BUCKET) * BUCKET
        toks = np.zeros(T, np.int32)
        toks[:len(s)] = s
        hs.append(head["embed"][jnp.asarray(toks)])
    unembed, ln_f = head["unembed"], head["ln_f"]
    del head
    with jax.default_matmul_precision("highest"):
        for layer in range(cfg.num_layers):
            w = _make_layer(key, cfg, layer)
            fn = (_dense_layer if layer < cfg.first_dense_layers
                  else _moe_layer)
            hs = [fn(h, w, conf_key, precision) for h in hs]
            del w
        out = []
        for h, r in zip(hs, rows):
            n = -(-len(r) // BUCKET) * BUCKET
            idx = np.zeros(n, np.int32)
            idx[:len(r)] = r
            lg = _logits(h[jnp.asarray(idx)], ln_f, unembed,
                         float(cfg.norm_eps), precision)
            out.append(np.asarray(lg[:len(r)], np.float32))
    return out


# ---------------------------------------------------------------------------
# counts: operations and bytes from shapes alone (``costs``)
# ---------------------------------------------------------------------------

def attn_params(cfg) -> int:
    """Weights of one layer's MLA (norms excluded)."""
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    return (d * H * (m.qk_nope_head_dim + m.qk_rope_head_dim)
            + d * (m.kv_lora_rank + m.qk_rope_head_dim)
            + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            + H * m.v_head_dim * d)


def expert_params(cfg) -> int:
    """Weights of one routed expert (gate, up, down)."""
    return 3 * cfg.d_model * cfg.moe.d_ff_expert


def active_params(cfg, moe: bool) -> int:
    """Weights a token multiplies by in one layer, the router excluded: the
    MLA and the dense MLP, or the MLA, k routed experts and the shared
    experts."""
    if not moe:
        return attn_params(cfg) + 3 * cfg.d_model * cfg.d_ff
    e = cfg.moe
    return attn_params(cfg) + (e.experts_per_token + e.num_shared_experts) \
        * expert_params(cfg)


def total_params(cfg) -> int:
    """Every weight of the configuration, norms and the bias excluded."""
    e, d = cfg.moe, cfg.d_model
    moe_layer = (attn_params(cfg) + d * e.num_experts
                 + (e.num_experts + e.num_shared_experts)
                 * expert_params(cfg))
    nd = cfg.first_dense_layers
    return (nd * active_params(cfg, False) + (cfg.num_layers - nd)
            * moe_layer + 2 * cfg.vocab_size * d)


def latent_row_bytes(cfg, itemsize: int) -> int:
    """One token's latent KV in one layer: c_kv and the rope key."""
    return (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim) * itemsize


def attn_flops(cfg, lanes: Iterable[Tuple[int, int]]) -> float:
    """Absorbed-MLA scores and latent mix of one layer over ``lanes`` of
    (start, rows): per key and head, q_abs . c_kv and q_rope . k_rope
    (2 x (r + rd)) and p x c_kv (2 x r)."""
    m = cfg.mla
    per_key = cfg.num_heads * (2.0 * (m.kv_lora_rank + m.qk_rope_head_dim)
                               + 2.0 * m.kv_lora_rank)
    return per_key * sum(attended_keys(s, n) for s, n in lanes if n > 0)


def attn_bytes(cfg, lanes: Iterable[Tuple[int, int]],
               itemsize: int) -> float:
    """One layer's least traffic: every live latent row (r + rd wide) of a
    lane read once, its absorbed query rows (H x (r + rd)) read and its
    latent outputs (H x r) written once."""
    m = cfg.mla
    q_row = cfg.num_heads * (2 * m.kv_lora_rank + m.qk_rope_head_dim) \
        * itemsize
    return float(sum((s + n) * latent_row_bytes(cfg, itemsize) + n * q_row
                     for s, n in lanes if n > 0))


def attn_layers(cfg) -> int:
    """Layers that run the paged attention kernels: all of them."""
    return cfg.num_layers


def site_layers(cfg, site: str) -> int:
    """How many layers run a GEMM site of the dispatch registry: every
    layer for the MLA's ``layer.mla.*``, the dense stack for
    ``layer.mlp.*``, the MoE layers for ``moe.*``, once for the rest (the
    unembedding)."""
    nd = cfg.first_dense_layers
    if site.startswith("layer.mlp."):
        return nd
    if site.startswith("layer."):
        return cfg.num_layers
    if site.startswith("moe."):
        return cfg.num_layers - nd
    return 1


def model_flops(cfg, lanes: Iterable[Tuple[int, int]],
                logit_rows: int) -> float:
    """Model operations of one step over the live ``lanes`` (start, rows):
    each layer's active weights and the router for each live token, causal
    attention over its context, and the unembedding for each live row that
    yields logits."""
    lanes = [(s, n) for s, n in lanes if n > 0]
    tokens = sum(n for _, n in lanes)
    nd, L = cfg.first_dense_layers, cfg.num_layers
    weights = (nd * active_params(cfg, False) + (L - nd)
               * (active_params(cfg, True)
                  + cfg.d_model * cfg.moe.num_experts))
    return (2.0 * weights * tokens + L * attn_flops(cfg, lanes)
            + 2.0 * cfg.d_model * cfg.vocab_size * logit_rows)
