"""The Llama family (Hugging Face ``LlamaForCausalLM``, ``model_type``
"llama"): dense GQA or MHA attention with rotary positions, a gated SiLU
MLP, RMS norms.

What the harness needs of a model family, found by the configuration
file's ``model_type`` (``bench/models/<model_type>.py``):

- ``arch_config(conf)``: the program's ``ArchConfig``;
- ``make_params(seed, cfg)``: the served tree, made on the device;
- ``served_logits(seed, cfg, seqs, rows, precision)``: the plain float32
  reference and its float8 control;
- ``model_flops``, ``attn_flops``, ``attn_bytes``, ``attn_layers``,
  ``site_layers``: the counts behind ``step_mfu`` and the roofline shares;
- ``PAGED_ATTN_KERNELS``: the custom calls of its paged attention.

Every weight is uniform with a fan-in scale, drawn from a key that depends
only on (seed, leaf, layer) (``weights.draw``).  ``make_params`` makes the
whole tree in one jitted call, in the type it is served in; ``make_layer``
makes one layer's leaves in float32 from the same keys, which is how the
reference gets the same numbers without taking anything from the program.

The reference is the published forward pass in float32 ``jax.numpy`` at
``highest`` matmul precision, with no kernels, cache or batching, one
sequence at a time, layer by layer, so that it fits beside what is left on
the chip.  Sequences are padded at the end to a multiple of
``reference.BUCKET`` (causal attention leaves earlier positions
unchanged), which bounds the number of distinct programs.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from costs import attended_keys
from reference import (BUCKET, causal_attention, mm, rms_norm, rope)
from weights import NORM_SPREAD, draw, seed_key

# leaf name -> index of its key stream; layer leaves fold in the layer too
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "ln1", "ln2")
HEAD_LEAVES = ("embed", "unembed", "ln_f")

# the paged kernels' custom calls, named after their jitted wrappers
# (``kernels/ops.py``)
PAGED_ATTN_KERNELS = ("%paged_attention", "%paged_prefill_attention")


def arch_config(conf: Dict):
    """The program's ``ArchConfig`` for a Llama-architecture config file."""
    from repro.configs.base import ArchConfig
    if conf.get("hidden_act") != "silu":
        raise ValueError("only the SiLU-gated MLP is mapped")
    if conf.get("rope_scaling") or conf.get("attention_bias"):
        raise ValueError("rope scaling and attention bias are not mapped")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        conf["torch_dtype"]]
    return ArchConfig(
        name=conf.get("name", "bench"), family="dense",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim", d // h),
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
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
    d, q, kv, ff = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    L = cfg.num_layers

    def fan(n_in, extra=1.0):
        return math.sqrt(3.0 / (n_in * extra))   # uniform with std 1/sqrt(.)

    return {
        "wq": ((d, q), fan(d)), "wk": ((d, kv), fan(d)),
        "wv": ((d, kv), fan(d)), "wo": ((q, d), fan(q, 2 * L)),
        "w_gate": ((d, ff), fan(d)), "w_up": ((d, ff), fan(d)),
        "w_down": ((ff, d), fan(ff, 2 * L)),
        "ln1": ((d,), NORM_SPREAD), "ln2": ((d,), NORM_SPREAD),
        "embed": ((cfg.vocab_size, d), math.sqrt(3.0)),
        "unembed": ((d, cfg.vocab_size), fan(d)),
        "ln_f": ((d,), NORM_SPREAD),
    }


def _draw(key, name: str, layer, shape, half):
    return draw(key, (LAYER_LEAVES + HEAD_LEAVES).index(name), layer, shape,
                half)


def make_layer(key, cfg, layer) -> Dict[str, jnp.ndarray]:
    """Float32 leaves of one layer, flat: wq ... w_down, and the norm
    weights as the HF checkpoint stores them (1 + scale); each value is
    first rounded to ``cfg.param_dtype``, as the served weights are."""
    shapes, dt = _leaf_shapes(cfg), jnp.dtype(cfg.param_dtype)
    out = {}
    for name in LAYER_LEAVES:
        shape, half = shapes[name]
        w = _draw(key, name, layer, shape, half).astype(dt)
        w = w.astype(jnp.float32)
        out[name] = 1.0 + w if name.startswith("ln") else w
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
    and in ``cfg.param_dtype``.  The program stores a norm weight as
    ``scale`` and applies ``1 + scale``."""
    dt = jnp.dtype(cfg.param_dtype)
    shapes = _leaf_shapes(cfg)

    def build(key):
        def layer_leaf(name):
            shape, half = shapes[name]
            return jax.vmap(lambda i: _draw(key, name, i, shape, half))(
                jnp.arange(cfg.num_layers)).astype(dt)

        def head_leaf(name):
            shape, half = shapes[name]
            return _draw(key, name, 0, shape, half).astype(dt)

        return {
            "embed": head_leaf("embed"), "unembed": head_leaf("unembed"),
            "ln_f": head_leaf("ln_f"),
            "layers": {
                "ln1": layer_leaf("ln1"), "ln2": layer_leaf("ln2"),
                "attn": {n: layer_leaf(n) for n in ("wq", "wk", "wv", "wo")},
                "mlp": {n: layer_leaf(n)
                        for n in ("w_gate", "w_up", "w_down")},
            },
        }

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("conf_key", "precision"))
def _layer(h, w, conf_key, precision):
    H, KVH, hd, theta, eps = conf_key
    T = h.shape[0]
    x = rms_norm(h, w["ln1"], eps)
    q = rope(mm(x, w["wq"], precision).reshape(T, H, hd), theta)
    k = rope(mm(x, w["wk"], precision).reshape(T, KVH, hd), theta)
    v = mm(x, w["wv"], precision).reshape(T, KVH, hd)
    o = causal_attention(q, k, v, precision).reshape(T, H * hd)
    h = h + mm(o, w["wo"], precision)
    x = rms_norm(h, w["ln2"], eps)
    g = mm(x, w["w_gate"], precision)
    u = mm(x, w["w_up"], precision)
    return h + mm(jax.nn.silu(g) * u, w["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(h, ln_f, unembed, eps, precision):
    return mm(rms_norm(h, ln_f, eps), unembed, precision)


_make_layer = jax.jit(make_layer, static_argnums=(1,))
_make_head = jax.jit(make_head, static_argnums=(1,))


def served_logits(seed: int, cfg, seqs: Sequence[np.ndarray],
                  rows: Sequence[np.ndarray],
                  precision: str = "f32") -> List[np.ndarray]:
    """For each token sequence, float32 logits at the positions ``rows``
    (each (n,) int): ``out[i][j]`` is the distribution over the token that
    follows ``seqs[i][rows[i][j]]``.  ``precision="fp8"`` is the control
    (``reference.to_fp8``)."""
    key = seed_key(seed)
    conf_key = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                float(cfg.rope_theta), float(cfg.norm_eps))
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
            hs = [_layer(h, w, conf_key, precision) for h in hs]
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

def layer_params(cfg) -> int:
    """Weights one decoder layer multiplies by (norms excluded)."""
    d, q, kv, ff = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def attn_flops(cfg, lanes: Iterable[Tuple[int, int]]) -> float:
    """QK^T and PV operations of one layer over ``lanes`` of (start, rows)."""
    per_key = 4.0 * cfg.num_heads * cfg.head_dim
    return per_key * sum(attended_keys(s, n) for s, n in lanes if n > 0)


def attn_bytes(cfg, lanes: Iterable[Tuple[int, int]],
               itemsize: int) -> float:
    """One layer's least traffic: every live K and V row of a lane read
    once, its query rows read and its output rows written once."""
    kv_row = 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
    q_row = 2 * cfg.num_heads * cfg.head_dim * itemsize
    return float(sum((s + n) * kv_row + n * q_row
                     for s, n in lanes if n > 0))


def attn_layers(cfg) -> int:
    """Layers that run the paged attention kernels: all of them."""
    return cfg.num_layers


def site_layers(cfg, site: str) -> int:
    """How many layers run a GEMM site of the dispatch registry: every
    layer for ``layer.*``, once for the rest (the unembedding)."""
    return cfg.num_layers if site.startswith("layer.") else 1


def model_flops(cfg, lanes: Iterable[Tuple[int, int]],
                logit_rows: int) -> float:
    """Model operations of one step over the live ``lanes`` (start, rows):
    every layer's weights for each live token, causal attention over its
    context, and the unembedding for each live row that yields logits."""
    lanes = [(s, n) for s, n in lanes if n > 0]
    tokens = sum(n for _, n in lanes)
    per_layer = 2.0 * layer_params(cfg) * tokens + attn_flops(cfg, lanes)
    return (cfg.num_layers * per_layer
            + 2.0 * cfg.d_model * cfg.vocab_size * logit_rows)
