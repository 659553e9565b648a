"""A configuration file's sizes as the program's ``ArchConfig``, and the
weights of a run, made on the device from the seed.

Every weight is uniform with a fan-in scale, drawn from a key that depends
only on (seed, leaf, layer).  ``make_params`` makes the whole tree in one
jitted call, in the type it is served in (bfloat16); ``make_layer`` makes
one layer's leaves in float32 from the same keys, which is how the plain
reference gets the same numbers without taking anything from the program.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1        # norm weights are 1 + U(-0.1, 0.1)

# leaf name -> index of its key stream; layer leaves fold in the layer too
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "ln1", "ln2")
HEAD_LEAVES = ("embed", "unembed", "ln_f")


def arch_config(conf: Dict):
    """The program's ``ArchConfig`` for a Llama-architecture config file."""
    from repro.configs.base import ArchConfig
    if conf.get("model_type") != "llama" or conf.get("hidden_act") != "silu":
        raise ValueError("only LlamaForCausalLM configurations are mapped")
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


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (64 bits and more fold in)."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if seed == 0:
            return key


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
    idx = (LAYER_LEAVES + HEAD_LEAVES).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer)
    return jax.random.uniform(k, shape, jnp.float32, -half, half)


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
