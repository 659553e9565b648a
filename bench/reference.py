"""The plain reference: a Llama-architecture forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, written from the published
equations (Hugging Face ``LlamaForCausalLM``), with no kernels, cache or
batching.  It imports nothing of the program and takes nothing it made: the
weights come from ``weights.make_layer``/``make_head`` and the seed.

One sequence at a time, layer by layer, so that it fits beside what is left
on the chip.  Sequences are padded at the end to a multiple of ``BUCKET``
(causal attention leaves earlier positions unchanged), which bounds the
number of distinct programs.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 (per-tensor scale for weights, per-row for activations) before
a float32 product, the step below the configuration's bfloat16 that a
later change could be tempted to take.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weights import make_head, make_layer, seed_key

BUCKET = 512
Q_BLOCK = 512             # query rows per attention block
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0           # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale over ``axis`` (None: per tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, precision: str):
    """a (..., K) @ w (K, N) in float32."""
    if precision == "fp8":
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x, theta: float):
    """x (T, heads, hd) at positions 0..T-1, as HF Llama rotates it."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def attention(q, k, v, precision: str):
    """Causal softmax attention.  q (T, H, hd); k, v (T, KVH, hd)."""
    T, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if precision == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if precision == "fp8":
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H, hd)


@functools.partial(jax.jit, static_argnames=("conf_key", "precision"))
def _layer(h, w, conf_key, precision):
    H, KVH, hd, theta, eps = conf_key
    T = h.shape[0]
    x = rms_norm(h, w["ln1"], eps)
    q = rope(_mm(x, w["wq"], precision).reshape(T, H, hd), theta)
    k = rope(_mm(x, w["wk"], precision).reshape(T, KVH, hd), theta)
    v = _mm(x, w["wv"], precision).reshape(T, KVH, hd)
    o = attention(q, k, v, precision).reshape(T, H * hd)
    h = h + _mm(o, w["wo"], precision)
    x = rms_norm(h, w["ln2"], eps)
    g = _mm(x, w["w_gate"], precision)
    u = _mm(x, w["w_up"], precision)
    return h + _mm(jax.nn.silu(g) * u, w["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(h, ln_f, unembed, eps, precision):
    return _mm(rms_norm(h, ln_f, eps), unembed, precision)


_make_layer = jax.jit(make_layer, static_argnums=(1,))
_make_head = jax.jit(make_head, static_argnums=(1,))


def served_logits(seed: int, cfg, seqs: Sequence[np.ndarray],
                  rows: Sequence[np.ndarray],
                  precision: str = "f32") -> List[np.ndarray]:
    """For each token sequence, float32 logits at the positions ``rows``
    (each (n,) int): ``out[i][j]`` is the distribution over the token that
    follows ``seqs[i][rows[i][j]]``."""
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


def logit_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]
