"""What every model's plain reference is built from: float32 ``jax.numpy``
at ``highest`` matmul precision, with no kernels, cache or batching.  Each
model module (``bench/models/<model_type>.py``) writes its forward pass
from the published equations with these pieces; none of them imports
anything of the program or reads a model's fields.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 (per-tensor scale for weights, per-row for activations) before
a float32 product, the step below the configuration's bfloat16 that a
later change could be tempted to take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 512              # sequences are padded to a multiple of this
Q_BLOCK = 512             # query rows per attention block
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0           # largest finite float8_e4m3fn


def to_fp8(x, axis):
    """Round to float8 e4m3 with a scale over ``axis`` (None: per tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, w, precision: str):
    """a (..., K) @ w (K, N) in float32."""
    if precision == "fp8":
        a, w = to_fp8(a, -1), to_fp8(w, None)
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


def causal_attention(q, k, v, precision: str):
    """Causal softmax attention in blocks of ``Q_BLOCK`` query rows, scaled
    by 1 / sqrt(dk).  q (T, H, dk); k (T, KVH, dk); v (T, KVH, dv), with
    KVH dividing H; T a multiple of ``Q_BLOCK``.  Returns (T, H, dv)."""
    T, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if precision == "fp8":
        q, k, v = to_fp8(q, -1), to_fp8(k, -1), to_fp8(v, -1)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if precision == "fp8":
            p = to_fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H, v.shape[-1])


def logit_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]
