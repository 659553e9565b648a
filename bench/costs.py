"""Operations and bytes of the work the chip is asked to do, from shapes
alone: the numerators of every roofline share and of ``step_mfu``.

All counts are per call, with bfloat16 operands where the configuration
serves in bfloat16 (``itemsize``), and they are least counts: unpadded
shapes, each operand read once, each result written once.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, itemsize: int,
               out_itemsize: int) -> float:
    return float((m * k + k * n) * itemsize + m * n * out_itemsize)


def layer_params(cfg) -> int:
    """Weights one decoder layer multiplies by (norms excluded)."""
    d, q, kv, ff = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def attended_keys(start: int, rows: int) -> int:
    """Keys summed over ``rows`` causal query rows at positions
    ``start .. start + rows - 1`` (row at position p sees p + 1 keys)."""
    return rows * start + rows * (rows + 1) // 2


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


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


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
