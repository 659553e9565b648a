"""Operations and bytes of the work the chip is asked to do, from shapes
alone: the numerators of every roofline share and of ``step_mfu``.  What
depends on a model's layers (its weights per token, its attention) is
counted by its module, ``bench/models/<model_type>.py``, with these.

All counts are per call, with bfloat16 operands where the configuration
serves in bfloat16 (``itemsize``), and they are least counts: unpadded
shapes, each operand read once, each result written once.
"""

from __future__ import annotations


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, itemsize: int,
               out_itemsize: int) -> float:
    return float((m * k + k * n) * itemsize + m * n * out_itemsize)


def attended_keys(start: int, rows: int) -> int:
    """Keys summed over ``rows`` causal query rows at positions
    ``start .. start + rows - 1`` (row at position p sees p + 1 keys)."""
    return rows * start + rows * (rows + 1) // 2


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
