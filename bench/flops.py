"""Operations and bytes the served path's work requires, from its shapes.

Counts what the algorithm needs, not what the program happens to do: an
encoder forward over each request's real, unpadded tokens; one score per live
row for the search; the rows read once by the top-k kernel.
"""
from __future__ import annotations


def encoder_flops(tokens: int, enc: dict) -> float:
    """Multiply-adds x 2 of one BERT-style forward over ``tokens`` tokens:
    Q, K, V, O and the two feed-forward matrices, plus the attention scores
    and their weighted sum."""
    d, F, n = enc["hidden_size"], enc["intermediate_size"], enc["num_hidden_layers"]
    dense = 2 * tokens * (4 * d * d + 2 * d * F)
    attn = 2 * 2 * tokens * tokens * d
    return float(n * (dense + attn))


def search_flops(rows: int, dim: int) -> float:
    """One query scored against every live row."""
    return 2.0 * rows * dim


def topk_bytes(rows: int, dim: int) -> float:
    """One top-k pass: every float32 row once, and its one-byte valid flag."""
    return float(rows) * (4 * dim + 1)


def topk_flops(batch: int, rows: int, dim: int) -> float:
    return 2.0 * batch * rows * dim


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
