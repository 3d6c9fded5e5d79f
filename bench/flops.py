"""Operations and bytes of the work a cell asks for, from shapes alone.

These counts are the yardstick's, not the program's: they say what the
algorithm needs, so they stay the same when the program's kernels change.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def matmul_params(c: dict) -> int:
    """Weights that take part in matmuls (the embedding lookup does not)."""
    D, F, V = c["d_model"], c["d_ff"], c["vocab_size"]
    H, K, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return c["num_layers"] * per_layer + D * V


def attention_flops_forward(c: dict, batch: int, seq: int) -> int:
    """Causal scores and weighted values over the unmasked pairs only."""
    pairs = seq * (seq + 1) // 2
    return c["num_layers"] * 2 * 2 * batch * c["num_heads"] * c["head_dim"] * pairs


def train_flops_per_step(c: dict, batch: int, seq: int) -> int:
    """Forward and backward (3x forward) of one step; recompute not counted."""
    tokens = batch * seq
    return 6 * matmul_params(c) * tokens + 3 * attention_flops_forward(c, batch, seq)


def weight_bytes_at_rest(shapes: Iterable[Sequence[int]], weights: str,
                         block: int = 128, threshold: int = 4096) -> int:
    """Bytes a forward pass reads of the served weights in their at-rest
    format: ``bf16`` 2 B per element of each matrix, ``q4`` half a byte
    plus a 4-byte scale per block of ``block``; vectors and tensors of
    ``threshold`` elements or fewer stay float32."""
    total = 0
    for s in shapes:
        n = math.prod(s)
        if len(s) < 2 or n <= threshold:
            total += 4 * n
        elif weights == "bf16":
            total += 2 * n
        elif weights == "q4":
            total += n // 2 + 4 * (-(-n // block))
        else:
            raise ValueError(f"unknown weights format {weights!r}")
    return total


def kv_bytes_per_entry(c: dict, itemsize: int = 2) -> int:
    """Bytes of one cached position of one sequence: K and V of every layer."""
    return c["num_layers"] * 2 * c["num_kv_heads"] * c["head_dim"] * itemsize
