"""Operations and bytes the algorithms need, from their shapes.

Bytes are what a call has to move through HBM at least: each operand read
once and the result written once. A kernel that re-reads an operand moves
more; its roofline share then reads lower, which is the point. A model's
operations per position are its architecture's (`body_flops`, `head_flops`
and `mixer_flops` in `bench/archs/<name>.py`).
"""
from __future__ import annotations


def q8_matmul(M: int, K: int, N: int):
    """x (M, K) bf16 @ int8 (K, N) with an f32 scale per column -> bf16."""
    return 2 * M * K * N, K * N + 4 * N + 2 * M * K + 2 * M * N


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes)
