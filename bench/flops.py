"""Operations and bytes the algorithms need, from their shapes.

Bytes are what a call has to move through HBM at least: each operand read
once and the result written once. A kernel that re-reads an operand moves
more; its roofline share then reads lower, which is the point.
"""
from __future__ import annotations


def q8_matmul(M: int, K: int, N: int):
    """x (M, K) bf16 @ int8 (K, N) with an f32 scale per column -> bf16."""
    return 2 * M * K * N, K * N + 4 * N + 2 * M * K + 2 * M * N


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes)


def matmul_shapes(dims: dict):
    """(K, N) of every quantized matrix product of one token's forward pass,
    in the order the model runs them: per layer q, k, v, o, gate, up, down,
    then the LM head."""
    d, f, V = dims["d"], dims["f"], dims["V"]
    NH, KH = dims["N"] * dims["H"], dims["K"] * dims["H"]
    layer = [(d, NH), (d, KH), (d, KH), (NH, d), (d, f), (d, f), (f, d)]
    return layer * dims["L"] + [(d, V)]


def body_flops(dims: dict) -> float:
    """Weight-product operations of one position, LM head left out (a
    prompt needs the head at its last position only)."""
    return sum(2 * k * n for k, n in matmul_shapes(dims)[:-1])


def head_flops(dims: dict) -> float:
    return 2 * dims["d"] * dims["V"]


def attn_flops(dims: dict, keys: float) -> float:
    """QK and PV products of one query position over `keys` positions."""
    return 4 * dims["L"] * dims["N"] * dims["H"] * keys
