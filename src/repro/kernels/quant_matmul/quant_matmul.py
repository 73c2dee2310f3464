"""Fused dequant-matmul Pallas kernels: x(bf16) @ W(int8 | packed-int4).

The point (paper §III-D made kernel-real): decode-time matmuls are memory
bound, so the weight bytes that cross HBM->VMEM set the step time. Keeping
weights quantized in HBM and dequantizing in VMEM tiles right next to the MXU
cuts HBM traffic 2x (q8) / ~4x (q4) vs bf16 — the same mechanism that lets the
paper's Orin sustain TPS at lower power, expressed as a TPU kernel.

VMEM working set per grid step (defaults bm=128, bk=512, bn=256):
  q8:  x 128x512 bf16 (128 KiB) + w 512x256 int8 (128 KiB)
       + acc 128x256 f32 (128 KiB) + scale 1x256 f32 (1 KiB)   ~= 385 KiB
  q4:  bk=128 (= group size): x 32 KiB + w-packed 64x256 uint8 (16 KiB)
       + scale/zero 2x(K/128)x256 f32 (296 KiB at K=18944) + acc 128 KiB
                                                               ~= 472 KiB
Both fit VMEM (~128 MiB on v5e) with generous double-buffering headroom.
MXU alignment: bn, bk multiples of 128; bm multiple of 8 (f32 sublane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# int8 (Q8): W (K, N) int8, scale (1, N) f32 — per-output-channel
# ---------------------------------------------------------------------------


def _q8_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _fit(n: int, pref: int) -> int:
    """Largest 128-multiple block <= pref dividing n; else n itself."""
    b = min(pref, n)
    while b >= 128:
        if n % b == 0:
            return b
        b -= 128
    return n


def q8_matmul(x, wq, scale, *, bm=128, bk=512, bn=256, interpret=False):
    """x: (M, K) bf16; wq: (K, N) int8; scale: (1, N) f32 -> (M, N) bf16."""
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    bm, bk, bn = min(bm, M), _fit(K, bk), _fit(N, bn)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (x.shape, wq.shape)
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_q8_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, wq, scale)


# ---------------------------------------------------------------------------
# int4 (Q4_K_M-style): W packed (K/2, N) uint8, scale/zero (K/g, N) f32
# ---------------------------------------------------------------------------


def _q4_kernel(x_ref, w_ref, s_ref, z_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)               # (bm, bk)
    # (bk/2, bn) uint8; the TPU lowering casts uint8 to int32, not to f32
    packed = w_ref[...].astype(jnp.int32)
    lo = (packed & 0x0F).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    bk2, bn = packed.shape
    # packing is (even_rows | odd_rows << 4): un-interleave
    q = jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)
    k = pl.program_id(2)                             # block = 1 group
    s = s_ref[pl.ds(k, 1), :].astype(jnp.float32)    # (1, bn)
    z = z_ref[pl.ds(k, 1), :].astype(jnp.float32)    # (1, bn)
    # sum_k x_k*(q*s + z) = s * (x @ q) + (sum_k x_k) * z
    acc_ref[...] += s * jnp.dot(x, q, preferred_element_type=jnp.float32)
    acc_ref[...] += x.sum(axis=1, keepdims=True) * z

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def q4_matmul(x, wq, scale, zero, *, group=128, bm=128, bn=256, interpret=False):
    """x: (M, K) bf16; wq: (K/2, N) uint8 packed; scale/zero: (K/g, N) f32."""
    M, K = x.shape
    N = wq.shape[1]
    assert wq.shape[0] * 2 == K, (x.shape, wq.shape)
    bk = group                                       # one quant group per step
    bm, bn = min(bm, M), _fit(N, bn)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_q4_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            # every group's scale/zero row of the column block stays
            # resident across k: a one-row block of the (K/g, N) arrays
            # is not a tile the TPU lowering accepts
            pl.BlockSpec((nk, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((nk, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, wq, scale, zero)
