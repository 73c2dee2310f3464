"""Fused dequant-matmul Pallas kernels: x(bf16) @ W(int8 | packed-int4).

The point (paper §III-D made kernel-real): decode-time matmuls are memory
bound, so the weight bytes that cross HBM->VMEM set the step time. Keeping
weights quantized in HBM and dequantizing in VMEM tiles right next to the MXU
cuts HBM traffic 2x (q8) / ~4x (q4) vs bf16 — the same mechanism that lets the
paper's Orin sustain TPS at lower power, expressed as a TPU kernel.

Q8 reads its weight in place from a stack of layers: `wq` (L, K, N) and
`scale` (L, 1, N) stay in HBM (`pl.ANY`) and the kernel DMAs layer `l`'s
blocks into a two-slot VMEM buffer, fetching the next grid step's block while
it computes this one. The layer index is the last operand, an SMEM s32[1], so
the call's operands read (x, w, scale, layer). A model's layer scan hands the
kernel the whole stack and its index: a Pallas call cannot read a slice in
place, so a sliced operand would make XLA copy every layer's weights before
every call. A weight stored as one matrix is a stack of one.

Tiles follow the row count M (`q8_tiles`), chosen by timing the published
Qwen2-7B shapes on a v5e (`benchmarks/kernels_bench.py --chip`):
  decode (M < 128: 8 decode rows, verify windows): x (M, K) resident, the
       whole K in one block, grid over N only, bn the widest of at most 512
       whose two weight slots fit `Q8_VMEM_BUDGET`. At K=18944, bn=256:
       w 2 x 18944x256 int8 (9.25 MiB) + x 2 x 8x18944 bf16 (0.6 MiB)
       + a 512-row cast slab and the out block                ~= 10 MiB;
       at K=3584, bn=512: w 2 x 3584x512 int8 (3.5 MiB)      ~= 4 MiB.
       86-87% of HBM bandwidth on the large products; wider blocks under a
       raised VMEM limit read no faster.
  prefill (M >= 128): grid (M/bm, N/bn, K/bk), bm up to 1024, bk=bn=512:
       x 2 x 1024x512 bf16 (2 MiB) + w 2 x 512x512 int8 (0.5 MiB)
       + acc 1024x512 f32 (2 MiB) + out 2 x 1024x512 bf16 (2 MiB)
       + the dot's f32 partial (2 MiB)                         ~= 8.5 MiB.
       73-81% of the roofline on the large products; 128x512x256 tiles
       reach 36%.
  q4:  bk=128 (= group size): x 32 KiB + w-packed 64x256 uint8 (16 KiB)
       + scale/zero 2x(K/128)x256 f32 (296 KiB at K=18944) + acc 128 KiB
                                                               ~= 472 KiB
Every Q8 working set stays within the 16 MiB of scoped VMEM the compiler
grants a kernel on v5e by default.
MXU alignment: bn, bk multiples of 128; bm multiple of 8 (f32 sublane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# int8 (Q8): W (L, K, N) int8, scale (L, 1, N) f32 — per-output-channel
# ---------------------------------------------------------------------------

# rows from which the Q8 kernel tiles K (prefill) rather than keeping x and
# a whole-K weight block resident (decode, verify windows)
Q8_PREFILL_ROWS = 128
# scoped VMEM the decode tiles may fill: v5e's default scoped limit less
# room for the compiler's own scratch
Q8_VMEM_BUDGET = 12 * 1024 * 1024
# rows of the weight block cast and multiplied at a time inside a step
Q8_CHUNK = 512


def _fit(n: int, pref: int) -> int:
    """Largest 128-multiple block <= pref dividing n; else n itself."""
    b = min(pref, n)
    while b >= 128:
        if n % b == 0:
            return b
        b -= 128
    return n


def q8_tiles(M: int, K: int, N: int):
    """(bm, bk, bn) for an (M, K) @ (K, N) Q8 product."""
    if M >= Q8_PREFILL_ROWS:
        return _fit(M, 1024), _fit(K, 512), _fit(N, 512)
    bn = _fit(N, 512)
    while bn > 128 and 2 * K * bn + 4 * M * K > Q8_VMEM_BUDGET:
        bn = _fit(N, bn - 128)
    return M, K, bn


def _q8_kernel(x_ref, w_hbm, s_hbm, l_ref, o_ref, w_buf, s_buf, sem,
               *acc, nj: int, nk: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bk, bn = w_buf.shape[1:]
    layer = l_ref[0]
    step = (i * nj + j) * nk + k
    slot = step % 2

    def copies(jj, kk, s):
        return (pltpu.make_async_copy(
                    w_hbm.at[layer, pl.ds(kk * bk, bk), pl.ds(jj * bn, bn)],
                    w_buf.at[s], sem.at[0, s]),
                pltpu.make_async_copy(
                    s_hbm.at[layer, :, pl.ds(jj * bn, bn)],
                    s_buf.at[s], sem.at[1, s]))

    @pl.when(step == 0)
    def _first():
        for c in copies(j, k, slot):
            c.start()

    # the next grid step's block streams in while this one computes
    last_k = k == nk - 1
    k_next = jnp.where(last_k, 0, k + 1)
    j_next = jnp.where(last_k, jnp.where(j == nj - 1, 0, j + 1), j)

    @pl.when(step + 1 < pl.num_programs(0) * nj * nk)
    def _prefetch():
        for c in copies(j_next, k_next, 1 - slot):
            c.start()

    for c in copies(j, k, slot):
        c.wait()

    # int8 codes are exact in bf16: a bf16 x meets bf16 weights on the MXU
    # (products exact, sums in f32), which ran the gate/up prefill product
    # 3.7x faster than f32 operands on a v5e. An f32 x keeps f32
    dt = jnp.bfloat16 if x_ref.dtype == jnp.bfloat16 else jnp.float32
    part = None
    for c0 in range(0, bk, Q8_CHUNK):          # cast a slab at a time
        c1 = min(c0 + Q8_CHUNK, bk)
        d = jnp.dot(x_ref[:, c0:c1].astype(dt),
                    w_buf[slot, c0:c1, :].astype(dt),
                    preferred_element_type=jnp.float32)
        part = d if part is None else part + d

    if nk == 1:
        o_ref[...] = (part * s_buf[slot]).astype(o_ref.dtype)
        return
    acc_ref, = acc

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += part

    @pl.when(last_k)
    def _done():
        o_ref[...] = (acc_ref[...] * s_buf[slot]).astype(o_ref.dtype)


def q8_matmul(x, wq, scale, layer, *, tiles=None, interpret=False):
    """x: (M, K) @ layer `layer` of wq: (L, K, N) int8 with scale (L, 1, N)
    f32 -> (M, N) in x's dtype. `tiles` (bm, bk, bn) overrides `q8_tiles`."""
    M, K = x.shape
    _, K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    bm, bk, bn = tiles or q8_tiles(M, K, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (x.shape, wq.shape)
    nj, nk = N // bn, K // bk
    scratch = [pltpu.VMEM((2, bk, bn), wq.dtype),
               pltpu.VMEM((2, 1, bn), jnp.float32),
               pltpu.SemaphoreType.DMA((2, 2))]
    if nk > 1:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))
    return pl.pallas_call(
        functools.partial(_q8_kernel, nj=nj, nk=nk),
        grid=(M // bm, nj, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=scratch,
        # each step starts the next one's DMA: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(x, wq, scale, jnp.asarray(layer, jnp.int32).reshape(1))


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
