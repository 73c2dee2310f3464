"""Paged-attention decode kernel (Pallas, TPU target).

One query token per sequence attends over a KV cache scattered across a block
pool: `block_tables` maps (sequence, logical block) -> physical block id, and
the kernel walks a sequence's chain without ever materializing the gathered
(B, S, K, H) view the XLA fallback builds.

Grid: (batch, max_blocks) — the block dimension is innermost and
sequential, carrying online-softmax state (m, l, acc) in VMEM scratch exactly
like the flash-attention kernel. Each step DMAs one whole physical block
(bs, K, H) and loops the K kv heads inside the kernel: a block spec whose
last two dims are the array's own (K, H) is what the TPU lowering accepts,
where a per-head (bs, 1, H) stripe is refused. The block table and per-row
lengths ride in as scalar-prefetch operands (`pltpu.PrefetchScalarGridSpec`),
so the KV index maps can resolve `bt[b, j]` before the DMA for step j issues
— the physical block fetch is data-dependent but still pipelined.

int8 pools (fused dequant): with `k_scale`/`v_scale` stripes the pool leaves
are int8 and the per-(position, head) fp32 scales ride in as two extra
operands sharing the k/v index maps. Dequant happens in-VMEM right after the
DMA (`k_int8 * scale`), so HBM traffic stays int8 — the bandwidth the block
pool saved is the bandwidth the decode step saves.

Split-K (flash-decode): `num_splits > 1` partitions the block chain over an
extra grid axis — grid (batch, split, blocks_per_split). Each split
accumulates its own online-softmax partial and flushes (m, l, acc) into
per-split VMEM scratch; the last split combines all partials with the usual
max-rebased merge. For long chains this bounds the sequential chain walk per
state vector — the lowering a real flash-decode pass parallelizes over
megacore/vector units.

GQA stays no-copy: q arrives as (B, K, G, H) and kv head h's G query heads
read only the block's (bs, H) stripe of head h. Blocks past a row's length are
skipped with `pl.when` (their DMA still targets a valid pool slot — dead rows
point at the reserved scratch block 0), so a mostly-empty cache costs only its
occupied blocks.

VMEM per step (bs=16..128, H<=256): q K x G x H bf16 + k/v bs x K x H (bf16
or int8 + 2 x bs x K fp32 scales) + acc K x G x H f32 + m/l 2 x K x G x 128
f32 — plus, under split-K, S x K x (2 x G x 128 + G x H) f32 partials — well
under the budget for any real K x G.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest, bs: int, nbs: int,
            splits: int, scale: float, cap: float, window: int,
            quantized: bool):
    refs = list(rest)
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    o_ref = refs[0]
    m_ref, l_ref, acc_ref = refs[1:4]
    ms_ref = ls_ref = accs_ref = None
    if splits > 1:
        ms_ref, ls_ref, accs_ref = refs[4:]
    K = q_ref.shape[1]

    b = pl.program_id(0)
    if splits > 1:
        s_id = pl.program_id(1)
        j = pl.program_id(2)
    else:
        s_id = 0
        j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    start = (s_id * nbs + j) * bs          # global position of this block

    def _compute():
        for h in range(K):                  # kv heads of the resident block
            q = q_ref[0, h].astype(jnp.float32) * scale      # (G, H)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (bs, H)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                # fused dequant: int8 stripes just DMA'd, scales broadcast
                # per position — the gathered bf16 view never exists
                k = k * ks_ref[0, :, h:h + 1]
                v = v * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if cap > 0.0:
                s = jnp.tanh(s / cap) * cap
            G = s.shape[0]
            pos = start + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
            ok = pos < length
            if window > 0:
                ok &= pos > length - 1 - window
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h, :, :1]                         # (G, 1)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h, :, :1] = (l_ref[h, :, :1] * alpha
                               + p.sum(axis=1, keepdims=True))
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h, :, :1] = m_new

    pl.when(start < length)(_compute)

    @pl.when(j == nbs - 1)
    def _flush():
        if splits == 1:
            lsum = jnp.maximum(l_ref[:, :, :1], 1e-37)
            o_ref[0] = (acc_ref[...] / lsum).astype(o_ref.dtype)
        else:
            # park this split's partial online-softmax state; an untouched
            # split (chain shorter than its range) parks (NEG_INF, 0, 0),
            # which the merge weights to exactly zero
            ms_ref[s_id] = m_ref[...]
            ls_ref[s_id] = l_ref[...]
            accs_ref[s_id] = acc_ref[...]

            @pl.when(s_id == splits - 1)
            def _combine():
                m_all = ms_ref[:, :, :, :1]                  # (S, K, G, 1)
                m_tot = jnp.max(m_all, axis=0)               # (K, G, 1)
                w = jnp.exp(m_all - m_tot[None])
                l_tot = jnp.sum(ls_ref[:, :, :, :1] * w, axis=0)
                acc_tot = jnp.sum(accs_ref[...] * w, axis=0)  # (K, G, H)
                lsum = jnp.maximum(l_tot, 1e-37)
                o_ref[0] = (acc_tot / lsum).astype(o_ref.dtype)


def paged_attention_bkgh(q, k_pool, v_pool, block_tables, lengths, *,
                         k_scale=None, v_scale=None, cap=0.0, window=0,
                         num_splits=1, interpret=False):
    """q: (B, K, G, H); pools: (num_blocks, bs, K, H) — bf16, or int8 with
    (num_blocks, bs, K) fp32 `k_scale`/`v_scale`; block_tables: (B, nb)
    int32; lengths: (B,) int32 -> (B, K, G, H)."""
    B, K, G, H = q.shape
    bs = k_pool.shape[1]
    nb = block_tables.shape[1]
    quantized = k_scale is not None
    splits = max(1, min(int(num_splits), nb))
    nbs = -(-nb // splits)                 # blocks per split (last ragged)
    scale = 1.0 / (H ** 0.5)
    kernel = functools.partial(_kernel, bs=bs, nbs=nbs, splits=splits,
                               scale=scale, cap=float(cap),
                               window=int(window), quantized=quantized)

    if splits > 1:
        grid = (B, splits, nbs)

        def _block(b, s, j, bt, ln):
            # split s's j-th block; the ragged tail past nb-1 clamps to a
            # valid table slot (the kernel masks it via start >= length)
            return bt[b, jnp.minimum(s * nbs + j, nb - 1)]
    else:
        grid = (B, nb)

        def _block(b, j, bt, ln):
            return bt[b, j]

    def q_map(b, *rest):
        return (b, 0, 0, 0)

    def kv_map(*idx):
        return (_block(*idx), 0, 0, 0)

    def sc_map(*idx):
        return (_block(*idx), 0, 0)

    # whole (bs, K, H) pool blocks per step: the last two block dims equal
    # the array's, which is what the TPU lowering requires of them
    in_specs = [
        pl.BlockSpec((1, K, G, H), q_map),
        pl.BlockSpec((1, bs, K, H), kv_map),
        pl.BlockSpec((1, bs, K, H), kv_map),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, K), sc_map),
                     pl.BlockSpec((1, bs, K), sc_map)]
        operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    scratch = [
        pltpu.VMEM((K, G, 128), jnp.float32),
        pltpu.VMEM((K, G, 128), jnp.float32),
        pltpu.VMEM((K, G, H), jnp.float32),
    ]
    if splits > 1:
        scratch += [
            pltpu.VMEM((splits, K, G, 128), jnp.float32),
            pltpu.VMEM((splits, K, G, 128), jnp.float32),
            pltpu.VMEM((splits, K, G, H), jnp.float32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block_tables, lengths
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, H), q_map),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
