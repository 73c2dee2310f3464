"""Blockwise flash attention (Pallas, TPU target) with GQA, causal masks,
sliding windows (gemma2 local layers), and logit softcapping.

Grid: (batch, q_head, Sq/bq, Skv/bk) — the KV dimension is innermost and
sequential, carrying online-softmax state (m, l, acc) in VMEM scratch across
KV steps for a fixed q block. GQA is handled in the index maps: q head `n`
reads kv head `n // (N/K)` — no KV replication in HBM.

Causal/window block skipping: fully-masked KV blocks are skipped with
pl.when (predicated on block-level position bounds), so causal attention does
~half the work and sliding-window attention touches only O(window) blocks per
q row — the kernel is what makes gemma2's local layers actually sub-quadratic
on TPU (the XLA reference path masks but cannot skip).

VMEM per step (bq=128, bk=256, H<=256):
  q 128xH bf16 + k/v 256xH bf16 + acc 128xH f32 + m/l 2x128x128 f32
  ~= (for H=128) 32 KiB + 128 KiB + 64 KiB + 128 KiB ~= 352 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, nk: int, causal: bool, window: int,
            cap: float, scale: float, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq + q_offset
    k_start = ik * bk

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, H)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, H)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if cap > 0.0:
            s = jnp.tanh(s / cap) * cap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window > 0:
            ok &= (q_pos - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, :1]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    # block-level relevance: skip fully-masked KV blocks (causal upper
    # triangle / outside the sliding window)
    conds = []
    if causal:
        conds.append(q_start + bq - 1 >= k_start)
    if window > 0:
        conds.append(k_start + bk - 1 >= q_start - window + 1)
    if conds:
        cond = conds[0]
        for c in conds[1:]:
            cond = jnp.logical_and(cond, c)
        pl.when(cond)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _done():
        lsum = jnp.maximum(l_ref[:, :1], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / lsum).astype(o_ref.dtype)


def flash_attention_bnh(q, k, v, *, causal=True, window=0, cap=0.0,
                        q_offset=0, bq=128, bk=256, interpret=False):
    """q: (B, N, Sq, H); k/v: (B, K, Skv, H) -> (B, N, Sq, H)."""
    B, N, Sq, H = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = N // K
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (q.shape, k.shape, bq, bk)
    nq, nk = Sq // bq, Skv // bk
    grid = (B, N, nq, nk)
    scale = 1.0 / (H ** 0.5)
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        cap=cap, scale=scale, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, H), lambda b, n, i, j: (b, n, i, 0)),
            pl.BlockSpec((1, 1, bk, H), lambda b, n, i, j: (b, n // G, j, 0)),
            pl.BlockSpec((1, 1, bk, H), lambda b, n, i, j: (b, n // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, H), lambda b, n, i, j: (b, n, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, H), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
