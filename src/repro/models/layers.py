"""Core layers: norms, rotary embeddings (incl. M-RoPE), GQA attention.

Attention comes in three implementations:
  * naive      — O(S^2) materialized logits; the oracle for tests.
  * chunked    — lax.scan over KV blocks with online softmax ("XLA flash");
                 O(S) memory, compiles on any backend; the dry-run path.
  * pallas     — kernels/flash_attention (TPU target), selected via RuntimeConfig.
Decode attention is a single-pass einsum over the cache; with the cache
sequence dim sharded over `model` GSPMD reduces the per-shard partial softmax
with two small all-reduces (flash-decode pattern).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.sharding.rules import batch_parallel, constrain

NEG_INF = -1e30


def rms_norm(x, w, eps: float = 1e-6, *, add_unit_offset: bool = True):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = w.astype(jnp.float32)
    scale = (1.0 + scale) if add_unit_offset else scale
    return (y * scale).astype(x.dtype)


def softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap if cap > 0.0 else x


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions: (..., S) int -> cos/sin (..., S, head_dim/2)."""
    inv = rope_freqs(head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions, head_dim: int, theta: float, sections: Tuple[int, int, int]):
    """Qwen2-VL M-RoPE: positions (3, B, S) for (temporal, height, width) streams.

    Each frequency band is driven by one of the three position streams,
    partitioned by `sections` (which sum to head_dim/2).
    """
    assert positions.shape[0] == 3
    inv = rope_freqs(head_dim, theta)                       # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv    # (3, B, S, hd/2)
    # which of the 3 streams drives each frequency band
    idx = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=head_dim // 2)
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang, 0, -1),                           # (B, S, hd/2, 3)
        idx[None, None, :, None].astype(jnp.int32),
        axis=-1,
    )[..., 0]                                               # (B, S, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, N, H); cos/sin: (B, S, H/2) or (S, H/2). Interleaved halves."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_positions(num_pos: int, d_model: int):
    pos = jnp.arange(num_pos, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d_model)
    out = jnp.zeros((num_pos, d_model), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return out


# ---------------------------------------------------------------------------
# Attention (training / prefill)
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int):
    """(…, Sq, Skv) additive bias from position comparisons."""
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    ok = jnp.ones(d.shape, bool)
    if causal:
        ok &= d >= 0
    if window > 0:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def repeat_kv(k, n_heads: int):
    """(B,S,K,H) -> (B,S,N,H). GQA KV heads are broadcast to the full head
    count BEFORE the attention einsums: a (K, G)-factorized einsum cannot
    shard 16 ways when K < 16 (GSPMD pays per-chunk all-to-alls to reshard
    the G factor — measured 0.8 TB/step on deepseek train), while the flat
    N-head form shards cleanly; XLA fuses the broadcast into the dot. The
    Pallas kernel keeps true no-copy GQA via its index maps."""
    K = k.shape[2]
    if K == n_heads:
        return k
    return jnp.repeat(k, n_heads // K, axis=2)


def naive_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                    q_offset=0, kv_offset=0):
    """Oracle. q: (B,Sq,N,H), k/v: (B,Skv,K,H) with N = K*G."""
    B, Sq, N, H = q.shape
    kf = repeat_kv(k, N).astype(jnp.float32)
    vf = repeat_kv(v, N).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    logits = jnp.einsum("bqnh,bsnh->bnqs", qf, kf) / jnp.sqrt(H).astype(jnp.float32)
    logits = softcap(logits, cap)
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = kv_offset + jnp.arange(k.shape[1])
    logits += _mask_bias(q_pos, kv_pos, causal=causal, window=window)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqs,bsnh->bqnh", p, vf)
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, causal=True, window=0, cap=0.0, chunk=512,
                      q_offset=0, kv_offset=0):
    """Online-softmax attention via lax.scan over KV chunks. O(Sq·chunk) memory."""
    B, Sq, N, H = q.shape
    Skv = k.shape[1]
    if Skv % chunk != 0:
        chunk = Skv  # degenerate fallback for tiny shapes
    n_chunks = Skv // chunk
    k = repeat_kv(k, N)
    v = repeat_kv(v, N)
    qr = (q.swapaxes(1, 2) / jnp.sqrt(H)).astype(jnp.float32)   # (B,N,Sq,H)
    q_pos = q_offset + jnp.arange(Sq)

    ks = k.reshape(B, n_chunks, chunk, N, H)
    vs = v.reshape(B, n_chunks, chunk, N, H)

    def body(carry, inp):
        m, lsum, acc = carry
        kc, vc, start = inp                                  # (B,chunk,N,H)
        logits = jnp.einsum("bnqh,bsnh->bnqs", qr, kc.astype(jnp.float32))
        logits = softcap(logits, cap)
        kv_pos = kv_offset + start + jnp.arange(chunk)
        logits += _mask_bias(q_pos, kv_pos, causal=causal, window=window)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = lsum * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bnqs,bsnh->bnqh", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, N, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, N, Sq), jnp.float32)
    acc0 = jnp.zeros((B, N, Sq, H), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (m, lsum, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (ks.swapaxes(0, 1), vs.swapaxes(0, 1), starts))
    out = acc / jnp.maximum(lsum, 1e-37)[..., None]
    out = out.swapaxes(1, 2)                                  # (B,Sq,N,H)
    return out.astype(q.dtype)


def attention(q, k, v, rcfg, **kw):
    """Dispatch on RuntimeConfig. Pallas path lives in kernels/flash_attention."""
    if rcfg is not None and rcfg.use_pallas:
        from repro.kernels.flash_attention import ops as fa_ops
        return batch_parallel(functools.partial(
            fa_ops.flash_attention, causal=kw.get("causal", True),
            window=kw.get("window", 0), cap=kw.get("cap", 0.0),
            q_offset=kw.get("q_offset", 0), interpret=rcfg.interpret),
            (q, k, v))
    chunk = rcfg.attn_chunk if rcfg is not None else 512
    if q.shape[1] * k.shape[1] <= 512 * 512:
        return naive_attention(q, k, v, **kw)
    return chunked_attention(q, k, v, chunk=chunk, **kw)


def prefix_attention(q, k_pre, v_pre, k_suf, v_suf, prefix_lens, q_positions,
                     *, window=0, cap=0.0):
    """Suffix attention over a cached prefix + freshly-projected suffix KV.

    Used by the paged engine's prefix-cache-hit prefill: the prompt's first
    `prefix_lens[b]` positions were already prefilled (their KV is gathered
    from the block pool into `k_pre`/`v_pre`), so only the suffix runs through
    the model and attends over [prefix, suffix] jointly.

      q, k_suf, v_suf: (B, S, N|K, H) at absolute positions `q_positions` —
                       (S,) uniform across rows, or (B, S) per-row (the
                       speculative-decode verify window, where every row
                       continues from its own length)
      k_pre, v_pre:    (B, P, K, H) at absolute positions 0..P-1, valid where
                       the position is < prefix_lens[b]
      prefix_lens:     (B,) cached tokens per row (0 = no cached prefix)

    Rows are left-padded: suffix slots whose absolute position falls inside
    the row's cached prefix are pad — they are masked out as *keys* (the
    prefix blocks already cover those positions) and their query outputs are
    garbage the caller discards. Math mirrors `naive_attention` (f32 einsum,
    softcap, additive NEG_INF bias) so a cache-hit prefill stays token-exact
    with the dense full-row prefill under greedy decoding.
    """
    B, S, N, H = q.shape
    P = k_pre.shape[1]
    k = jnp.concatenate([repeat_kv(k_pre, N), repeat_kv(k_suf, N)], axis=1)
    v = jnp.concatenate([repeat_kv(v_pre, N), repeat_kv(v_suf, N)], axis=1)
    qf = q.astype(jnp.float32)
    logits = jnp.einsum("bqnh,bsnh->bnqs", qf, k.astype(jnp.float32)) \
        / jnp.sqrt(H).astype(jnp.float32)
    logits = softcap(logits, cap)
    q_pos = q_positions                                       # (S,) or (B,S)
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None, :], (B, S))
    # suffix keys sit at the row's own query positions, so with per-row
    # q_positions the key-position grid is per-row too
    k_pos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(P)[None, :], (B, P)), q_pos],
        axis=1)                                               # (B, P+S)
    d = q_pos[:, :, None] - k_pos[:, None, :]                 # (B, S, P+S)
    ok = d >= 0                                               # causal
    if window > 0:
        ok &= d < window
    in_prefix = (k_pos[:, None, :] < prefix_lens[:, None, None])
    is_pre = jnp.concatenate([jnp.ones((P,), bool), jnp.zeros((S,), bool)])
    # prefix keys count only below the row's cached length; suffix keys only
    # at or above it (their positions overlap the prefix region in pad slots)
    ok &= jnp.where(is_pre[None, None, :], in_prefix, ~in_prefix)
    logits += jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[:, None]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqs,bsnh->bqnh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention (decode: one query position against a cache)
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, length, *, window=0, cap=0.0):
    """q: (B,1,N,H); caches: (B,Smax,K,H); length: () or (B,) current cache fill.

    Flash-decode layout: the cache stays sequence-sharded over `model`; q is
    replicated (it is tiny), the (B,K,G,S) logits are S-sharded and local to
    each cache shard, and only the softmax statistics and the (B,K,G,H)
    partial outputs cross links. GQA stays in (K,G) form here — repeating KV
    to N heads would force GSPMD to all-gather the cache (1 GB/layer/step
    measured on deepseek decode).
    """
    B, _, N, H = q.shape
    Smax, K = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    q = constrain(q, (None, None, None, None))               # replicate tiny q
    qr = (q.reshape(B, K, G, H) / jnp.sqrt(H)).astype(jnp.float32)
    logits = jnp.einsum("bkgh,bskh->bkgs", qr, k_cache.astype(jnp.float32))
    logits = constrain(logits, ("act_batch", None, None, "cache_seq"))
    logits = softcap(logits, cap)
    pos = jnp.arange(Smax)
    length = jnp.asarray(length)
    if length.ndim == 0:
        length = jnp.full((B,), length)
    valid = pos[None, :] < length[:, None]                   # (B, Smax)
    if window > 0:
        cur = length[:, None] - 1
        valid = valid & (pos[None, :] > cur - window)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    logits = logits + bias[:, None, None, :]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, N, H).astype(q.dtype)
