"""Jit'd wrapper converting model layout (B,S,N,H) <-> kernel layout (B,N,S,H)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_bnh


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, cap=0.0, q_offset=0,
                    interpret):
    """q: (B, Sq, N, H); k/v: (B, Skv, K, H) -> (B, Sq, N, H)."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = flash_attention_bnh(qt, kt, vt, causal=causal, window=window,
                              cap=float(cap), q_offset=q_offset,
                              interpret=interpret)
    return out.swapaxes(1, 2)
