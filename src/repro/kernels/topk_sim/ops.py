"""Jit'd wrapper: normalize queries, pad tools, fused score + top-k."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.topk_sim.topk_sim import sim_scores


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x.astype(jnp.float32), axis=-1,
                                           keepdims=True), 1e-9)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_tools(tool_embeds, query_embeds, *, k: int, interpret: bool):
    """tool_embeds: (N, d) pre-normalized; query_embeds: (m, d) raw.
    Returns (scores (k,), indices (k,))."""
    q = _normalize(query_embeds)
    N, d = tool_embeds.shape
    bt = 1024 if N % 1024 == 0 else (256 if N % 256 == 0 else N)
    # pad query rows to sublane multiple
    m = q.shape[0]
    pad = (-m) % 8
    if pad:
        # pad with copies of row 0 — max-over-rows is unchanged
        q = jnp.concatenate([q, jnp.broadcast_to(q[:1], (pad, d))], axis=0)
    scores = sim_scores(tool_embeds, q, bt=bt, interpret=interpret)
    return jax.lax.top_k(scores, k)
