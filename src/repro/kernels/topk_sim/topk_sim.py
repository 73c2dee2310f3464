"""Fused tool-retrieval scoring kernel (paper Eq. 3 on TPU).

Computes Score(t_j) = max_i cos(s_i, t_j) for every tool j in one pass:
the tool-embedding matrix streams through VMEM in blocks, each block is
scored against all query sentences on the MXU, and only the (N_tools,)
max-over-sentences vector is written back — the (m, N) similarity matrix
never touches HBM. This is the FAISS-replacement adaptation from DESIGN.md:
for edge-scale tool sets (<=100k) an exact blocked scan on the MXU beats ANN
index chasing, and fuses the paper's max-over-sentences reduction for free.

Embeddings are pre-normalized at index build time; queries are normalized in
ops.py, so cosine == dot. Top-k over the (N,) score vector happens outside
(jax.lax.top_k on a vector is trivial).

VMEM per step (bt=1024, d<=512, m<=32): tools 1024xd bf16 (1 MiB at d=512)
+ queries mxd + scores 1024x32 f32 ~= 1.2 MiB.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(t_ref, q_ref, o_ref):
    t = t_ref[...].astype(jnp.float32)                # (bt, d)
    q = q_ref[...].astype(jnp.float32)                # (m, d)
    sims = jax.lax.dot_general(t, q, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (bt, m)
    o_ref[0, :] = jnp.max(sims, axis=1)


def sim_scores(tools, queries, *, bt=1024, interpret=False):
    """tools: (N, d) L2-normalized; queries: (m, d) L2-normalized
    -> scores (N,) = max over queries of cosine similarity."""
    N, d = tools.shape
    m = queries.shape[0]
    bt = min(bt, N)
    assert N % bt == 0, (N, bt)
    grid = (N // bt,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(tools, queries)
    return out[0]
