"""Mamba2 SSD chunk-scan Pallas kernel.

Grid: (B, H, S/Q) with the chunk dim innermost and sequential — the inter-chunk
SSM state (P, N) lives in VMEM scratch and is carried across chunk steps for a
fixed (batch, head), exactly the sequential-grid + VMEM-carry idiom the TPU
pipeline emitter supports. Intra-chunk work is three (Q,Q)/(Q,P)/(Q,N) dense
matmuls on the MXU — this is the SSD insight (quadratic-in-chunk dual form)
mapped onto TPU tiling.

VMEM per step (Q=128, P=64, N=128):
  x/dt/B/C blocks: 128x64 + 128 + 2x128x128 f32 ~= 166 KiB
  state scratch 64x128 f32 = 32 KiB; decay matrix 128x128 f32 = 64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fs_ref, state_ref, *,
            Q: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # (Q,)
    a = a_ref[0, 0]                                    # scalar (negative)
    Bc = b_ref[0, :, 0].astype(jnp.float32)           # (Q, N)
    Cc = c_ref[0, :, 0].astype(jnp.float32)           # (Q, N)

    dA = dt * a                                        # (Q,)
    cs = jnp.cumsum(dA)                                # (Q,) inclusive
    diff = cs[:, None] - cs[None, :]                   # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    Lmat = jnp.where(qi >= ki, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    xdt = x * dt[:, None]                              # (Q, P)
    y = jax.lax.dot_general(scores * Lmat, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    state = state_ref[...]                             # (P, N)
    y += jax.lax.dot_general(Cc, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cs)[:, None]
    total = cs[Q - 1]
    w = jnp.exp(total - cs)                            # (Q,)
    state_ref[...] = state * jnp.exp(total) + jax.lax.dot_general(
        xdt, Bc * w[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (P, N)
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _done():
        fs_ref[0, 0] = state_ref[...].astype(fs_ref.dtype)


def ssd_bshp(x, dt, A, Bm, Cm, *, chunk=128, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H) post-softplus; A: (H,) negative;
    Bm/Cm: (B,S,G,N). Returns (y (B,S,H,P) f32-accurate, final (B,H,P,N) f32)."""
    Bb, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    grid = (Bb, H, nc)
    a2 = A.reshape(H, 1).astype(jnp.float32)
    kernel = functools.partial(_kernel, Q=Q, nc=nc)
    y, fs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, Q, 1, N), lambda b, h, c: (b, c, h // rep, 0)),
            pl.BlockSpec((1, Q, 1, N), lambda b, h, c: (b, c, h // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, S, H, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, a2, Bm, Cm)
    return y, fs
