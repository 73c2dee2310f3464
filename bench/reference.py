"""What every plain reference shares: float32 arithmetic on the served codes.

An architecture's reference (`logits_at` in `bench/archs/<name>.py`) is built
from these pieces. It imports nothing of the program and reads only the
benchmark's plain weight dict. Every matrix is dequantized to float32 from
the served codes (`dequant`) and every product runs at `Precision.HIGHEST`
(`mm`). `rms` is RMSNorm with the weight stored as its offset from 1, as the
benchmark's weights hold it; `rope` rotates the two halves of each head. The
final norm and LM head run a slice of the vocabulary at a time
(`head_logits`), so that only one slice is ever dequantized to float32.

`act="int8"` (or `"fp8"`) is the control: every matrix product's input
rounded per token to int8 (symmetric, scaled by the row's absolute maximum)
or to float8 e4m3 (scaled so the row's absolute maximum is 448), a step
below the bf16 activations the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Q4_GROUP

HI = jax.lax.Precision.HIGHEST


def dequant(t: dict, fmt: str, layer=None) -> jax.Array:
    """Float32 matrix (d_in, d_out) from one served format's codes."""
    def pick(a):
        return a if layer is None else a[layer]
    if fmt == "q8":
        return pick(t["q"]).astype(jnp.float32) * pick(t["s"])
    packed = pick(t["q"])
    lo = (packed & 0x0F).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    half, d_out = packed.shape
    codes = jnp.stack([lo, hi], axis=1).reshape(half * 2, d_out)
    g = codes.reshape(-1, Q4_GROUP, d_out)
    w = g * pick(t["s"])[:, None, :] + pick(t["z"])[:, None, :]
    return w.reshape(half * 2, d_out)


def _act_round(x, act):
    if act is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}[act]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / top
    if act == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, act):
    return jnp.dot(_act_round(x, act), w, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def rope(x, pos, theta):
    H = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, H, 2, dtype=jnp.float32) / H)
    ang = pos[:, None] * inv                              # (S, H/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :H // 2], x[..., H // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def head_slice(eps, fmt, act, cols, x_sel, final_norm, lm_head, start):
    """Logits of vocabulary columns [start, start + cols)."""
    h = rms(x_sel, final_norm, eps)
    part = {k: jax.lax.dynamic_slice_in_dim(a, start, cols, axis=-1)
            for k, a in lm_head.items()}
    return mm(h, dequant(part, fmt), act)


def head_cols(V: int, max_cols: int = 40000) -> int:
    """The widest slice of a V-column head, at most `max_cols`, that
    divides it evenly."""
    parts = next(p for p in range(1, V + 1) if V % p == 0 and V // p <= max_cols)
    return V // parts


def head_logits(eps, fmt, act, x_sel, final_norm, lm_head) -> np.ndarray:
    """Final norm and LM head (`lm_head`: one format's codes, (d, V)) a
    slice of the vocabulary at a time."""
    V = lm_head["q"].shape[-1]
    cols = head_cols(V)
    return np.concatenate([np.asarray(head_slice(eps, fmt, act, cols, x_sel,
                                                 final_norm, lm_head, i * cols))
                           for i in range(V // cols)], axis=-1)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best."""
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - picked
