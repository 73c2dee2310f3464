"""Seeded random weights: the draws every architecture shares.

An architecture's `draw(dims, fmts, key)` (`bench/archs/<name>.py`) makes
every array on the device in one jitted call, in the types they are served
in, from the key `seed_key` gives. Each quantized matrix comes in both
served formats,

  q8: int8 codes (d_in, d_out), one f32 scale per output column;
  q4: 4-bit codes packed two per uint8 along d_in (row 2i in the low nibble,
      row 2i+1 in the high one), f32 scale and offset per group of 128 rows
      and output column.

The Q4 tree is drawn on its own, not quantized from the Q8 tree: each cell's
reference dequantizes the codes of the variant it serves. Scales are sized so
that every dequantized matrix has a standard deviation near 1/sqrt(d_in).

The result is a plain dict of arrays. The architecture's `program_params`
wraps one variant of it in the program's parameter tree; its reference reads
the plain dict and nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q4_GROUP = 128
# standard deviation of a code drawn uniformly from int8 (-128..127) and
# from the 16 levels of a nibble
_Q8_STD = 73.9
_Q4_STD = 4.61


def _scale(key, shape, std_code, d_in):
    u = jax.random.uniform(key, shape, jnp.float32, 0.75, 1.25)
    return u / (std_code * d_in ** 0.5)


def draw_q8(key, lead, d_in, d_out):
    """Q8 codes and scales of a (*lead, d_in, d_out) matrix."""
    kq, ks = jax.random.split(key)
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, (*lead, d_in, d_out), jnp.uint8), jnp.int8)
    return {"q": q, "s": _scale(ks, (*lead, 1, d_out), _Q8_STD, d_in)}


def draw_q4(key, lead, d_in, d_out):
    """Packed Q4 codes, scales and offsets of a (*lead, d_in, d_out) matrix."""
    kq, ks, kz = jax.random.split(key, 3)
    q = jax.random.bits(kq, (*lead, d_in // 2, d_out), jnp.uint8)
    s = _scale(ks, (*lead, d_in // Q4_GROUP, d_out), _Q4_STD, d_in)
    # offset centres the codes (mean 7.5) near 0, give or take a tenth
    z = -s * (7.5 + jax.random.uniform(kz, s.shape, jnp.float32, -0.75, 0.75))
    return {"q": q, "s": s, "z": z}


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, wider than 32 bits too,
    for XLA's RngBitGenerator ("rbg"): the same seed gives the same weights
    on a given backend. Its draw time on the chip is in PERF.md."""
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def drop_format(w: dict, fmt: str) -> dict:
    """`w` without format `fmt` (frees it once no other reference holds it)."""
    return {k: ({f: t for f, t in v.items() if f != fmt}
                if isinstance(v, dict) else v) for k, v in w.items()}
