"""Jit'd wrapper for the SSD kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd.ssd import ssd_bshp


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, Bm, Cm, *, chunk=128, interpret):
    y, fs = ssd_bshp(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
    return y.astype(x.dtype), fs
