"""Jit'd wrapper: QTensor-aware entry point with shape padding/flattening."""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.kernels.quant_matmul.quant_matmul import q8_matmul, q4_matmul
from repro.quant.qtensor import QTensor

# Q8 kernel lowerings by route, counted at trace time: "stacked" when the
# kernel was handed its layer's stack and index and reads the weight in place;
# "sliced" when it was handed a lone 2-D matrix, which inside a layer scan is
# a slice XLA copies out of the stack before the call
Q8_ROUTES: collections.Counter = collections.Counter()


def _pad_rows(x2d, multiple):
    M = x2d.shape[0]
    pad = (-M) % multiple
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, M


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul(x, w: QTensor, *, interpret: bool):
    """x: (..., K) @ QTensor (K, N) -> (..., N). Leading dims are flattened;
    rows padded to the sublane multiple the kernel tiles with. A Q8 `w` that
    carries a `layer` index is layer `w.layer` of its (L, K, N) stack."""
    *lead, K = x.shape
    x2d = x.reshape(-1, K)
    bm = 128 if x2d.shape[0] >= 128 else 8
    x2d, M = _pad_rows(x2d, bm)
    if w.fmt == "q8":
        if w.layer is None:
            Q8_ROUTES["sliced"] += 1
            q, scale, layer = w.q[None], w.scale[None], 0
        else:
            Q8_ROUTES["stacked"] += 1
            q, scale, layer = w.q, w.scale, w.layer
        out = q8_matmul(x2d, q, scale, layer, interpret=interpret)
    elif w.fmt == "q4":
        out = q4_matmul(x2d, w.q, w.scale, w.zero, group=w.group, bm=bm,
                        interpret=interpret)
    else:
        raise ValueError(w.fmt)
    return out[:M].reshape(*lead, out.shape[-1])
