"""Weight-only quantization: Q8 (int8 per-channel) and Q4 (int4 group-wise).

This is the paper's "mixed-quality model" substrate made real:
  * q8  — symmetric int8, one fp scale per output channel (llama.cpp Q8_0-like).
  * q4  — asymmetric 4-bit, group size 128 along the contraction dim with fp16
          scale + min per group (Q4_K_M-like); two nibbles packed per uint8.

`dense()` is the single entry point model code uses for every linear layer —
it transparently handles bf16 arrays, QTensors (XLA dequant path), and the
fused Pallas dequant-matmul kernel (RuntimeConfig.use_pallas).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.param import ParamDef, _init_leaf
from repro.sharding.rules import batch_parallel

Q4_GROUP = 128


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    q: jax.Array            # int8 (q8) or uint8 nibble-packed (q4); (..., d_in', d_out)
    scale: jax.Array        # q8: (..., 1, d_out); q4: (..., d_in/g, d_out)
    zero: Optional[jax.Array]   # q4 only: group minimum, same shape as scale
    fmt: str = "q8"
    group: int = Q4_GROUP
    # set on a stack of layers (leading dim of q, scale, zero): the traced
    # index of the layer this tensor stands for (`layer_params`)
    layer: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.q, self.scale, self.zero, self.layer), (self.fmt, self.group)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale, zero, layer = children
        return cls(q=q, scale=scale, zero=zero, fmt=aux[0], group=aux[1],
                   layer=layer)

    @property
    def shape(self) -> Tuple[int, ...]:
        # logical (dequantized) shape
        s = list(self.q.shape)
        if self.fmt == "q4":
            s[-2] *= 2
        return tuple(s)

    def sliced(self) -> "QTensor":
        """The layer this tensor stands for, cut out of its stack."""
        if self.layer is None:
            return self
        q, scale, zero = jax.tree.map(lambda a: a[self.layer],
                                      (self.q, self.scale, self.zero))
        return dataclasses.replace(self, q=q, scale=scale, zero=zero,
                                   layer=None)

    def nbytes(self) -> int:
        n = self.q.size * jnp.dtype(self.q.dtype).itemsize
        n += self.scale.size * jnp.dtype(self.scale.dtype).itemsize
        if self.zero is not None:
            n += self.zero.size * jnp.dtype(self.zero.dtype).itemsize
        return n


def _is_qt(x):
    return isinstance(x, QTensor)


def quantize(w: jax.Array, fmt: str, group: int = Q4_GROUP) -> QTensor:
    """Quantize along the contraction (second-to-last) dimension."""
    wf = w.astype(jnp.float32)
    if fmt == "q8":
        amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
        return QTensor(q=q, scale=scale.astype(jnp.float32), zero=None, fmt="q8", group=0)
    if fmt == "q4":
        *lead, din, dout = wf.shape
        assert din % group == 0, (din, group)
        g = wf.reshape(*lead, din // group, group, dout)
        lo = g.min(axis=-2)                                  # (..., din/g, dout)
        hi = g.max(axis=-2)
        scale = jnp.maximum((hi - lo) / 15.0, 1e-8)
        q = jnp.clip(jnp.round((g - lo[..., None, :]) / scale[..., None, :]), 0, 15)
        q = q.astype(jnp.uint8).reshape(*lead, din, dout)
        packed = (q[..., 0::2, :] | (q[..., 1::2, :] << 4)).astype(jnp.uint8)
        return QTensor(q=packed, scale=scale.astype(jnp.float32),
                       zero=lo.astype(jnp.float32), fmt="q4", group=group)
    raise ValueError(fmt)


def unpack_q4(packed: jax.Array) -> jax.Array:
    """(..., d_in/2, d_out) uint8 -> (..., d_in, d_out) uint8 nibbles."""
    lo = packed & 0x0F
    hi = packed >> 4
    *lead, dhalf, dout = packed.shape
    out = jnp.stack([lo, hi], axis=-2)                       # (..., d/2, 2, dout)
    return out.reshape(*lead, dhalf * 2, dout)


def dequantize(t: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    if t.fmt == "q8":
        return (t.q.astype(jnp.float32) * t.scale).astype(dtype)
    if t.fmt == "q4":
        q = unpack_q4(t.q).astype(jnp.float32)
        *lead, din, dout = q.shape
        g = q.reshape(*lead, din // t.group, t.group, dout)
        w = g * t.scale[..., None, :] + t.zero[..., None, :]
        return w.reshape(*lead, din, dout).astype(dtype)
    raise ValueError(t.fmt)


def _q4_matmul_xla(x: jax.Array, t: QTensor):
    """q4 matmul in factored (K/2, 2, N) space — the naive unpack merges the
    packed dim back to K, and when K is tensor-parallel-sharded GSPMD cannot
    merge a sharded-major reshape and falls back to a full weight all-gather
    (measured: 36 GB/layer on qwen2-72b q4 decode). Splits and new-axis stacks
    are shard-preserving, so everything here stays local; scales expand in
    replicated space and reshard for free at the multiply."""
    *lead, K = x.shape
    assert t.q.ndim == 2
    x_r = x.reshape(*lead, K // 2, 2)
    lo = (t.q & 0x0F).astype(jnp.float32)
    hi = (t.q >> 4).astype(jnp.float32)
    w_r = jnp.stack([lo, hi], axis=1)                # (K/2, 2, N)
    half_g = t.group // 2
    scale_full = jnp.repeat(t.scale, half_g, axis=0)  # (K/2, N), replicated
    zero_full = jnp.repeat(t.zero, half_g, axis=0)
    w_r = (w_r * scale_full[:, None, :] + zero_full[:, None, :]).astype(x.dtype)
    nd = x_r.ndim
    return jax.lax.dot_general(
        x_r, w_r, (((nd - 2, nd - 1), (0, 1)), ((), ())),
        preferred_element_type=x.dtype)


def dense(x: jax.Array, w, rcfg=None, *, spec: Optional[str] = None):
    """x: (..., d_in) @ w: (..., d_in, d_out) with optional leading batch dims
    on w that broadcast/batch against x (used by stacked experts). A Q8
    QTensor that carries its layer index goes to the kernel as its whole
    stack, which the kernel reads in place; every other route cuts the layer
    out first."""
    if _is_qt(w):
        use_kernel = rcfg is not None and rcfg.use_pallas
        if not (use_kernel and w.fmt == "q8"):
            w = w.sliced()
        if use_kernel:
            if w.q.ndim - (w.layer is not None) != 2:
                raise NotImplementedError(
                    f"quant_matmul kernel takes one 2-D weight a layer; got "
                    f"{w.fmt} {w.shape} (batched experts have no kernel path)")
            from repro.kernels.quant_matmul import ops as qm_ops
            return batch_parallel(
                functools.partial(qm_ops.quant_matmul,
                                  interpret=rcfg.interpret), (x,), (w,))
        if w.fmt == "q4" and w.q.ndim == 2:
            return _q4_matmul_xla(x, w)
        w = dequantize(w, x.dtype)
    # output in x.dtype (bf16): the MXU accumulates f32 internally either way,
    # and f32 dot outputs double every TP all-reduce and activation transient
    # (measured 2x on the per-layer (B,S,d) collectives in the dry-run)
    if w.ndim == 2:
        return jax.lax.dot_general(
            x, w.astype(x.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=x.dtype)
    # batched experts: x (E, C, d) @ w (E, d, f)
    assert w.ndim == 3 and x.ndim == 3
    return jax.lax.dot_general(
        x, w.astype(x.dtype),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=x.dtype)


def layer_inputs(layers):
    """What a layer scan slices from the stacked layer tree: every leaf but
    the QTensors, which `layer_params` hands on whole (None in their place)."""
    return jax.tree.map(lambda a: None if _is_qt(a) else a, layers,
                        is_leaf=_is_qt)


def layer_params(layers, sliced, i):
    """Layer `i`'s params: `sliced`, the scan's slice of `layer_inputs(layers)`,
    with each QTensor of `layers` left stacked and carrying the index `i`."""
    return jax.tree.map(
        lambda a, s: dataclasses.replace(a, layer=i) if _is_qt(a) else s,
        layers, sliced, is_leaf=_is_qt)


def one_layer(w):
    """A QTensor stored as one matrix, as a stack of one at index 0, so the
    Q8 kernel reads it in place like a layer's weight; arrays pass through."""
    if not _is_qt(w) or w.layer is not None:
        return w
    q, scale, zero = jax.tree.map(lambda a: a[None], (w.q, w.scale, w.zero))
    return dataclasses.replace(w, q=q, scale=scale, zero=zero,
                               layer=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Tree-level transforms (spec-driven so abstract and concrete trees match)
# ---------------------------------------------------------------------------


def _eligible(d: ParamDef) -> bool:
    """Quantize big matmul weights; skip norms/biases/conv/SSM vectors and the
    embedding table (its lookup path needs the full-precision array)."""
    if len(d.shape) < 2 or min(d.shape[-2:]) < 32:
        return False
    if d.logical[-2] == "vocab":           # (vocab, embed) lookup table
        return False
    if any(ax in ("conv", "state") for ax in d.logical if ax):
        return False
    if d.init in ("zeros", "ones"):        # biases, norm scales
        return False
    return True


def _qdef(d: ParamDef, fmt: str, group: int):
    *lead, din, dout = d.shape
    lead_log = d.logical[:-2]
    if fmt == "q4" and din % group == 0:
        return QTensor(
            q=ParamDef((*lead, din // 2, dout), d.logical, dtype="uint8", init="zeros"),
            scale=ParamDef((*lead, din // group, dout),
                           (*lead_log, None, d.logical[-1]), dtype="fp32", init="ones"),
            zero=ParamDef((*lead, din // group, dout),
                          (*lead_log, None, d.logical[-1]), dtype="fp32", init="zeros"),
            fmt="q4", group=group)
    # q8 (also the q4 fallback when the contraction dim is not group-divisible)
    return QTensor(
        q=ParamDef((*lead, din, dout), d.logical, dtype="int8", init="zeros"),
        scale=ParamDef((*lead, 1, dout), (*lead_log, None, d.logical[-1]),
                       dtype="fp32", init="ones"),
        zero=None, fmt="q8", group=0)


def quant_spec(spec, fmt: str, group: int = Q4_GROUP):
    """ParamDef tree -> tree with QTensor nodes holding ParamDef children.

    Feeding this through `abstract_params` yields a quantized serving model as
    ShapeDtypeStructs — the dry-run lowers 70B-class Q8/Q4 models without
    allocating anything.
    """
    if fmt in ("bf16", "none"):
        return spec
    return jax.tree.map(
        lambda d: _qdef(d, fmt, group) if _eligible(d) else d,
        spec, is_leaf=lambda x: isinstance(x, ParamDef))


def quantize_tree(params, spec, fmt: str, group: int = Q4_GROUP):
    """Quantize concrete params guided by the spec (same structure decisions
    as quant_spec, so abstract and concrete serving trees always agree)."""
    if fmt in ("bf16", "none"):
        return params
    qspec = quant_spec(spec, fmt, group)

    def go(node, p):
        if isinstance(node, QTensor):
            return quantize(p, node.fmt, node.group or group)
        return p

    return jax.tree.map(
        go, qspec, params,
        is_leaf=lambda x: isinstance(x, (QTensor, ParamDef)))


# ---------------------------------------------------------------------------
# Building variants without the bf16 tree
# ---------------------------------------------------------------------------

# largest piece a leaf is drawn in: 128 MiB in bf16, twice that while it is
# quantized in float32 — the builder's transient, whatever the model's size
MAX_PIECE_ELEMS = 1 << 26


def _pieces(d: ParamDef):
    """Piece shape and start offsets leaf `d` is drawn in: a stacked leaf
    (leading "layers" dim) one layer slice at a time, and the last (output)
    dim split into the fewest equal parts that keep a piece within
    MAX_PIECE_ELEMS. Quantization runs along the contraction dim per output
    column, so a piece quantizes exactly as its span of the whole leaf."""
    shape = list(d.shape)
    stacked = bool(shape) and d.logical[0] == "layers"
    piece = [1] + shape[1:] if stacked else list(shape)
    parts = 1
    if len(shape) > int(stacked):
        n = shape[-1]
        rest = math.prod(piece[:-1])
        parts = next((p for p in range(1, n + 1)
                      if n % p == 0 and rest * (n // p) <= MAX_PIECE_ELEMS),
                     n)
        piece[-1] = n // parts
    starts = []
    for layer in (range(shape[0]) if stacked else [0]):
        for c in range(parts):
            st = [0] * len(shape)
            if stacked:
                st[0] = layer
            if len(shape) > int(stacked):
                st[-1] = c * piece[-1]
            starts.append(tuple(st))
    return tuple(piece), starts


_draw = jax.jit(_init_leaf, static_argnums=(0, 2))


@functools.partial(jax.jit, donate_argnums=(0,))
def _write(bufs, vals, start):
    """Write each piece into its (donated, so updated in place) buffer."""
    return [jax.lax.dynamic_update_slice(b, v.astype(b.dtype), start)
            for b, v in zip(bufs, vals)]


def build_variants(spec, key, fmts=("q8", "q4"), *, sharding=None):
    """Seeded weights for `spec`, quantized to each format in `fmts`, built
    piece by piece: no whole bf16 tree ever exists, so the peak transient is
    one piece (see `_pieces`) rather than the model (15 GB in bf16 for a
    7B-class model, which does not fit beside its variants on a 16 GB chip).

    Returns {fmt: tree} shaped like `quant_spec(spec, fmt)`. "bf16" is the
    unquantized tree the pieces were drawn from: it exists to verify the
    quantized trees against `quantize_tree`, and since it is the whole bf16
    tree it only fits at reduced widths. Leaves no format quantizes
    (norms, biases, the embedding table) are one array shared by every
    variant. `sharding` is a replicated placement (e.g. over a data mesh)
    every piece is drawn and stored with; None means the default device."""
    leaves, treedef = jax.tree.flatten(spec,
                                       is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    qnodes = [treedef.flatten_up_to(quant_spec(spec, f)) for f in fmts]
    out = {f: [] for f in fmts}
    for i, d in enumerate(leaves):
        nodes = [qn[i] for qn in qnodes]
        raw = any(not _is_qt(n) for n in nodes)
        quantized = [n for n in nodes if _is_qt(n)]
        qfmts = tuple((n.fmt, n.group) for n in quantized)
        defs = ([d] if raw else []) + [c for n in quantized for c in
                                        (n.q, n.scale, n.zero) if c is not None]
        bufs = [jnp.zeros(c.shape, c.jnp_dtype, device=sharding) for c in defs]
        piece, starts = _pieces(d)
        for j, start in enumerate(starts):
            kj = jax.random.fold_in(keys[i], j)
            if sharding is not None:    # draw where the buffers live
                kj = jax.device_put(kj, sharding)
            w = _draw(d, kj, piece)
            # quantized op by op, exactly as `quantize_tree` does it: fused
            # into one program, XLA may round a division differently
            vals = [w] if raw else []
            for fmt, g in qfmts:
                t = quantize(w, fmt, g)
                vals += [t.q, t.scale] + ([t.zero] if t.zero is not None
                                          else [])
            bufs = _write(bufs, vals, start)
        pos = int(raw)
        for f, n in zip(fmts, nodes):
            if not _is_qt(n):
                out[f].append(bufs[0])
                continue
            k = 3 if n.zero is not None else 2
            q, scale, *zero = bufs[pos:pos + k]
            pos += k
            out[f].append(QTensor(q=q, scale=scale, zero=zero[0] if zero else None,
                                  fmt=n.fmt, group=n.group))
    return {f: jax.tree.unflatten(treedef, v) for f, v in out.items()}
