"""ParamDef: single-source-of-truth parameter specs.

Each model defines `param_spec(cfg) -> pytree of ParamDef`. From that one tree
we derive: RNG initialization (smoke tests / real training), abstract
ShapeDtypeStructs with shardings attached (the multi-pod dry-run lowers 67B+
parameter models without allocating a byte), logical-axis trees, byte/param
counts, and quantized-variant specs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import logical_sharding


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "fan_in"        # fan_in | normal | zeros | ones | embed | small
    dtype: str = "bf16"         # bf16 | fp32 | int8 | int4_packed(uint8 carrier)
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)

    @property
    def jnp_dtype(self):
        return {
            "bf16": jnp.bfloat16,
            "fp32": jnp.float32,
            "fp16": jnp.float16,
            "int8": jnp.int8,
            "uint8": jnp.uint8,
            "int32": jnp.int32,
        }[self.dtype]

    def num_params(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def nbytes(self) -> int:
        return self.num_params() * jnp.dtype(self.jnp_dtype).itemsize


def _is_def(x):
    return isinstance(x, ParamDef)


def _init_leaf(d: ParamDef, key, shape=None):
    """Draw leaf `d` — or, with `shape`, one piece of it: the init rule and
    fan-in are the whole leaf's, only the drawn block is smaller."""
    shape = d.shape if shape is None else tuple(shape)
    if d.init == "zeros":
        return jnp.zeros(shape, d.jnp_dtype)
    if d.init == "ones":
        return jnp.ones(shape, d.jnp_dtype)
    if d.init == "fan_in":
        # last-but-one dim is fan-in for (..., d_in, d_out) kernels
        fan_in = d.shape[-2] if len(d.shape) >= 2 else max(d.shape[-1], 1)
        std = d.scale / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(d.jnp_dtype)
    if d.init in ("normal", "embed", "small"):
        std = {"normal": 0.02, "embed": 1.0, "small": 1e-3}[d.init] * d.scale
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(d.jnp_dtype)
    raise ValueError(d.init)


def init_params(spec, key):
    """Materialize a ParamDef tree with RNG (used by smoke tests and training)."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(d, k) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(spec, mesh=None, rules=None):
    """ShapeDtypeStruct tree, with NamedShardings when a mesh is given.

    This is what the dry-run lowers against — no allocation ever happens.
    """
    def mk(d: ParamDef):
        sharding = logical_sharding(d.logical, d.shape, mesh, rules) \
            if mesh is not None else None
        return jax.ShapeDtypeStruct(d.shape, d.jnp_dtype, sharding=sharding)
    return jax.tree.map(mk, spec, is_leaf=_is_def)


def spec_logical_axes(spec):
    return jax.tree.map(lambda d: d.logical, spec, is_leaf=_is_def)


def param_shardings(spec, mesh):
    return jax.tree.map(
        lambda d: logical_sharding(d.logical, d.shape, mesh), spec, is_leaf=_is_def
    )


def count_params(spec) -> int:
    return sum(d.num_params() for d in jax.tree.leaves(spec, is_leaf=_is_def))


def param_bytes(spec) -> int:
    return sum(d.nbytes() for d in jax.tree.leaves(spec, is_leaf=_is_def))
