"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Every tensor dimension is tagged with a *logical* axis name; a rules table maps
logical names to an ordered preference of mesh axes. Resolution is per-tensor:
a mesh axis is used only if (a) it exists in the mesh, (b) it is not already
used by another dimension of the same tensor, and (c) the dimension size is
divisible by the accumulated shard count. This lets odd architectures (e.g.
gemma2's 8 q-heads on a 16-way `model` axis) compile without GSPMD padding —
the axis is simply dropped for that tensor and the next preference is tried.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical axis -> ordered mesh-axis preferences
DEFAULT_RULES: Dict[Optional[str], Tuple[str, ...]] = {
    # activations — Megatron 1-D TP layout: the residual stream (act_embed) is
    # REPLICATED over `model`; only head/mlp/vocab-parallel intermediates are
    # sharded. Contractions then never hit a model-sharded dim except in
    # row-parallel output projections, whose single (B,S,d) all-reduce per
    # block is the expected TP collective. (act_embed -> ("model",) was
    # measured in the dry-run to inject partial-sum all-reduces after every
    # matmul — 13 GB on the vocab chunk alone; see EXPERIMENTS.md §Perf.)
    "act_batch": ("pod", "data"),
    # Megatron-SP: the BETWEEN-block residual stream shards its sequence dim
    # over `model` — remat-saved layer inputs divide by TP (95-layer deepseek:
    # 102 GB -> 6.4 GB/device) and the per-block all-reduce becomes an
    # equal-byte all-gather + reduce-scatter pair. Decode (S=1) and whisper
    # frames (1500 % 16 != 0) drop the axis automatically via divisibility.
    "act_seq": ("model",),
    "act_xent_seq": ("model",),       # sequence-parallel loss: the LM-head/xent
                                      # tokens shard over `model` (otherwise the
                                      # per-device logits chunk is O(B_loc*S*V_c))
    "act_embed": (),
    "act_heads": ("model",),
    "act_mlp": ("model",),
    "act_vocab": ("model",),
    "act_experts": ("model",),
    # decode-time KV cache: batch over data, sequence over model (flash-decode
    # layout); for batch=1 long-context the batch dim drops `data` and the
    # sequence dim picks up both axes.
    "cache_batch": ("data",),
    "cache_seq": ("data", "model"),
    "cache_heads": (),
    # weights: FSDP over `data` x TP over `model` (2-D sharding)
    "embed": ("data",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_mlp": (),                 # per-expert hidden stays local to its expert shard
    "conv": (),
    "state": (),
    "layers": (),                     # stacked-scan layer dim: replicated
    None: (),
}


# Pure-DP profile: the `model` axis becomes extra batch parallelism and
# weights replicate across it (FSDP over `data` only). The right layout for
# small archs where TP=16 comm dwarfs per-device compute — mamba2-370m train
# measured 3.1 s collective vs 0.07 s compute under TP (§Perf bonus cell).
# Requires weights (+opt state) to fit: ~<2B params for train on 16 GB chips.
DP_RULES = dict(DEFAULT_RULES)
DP_RULES.update({
    "act_batch": ("pod", "data", "model"),
    "act_seq": (), "act_xent_seq": (), "act_heads": (), "act_mlp": (),
    "act_vocab": (), "act_experts": (),
    "mlp": (), "heads": (), "kv_heads": (), "vocab": (), "experts": (),
    "cache_batch": ("data", "model"), "cache_seq": (),
})

_ACTIVE_RULES: list = []


class activate_rules:
    """Context manager selecting the sharding-rules profile (default: the
    FSDPxTP DEFAULT_RULES). Lets launch code choose per-arch layouts without
    touching model code."""

    def __init__(self, rules: Dict):
        self.rules = rules

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()
        return False


def current_rules() -> Dict:
    return _ACTIVE_RULES[-1] if _ACTIVE_RULES else DEFAULT_RULES


# Serving layout: weights stay RESIDENT in their tensor-parallel form
# (replicated over `data`/`pod`, sharded over `model`). FSDP re-gathering
# 45 MB/layer/step was measured at 17 GB per decode step on deepseek-67b;
# a serving pod gathers weights once at load time, never per token. This is
# also where the paper's Q8/Q4 variants bite: 72B-class bf16 weights \16 + a
# 32k cache brush against 16 GB/chip, the quantized variants clear it.
SERVING_RULES = dict(DEFAULT_RULES)
SERVING_RULES.update({"embed": ()})


def resolve_spec(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Optional[Dict] = None,
) -> PartitionSpec:
    rules = rules or current_rules()
    assert len(logical) == len(shape), (logical, shape)
    used = set()
    entries = []
    for name, dim in zip(logical, shape):
        prefs = rules.get(name, ())
        chosen = []
        shards = 1
        for ax in prefs:
            if ax not in mesh.shape or ax in used:
                continue
            ax_size = mesh.shape[ax]
            if dim % (shards * ax_size) != 0:
                continue
            chosen.append(ax)
            used.add(ax)
            shards *= ax_size
        entries.append(tuple(chosen) if len(chosen) > 1 else (chosen[0] if chosen else None))
    return PartitionSpec(*entries)


def logical_sharding(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Optional[Dict] = None,
) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical, shape, mesh, rules))


def tree_shardings(logical_tree, shape_tree, mesh: Mesh, rules: Optional[Dict] = None):
    """Map matching trees of logical-axis tuples and shapes to NamedShardings."""
    return jax.tree.map(
        lambda lg, shp: logical_sharding(lg, shp, mesh, rules),
        logical_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


_ACTIVE_MESH: list = []


class activate_mesh:
    """Context manager marking the mesh used by `constrain` inside jitted fns.

    Launch code wraps lowering/execution in `with activate_mesh(mesh):` so model
    code can place logical-axis sharding constraints without threading the mesh
    through every call. Outside a context, `constrain` is a no-op (smoke tests
    and single-device benches see unconstrained programs).
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH.pop()
        return False


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1] if _ACTIVE_MESH else None


def batch_parallel(fn, batched, replicated=()):
    """Call a Pallas kernel wrapper as ``fn(*batched, *replicated)``, once per
    batch shard of the active mesh.

    The compiler cannot partition a Mosaic kernel, so under a data-parallel
    mesh each device runs the kernel on its own rows — the leading dim of
    every `batched` operand — against whole copies of the `replicated` ones
    (weights). The result is batch-sharded the same way. With no mesh, or
    one whose batch axes all have size 1, it is a plain call."""
    mesh = current_mesh()
    axes = () if mesh is None else tuple(
        a for a in current_rules()["act_batch"]
        if a in mesh.shape and mesh.shape[a] > 1)
    if not axes:
        return fn(*batched, *replicated)
    bspec = PartitionSpec(axes if len(axes) > 1 else axes[0])
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(bspec,) * len(batched) + (PartitionSpec(),) * len(replicated),
        out_specs=bspec, check_vma=False)(*batched, *replicated)


def constrain(x, logical: Sequence[Optional[str]], rules: Optional[Dict] = None):
    """with_sharding_constraint by logical axes; no-op outside activate_mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(logical, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
