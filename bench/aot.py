#!/usr/bin/env python3
"""Compile a configuration's programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py <config> [--variant q8|q4]

Compiles, at the configuration's published widths, what a run puts on the
chip: the weight draw, and for the variant the engine's cold prefill at
each prompt bucket, its suffix prefill over a cached prefix, its paged
decode step, and the reference's layer and head. Prints each program's
`memory_analysis()`. A compile that passes here is not a chip run; it shows
what the chip's compiler accepts and what each program needs beside its
arguments.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--variant", default="q8", choices=("q8", "q4"))
    ap.add_argument("--buckets", default="",
                    help="comma-separated prompt buckets (default: all)")
    args = ap.parse_args()

    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, weights
    from repro.config import RuntimeConfig
    from repro.models import get_model
    from repro.serving.engine import _EngineExec
    from repro.sharding.param import init_params

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = json.loads((harness.BENCH / "configs" / f"{args.config}.json")
                     .read_text())
    arch = harness.load_arch(cfg)
    dims = arch.dims_of(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def report(name, lowered):
        c = lowered.compile()
        m = c.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes} B, outputs "
              f"{m.output_size_in_bytes} B, temporaries "
              f"{m.temp_size_in_bytes} B", flush=True)
        return c

    fmts = tuple(cfg["variants"])
    key = on_chip(jax.eval_shape(lambda: weights.seed_key(0)))

    def draw(k):
        return arch.draw(dims, fmts, k)

    report("weights.draw", jax.jit(draw).lower(key))
    w = on_chip(jax.eval_shape(draw, key))

    model = get_model(arch.model_config(cfg, dims))
    params = arch.program_params(w, args.variant, model.param_spec())
    rcfg = RuntimeConfig(use_pallas=True, interpret=False)
    eng = cfg["engine"]
    bs, B, max_seq = eng["block_size"], eng["max_batch"], eng["max_seq"]
    nb = -(-max_seq // bs)
    num_blocks = (B + 1) * nb + B + 2
    ex = _EngineExec(model, rcfg, max_seq, block_size=bs)
    pool = on_chip(jax.eval_shape(lambda: init_params(
        model.paged_cache_spec(rcfg, num_blocks, bs), jax.random.PRNGKey(0))))
    i32 = jnp.int32
    buckets = ([int(b) for b in args.buckets.split(",")] if args.buckets
               else eng["prompt_buckets"])
    for b in buckets:
        toks = jax.ShapeDtypeStruct((B, b), i32, sharding=chip)
        report(f"prefill cold (8, {b})",
               jax.jit(ex.prefill_impl).lower(params, {"tokens": toks}))
    for s_suf in (256, 512):
        batch = {"tokens": jax.ShapeDtypeStruct((B, s_suf), i32, sharding=chip),
                 "positions": jax.ShapeDtypeStruct((s_suf,), i32, sharding=chip)}
        report(f"prefill suffix (8, {s_suf}) over 32 cached blocks",
               jax.jit(ex.prefill_prefix_impl).lower(
                   params, pool, batch,
                   jax.ShapeDtypeStruct((B, 32), i32, sharding=chip),
                   jax.ShapeDtypeStruct((B,), i32, sharding=chip)))
    report("paged decode (8, 1)", jax.jit(ex.decode_paged_impl,
                                          donate_argnums=(1,)).lower(
        params, pool, jax.ShapeDtypeStruct((B, 1), i32, sharding=chip),
        jax.ShapeDtypeStruct((B,), i32, sharding=chip),
        jax.ShapeDtypeStruct((B, nb), i32, sharding=chip)))
    for name, lowered in arch.aot_reference(w, args.variant, dims, chip):
        report(name, lowered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
