#!/usr/bin/env python3
"""Bring-up smoke run: the CarbonCall serving path at published widths on a TPU.

    python chip_smoke.py              # one chip: the paged engine, Q8 -> Q4
    python chip_smoke.py --chips 4    # the data-parallel engine on 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

One chip: the paper's Qwen2-7B (`carboncall-qwen2-7b`: 28 layers, d 3584,
28/4 GQA, head_dim 128, vocab 152064) with random weights drawn from a seed.
Its Q8 and Q4 variants are built piece by piece (`quant.build_variants`) and
both stay resident. A `ServingEngine` with the default paged KV layout
serves temperature-0 requests whose 128-token prompts share a 96-token tool
prefix, through `EngineClient.submit`/`settle`: a full batch of 8, a second
wave that hits the cached prefix, one live `swap_params` Q8 -> Q4 (the
CarbonCall variant switch), and a wave on Q4. Correctness, for every request:
the engine's own prefill logits (kept with its prefix-cache entries) and the
rows of its first batched paged decode steps are compared with the same model
on the XLA reference path (`use_pallas=False`) on the same chip, fed the
engine's tokens. Planted faults (a misread quant scale, a dropped KV head, a
masked KV block) must read above the tolerance, or the check fails too.
Every Q8 kernel the engine lowered must read its layer's weights in place
from the stacked arrays (`[routes]`: 0 sliced).

`--chips 4`: only the sharded path — a dense-layout engine over a 4-device
data mesh at published widths (Q8), against the same requests on a
one-device engine over the same weights. Token streams must match, and the
engine's params and cache must sit on all four devices.

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`;
every failed check exits non-zero before it. Without a TPU the script exits
non-zero and prints no result. `--rehearse` runs the same phases at reduced
widths with interpret-mode kernels, for a CPU; it never reports ok.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "carboncall-qwen2-7b"
SEED = 0
NEW_TOKENS = 16
PROMPT_LEN = 128            # one prompt bucket: rows carry no padding
TOOL_PREFIX = 96            # shared tool-schema prefix: six 16-token blocks
PROBE_STEPS = 4             # teacher-forced decode steps in the logit check
# Logit check: max |kernel - reference| over the vocab, relative to the
# largest reference logit, at every checked position. Both paths run bf16
# activations through 28 layers and dequantize in different orders (the
# kernels scale the f32 accumulator, the reference rounds scaled weights to
# bf16 first), so they agree to bf16 noise, not bit for bit.
LOGIT_TOL = 0.05


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


class Phases:
    """Wall seconds per phase; each phase ends in `block_until_ready` on
    what it produced, so the time covers the device work, not the enqueue."""

    def __init__(self):
        self.t0 = 0.0

    def start(self):
        self.t0 = time.perf_counter()

    def end(self, name: str, *outputs):
        import jax
        jax.block_until_ready(outputs)
        log(f"[phase] {name}: {time.perf_counter() - self.t0:.3f} s")


class CompileTimer:
    """Backend compile seconds and count, from JAX's monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.event:
            self.secs += duration
            self.count += 1


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def make_prompts(n: int, vocab: int, seed: int):
    """`n` prompts of PROMPT_LEN tokens: a shared TOOL_PREFIX, then each
    request's own query tokens."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prefix = rng.integers(2, vocab, TOOL_PREFIX).tolist()
    return [prefix + rng.integers(2, vocab, PROMPT_LEN - TOOL_PREFIX).tolist()
            for _ in range(n)]


def serve(client, prompts):
    """Submit temperature-0 requests and settle them; every one must emit
    all NEW_TOKENS tokens."""
    from repro.serving import SessionRequest
    handles = [client.submit(SessionRequest(prompt=p, max_new_tokens=NEW_TOKENS,
                                            eos_id=-1, temperature=0.0))
               for p in prompts]
    client.settle(handles)
    outs = [list(h.request.output) for h in handles]
    check(all(len(o) == NEW_TOKENS for o in outs),
          f"tokens emitted {[len(o) for o in outs]}, asked {NEW_TOKENS} each")
    return outs


class Reference:
    """The model on a given runtime path at batch 1: prefill a prompt into a
    private block pool, then feed teacher-forced tokens one paged decode step
    at a time. Compiled once per weight-tree structure."""

    def __init__(self, model, rcfg, *, max_seq, block_size):
        import jax
        from repro.sharding.param import init_params
        self.nb = nb = -(-max_seq // block_size)
        self.block_size = block_size
        self.cache0 = init_params(model.cache_spec(rcfg, 1, max_seq),
                                  jax.random.PRNGKey(0))
        # blocks 1..nb hold the row; block 0 is never written, so it reads
        # as zeros (the masked-block control points a table entry at it)
        self.pool0 = init_params(model.paged_cache_spec(rcfg, nb + 1, block_size),
                                 jax.random.PRNGKey(0))

        def prefill(p, cache, pool, toks):
            logits, cache, _ = model.prefill(p, cache, {"tokens": toks}, rcfg)
            pool = {k: pool[k].at[:, 1:].set(v[:, 0].reshape(
                        v.shape[0], nb, block_size, *v.shape[3:]))
                    for k, v in cache.items()}
            return logits, pool

        self.prefill = jax.jit(prefill)
        self.decode = jax.jit(functools.partial(
            model.decode_step_paged, rcfg=rcfg, seq_cap=max_seq))

    def logits(self, params, prompt, forced, *, fault=None):
        """(1 + len(forced), V) f32: the prefill's last position, then one
        row per forced token. `fault` plants one error (see FAULTS)."""
        import jax.numpy as jnp
        import numpy as np
        logits, pool = self.prefill(params, self.cache0, self.pool0,
                                    jnp.asarray([prompt], jnp.int32))
        tables = np.arange(1, self.nb + 1, dtype=np.int32)[None]
        if fault == "dropped kv head":
            pool = {k: v.at[..., -1, :].set(0) for k, v in pool.items()}
        elif fault == "masked last prompt block":
            tables[0, (len(prompt) - 1) // self.block_size] = 0
        rows = [logits[0]]
        for t, tok in enumerate(forced):
            logits, pool = self.decode(params, pool, jnp.asarray([[tok]], jnp.int32),
                                       jnp.asarray([len(prompt) + t], jnp.int32),
                                       jnp.asarray(tables))
            rows.append(logits[0])
        return np.asarray(jnp.stack(rows), np.float32)


# Planted faults, each the signature of a kernel bug, that the logit check
# must read above LOGIT_TOL (so it can tell a fault from bf16 drift).
FAULTS = ("quant scale of the next group or column", "dropped kv head",
          "masked last prompt block")


def misread_scales(params):
    """Every quantized weight dequantized with its neighbour's scale: the
    next group's (Q4) or the next column's (Q8, and Q4 with one group)."""
    import jax
    import jax.numpy as jnp
    from repro.quant import QTensor

    def shift(t):
        if not isinstance(t, QTensor):
            return t
        axis = -2 if t.scale.shape[-2] > 1 else -1
        return dataclasses.replace(t, scale=jnp.roll(t.scale, 1, axis=axis))
    return jax.tree.map(shift, params, is_leaf=lambda x: isinstance(x, QTensor))


def record_decode_logits(engine, steps: int):
    """Keep the logits the engine's own decode program produces: for each
    request (keyed by prompt), the rows of its first `steps` decode steps,
    taken from the batched step it ran in. Also counts live slots."""
    import numpy as np
    rows, live = {}, []
    # the engine fetches its jitted decode step through `_decode_fn` before
    # every step; wrapping it sees each step's logits and slots unchanged
    build = engine._decode_fn

    def decode_fn(variant=None):
        fn = build(variant)

        def step(*args):
            logits, state = fn(*args)
            reqs = [(i, r) for i, r in enumerate(engine.slots) if r is not None]
            live.append(len(reqs))
            want = [(i, r) for i, r in reqs
                    if len(rows.get(tuple(r.prompt), ())) < steps]
            if want:
                host = np.asarray(logits, np.float32)
                for i, r in want:
                    rows.setdefault(tuple(r.prompt), []).append(host[i])
            return logits, state
        return step

    engine._decode_fn = decode_fn
    return rows, live


def check_q8_routes():
    """Every Q8 kernel lowering so far read its weight in place from the
    layer stack: none was handed a slice for XLA to copy."""
    from repro.kernels.quant_matmul.ops import Q8_ROUTES
    log(f"[routes] q8 kernel lowerings: stacked {Q8_ROUTES['stacked']}, "
        f"sliced {Q8_ROUTES['sliced']}")
    check(Q8_ROUTES["stacked"] > 0 and Q8_ROUTES["sliced"] == 0,
          "a Q8 kernel was handed a sliced layer, or none ran")


def rel_err(got, want) -> float:
    import numpy as np
    scale = np.abs(want).max(axis=-1)
    return float((np.abs(got - want).max(axis=-1) / scale).max())


def one_chip(cfg, rcfg, phases):
    import jax
    import numpy as np
    from repro.models import get_model
    from repro.quant import build_variants
    from repro.serving import EngineConfig, ServingEngine

    model = get_model(cfg)
    phases.start()
    variants = build_variants(model.param_spec(), jax.random.PRNGKey(SEED))
    phases.end("build Q8+Q4 variants", variants)
    for name, tree in variants.items():
        log(f"[weights] {name}: {tree_bytes(tree)} bytes")

    econfig = EngineConfig(max_batch=8, max_seq=256, prompt_buckets=(PROMPT_LEN,),
                           kv_layout="paged")
    phases.start()
    engine = ServingEngine(cfg, variants["q8"], rcfg, config=econfig)
    engine.variant_name = "q8"
    client = engine.client()
    phases.end("engine construction", engine.pool)
    log(f"[engine] kv_layout={engine.kv_layout} blocks={engine.block_pool.num_blocks}"
        f" block_size={engine.block_size} max_batch={engine.max_batch}"
        f" max_seq={engine.max_seq}")
    engine_rows, live = record_decode_logits(engine, PROBE_STEPS)

    # a full batch cold, a prefix-hit wave, then a wave on Q4 after the swap
    prompts = make_prompts(15, cfg.vocab_size, SEED)
    waves = (("q8", prompts[:8]), ("q8", prompts[8:11]), ("q4", prompts[11:]))
    outputs = {}
    asked = emitted = 0
    for i, (variant, wave) in enumerate(waves):
        if variant != engine.variant_name:
            phases.start()
            engine.swap_params(variants[variant], variant)
            phases.end(f"swap_params -> {variant}", engine.params)
        hits0 = engine.prefix_cache.hits
        del live[:]
        phases.start()
        outs = serve(client, wave)
        phases.end(f"wave {i} ({variant}, {len(wave)} requests)", engine.pool)
        outputs.update({tuple(p): (variant, o) for p, o in zip(wave, outs)})
        asked += NEW_TOKENS * len(wave)
        emitted += sum(len(o) for o in outs)
        log(f"[serve] wave {i} {variant}: prefix-cache hits "
            f"{engine.prefix_cache.hits - hits0}/{len(wave)}, "
            f"most live slots in a decode step {max(live)}")
        check(max(live) == len(wave), f"wave {i} never decoded all its "
              f"{len(wave)} requests in one step")
    log(f"[serve] tokens emitted {emitted} / asked {asked}; "
        f"engine.tokens_emitted={engine.tokens_emitted}")
    log(f"[serve] swap_count={engine.swap_count} "
        f"kernel_fallbacks={engine.kernel_fallbacks}")
    check(emitted == asked, "not every requested token was emitted")
    check(engine.swap_count >= 1, "no live variant swap happened")
    check(engine.kernel_fallbacks == 0,
          f"{engine.kernel_fallbacks} decode steps fell back to the reference")
    check_q8_routes()
    check(engine.prefix_cache.hits >= len(waves[1][1]),
          "the second wave did not hit the cached tool prefix")

    # logit check, every served request: the engine's prefill logits (kept
    # with its prefix-cache entry) and its first PROBE_STEPS batched decode
    # steps' rows, against the XLA reference fed the engine's own tokens
    ref = Reference(model, dataclasses.replace(rcfg, use_pallas=False, interpret=False),
                    max_seq=econfig.max_seq, block_size=econfig.block_size)
    worst = 0.0
    clean = {}
    phases.start()
    for row, prompt in enumerate(prompts):
        variant, out = outputs[tuple(prompt)]
        entry = engine.prefix_cache.lookup(prompt, salt=variant)
        check(entry is not None and entry.last_logits is not None,
              f"the engine kept no prefill logits for request {row}")
        got = engine_rows.get(tuple(prompt), [])
        check(len(got) == PROBE_STEPS,
              f"request {row}: {len(got)} decode rows recorded, want {PROBE_STEPS}")
        want = ref.logits(variants[variant], prompt, out[:PROBE_STEPS])
        e_prefill = rel_err(entry.last_logits, want[0])
        e_decode = rel_err(np.stack(got), want[1:])
        log(f"[logits] request {row} {variant}: prefill {e_prefill:.3e}, "
            f"decode steps 1-{PROBE_STEPS} {e_decode:.3e}")
        worst = max(worst, e_prefill, e_decode)
        clean[row] = want
    phases.end(f"logit check ({len(prompts)} requests)")
    log(f"[logits] max relative logit error {worst:.3e} (tolerance {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"logit error {worst:.3e} above {LOGIT_TOL}")

    # controls: the same check must fail on each planted fault, on a Q8 and
    # a Q4 request
    phases.start()
    for row in (0, len(prompts) - 1):
        variant, out = outputs[tuple(prompts[row])]
        for fault in FAULTS:
            params = variants[variant]
            if fault == FAULTS[0]:
                params = misread_scales(params)
            bad = ref.logits(params, prompts[row], out[:PROBE_STEPS],
                             fault=None if fault == FAULTS[0] else fault)
            err = rel_err(bad, clean[row])
            del params, bad
            log(f"[control] request {row} {variant}, {fault}: {err:.3e}")
            check(err > LOGIT_TOL, f"the logit check cannot see a {fault} "
                  f"({err:.3e} <= {LOGIT_TOL})")
    phases.end("fault controls")


def four_chips(cfg, rcfg, phases):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_data_mesh
    from repro.models import get_model
    from repro.quant import build_variants
    from repro.serving import EngineConfig, ServingEngine

    mesh = make_data_mesh(4)
    devices = list(mesh.devices.flat)
    phases.start()
    params = build_variants(get_model(cfg).param_spec(), jax.random.PRNGKey(SEED),
                            ("q8",), sharding=NamedSharding(mesh, PartitionSpec()))["q8"]
    phases.end("build Q8 variant, replicated over the data mesh", params)
    # the one-device engine reads device 0's replica: no second copy
    dev0 = devices[0]
    params_one = jax.tree.map(
        lambda a: next(s.data for s in a.addressable_shards if s.device == dev0),
        params)

    econfig = EngineConfig(max_batch=8, max_seq=256, prompt_buckets=(PROMPT_LEN,),
                           kv_layout="dense")
    prompts = make_prompts(8, cfg.vocab_size, SEED)
    outs = {}
    for name, m, p in (("sharded", mesh, params), ("one-device", None, params_one)):
        phases.start()
        engine = ServingEngine(cfg, p, rcfg, config=econfig, mesh=m)
        engine.variant_name = "q8"
        outs[name] = serve(engine.client(), prompts)
        phases.end(f"{name} engine: {len(prompts)} requests", engine.cache)
        if m is None:
            continue
        log(f"[mesh] data_shards={engine.data_shards} kv_layout={engine.kv_layout}")
        for label, tree in (("params", engine.params), ("cache", engine.cache)):
            per_dev = {d: 0 for d in devices}
            for leaf in jax.tree.leaves(tree):
                check(set(leaf.sharding.device_set) == set(devices),
                      f"a {label} leaf is not on all four devices")
                for s in leaf.addressable_shards:
                    per_dev[s.device] += s.data.nbytes
            log(f"[mesh] {label} bytes per device: "
                + " ".join(f"{d.id}:{n}" for d, n in per_dev.items()))
            check(min(per_dev.values()) > 0, f"a device holds no {label}")
        for leaf in jax.tree.leaves(engine.cache):
            rows = {s.data.shape[1] for s in leaf.addressable_shards}
            check(rows == {econfig.max_batch // 4},
                  f"cache batch rows per device {rows}, expected "
                  f"{econfig.max_batch // 4}")
    check_q8_routes()
    same = sum(a == b for a, b in zip(outs["sharded"], outs["one-device"]))
    log(f"[mesh] temperature-0 streams identical: {same}/{len(prompts)}")
    first = np.asarray(outs["sharded"])[:, :4].tolist()
    log(f"[mesh] first tokens (sharded): {first}")
    check(same == len(prompts), "sharded and one-device outputs differ")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel sharded engine phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced widths, interpret-mode kernels, any backend; "
                         "never reports ok")
    args = ap.parse_args()

    import jax
    from repro.common.registry import get_arch
    from repro.config import RuntimeConfig
    from repro.configs.reduced import reduce_config
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform!r}); "
              "this run measures nothing off the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    log(f"[cache] compilation cache: {enable_compile_cache()}")
    compiles = CompileTimer()

    cfg = get_arch(ARCH)
    if args.rehearse:
        cfg = reduce_config(cfg)
        rcfg = RuntimeConfig(use_pallas=True, interpret=True)
    else:
        rcfg = RuntimeConfig()
        check(rcfg.use_pallas and not rcfg.interpret,
              "the runtime config did not resolve to compiled kernels")
    log(f"[model] {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    log(f"[runtime] use_pallas={rcfg.use_pallas} interpret={rcfg.interpret}")

    phases = Phases()
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(cfg, rcfg, phases)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[compile] {compiles.count} backend compiles, {compiles.secs:.3f} s")
    stats = dev.memory_stats() or {}
    log(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
        f"bytes_limit={stats.get('bytes_limit', 'n/a')}")
    log(f"[total] {time.perf_counter() - t0:.3f} s")
    if args.rehearse:
        log("rehearsal passed: not a chip run, nothing to report")
        return 0
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
