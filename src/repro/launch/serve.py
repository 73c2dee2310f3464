"""Serving launcher: the CarbonCall runtime on a REAL JAX model — tool
selection, CI-driven operating modes, and live Q8/Q4 hot-swap on the serving
engine.

  PYTHONPATH=src python -m repro.launch.serve --queries 12 --minutes-per-query 30

By default the model is the reduced config (d_model 64), which runs on a
CPU. ``--full-width`` serves the architecture at its published widths
(Qwen2-7B: 28 layers, d 3584, vocab 152064) — for one TPU v5e. Either way
both variants are built piece by piece from the seed (`quant.build_variants`).

With ``--workers N`` the same query stream is served by N worker PROCESSES
behind the engine control protocol (launch/workers.py): each worker builds
its own engine from the serialized `EngineConfig` + reduced model config,
queries round-robin across them as `SessionRequest` wire payloads, and
telemetry comes back as versioned `EngineStats`.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.common.hardware import ORIN_AGX
from repro.common.registry import get_arch
from repro.config import RuntimeConfig
from repro.configs.reduced import reduce_config
from repro.core import (CarbonGovernor, ORIN_MODES, ToolSelector,
                        VariantSwitcher, carbon_footprint, ci_trace,
                        forecast_trace)
from repro.core.power import PowerModel
from repro.data.workload import build_catalog, FunctionCallWorkload
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.quant import build_variants
from repro.serving import (EngineConfig, EngineStats, ServingEngine,
                           SessionRequest, WorkerSpec)


def _prompt_for(text: str, vocab_size: int):
    import hashlib
    return [2 + (int.from_bytes(hashlib.md5(w.encode()).digest()[:4],
                                'little') % (vocab_size - 2))
            for w in text.lower().split()][:24]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="carboncall-qwen2-7b")
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--minutes-per-query", type=float, default=30.0)
    ap.add_argument("--week", default="week1")
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--workers", type=int, default=0,
                    help="serve through N worker processes behind the "
                         "control protocol (0 = in-process engine)")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the architecture at its published widths "
                         "(in-process; one TPU v5e for the paper's model)")
    args = ap.parse_args()
    if args.full_width and args.workers:
        ap.error("--full-width serves in-process; drop --workers")
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full_width:
        cfg = reduce_config(cfg)
    econfig = EngineConfig(max_batch=4, max_seq=128)
    workers = []
    client = None
    if args.workers > 0:
        from repro.launch.workers import launch_workers
        specs = [WorkerSpec(config=econfig,
                            model_cfg=dataclasses.asdict(cfg), seed=w,
                            label=f"serve-w{w}")
                 for w in range(args.workers)]
        workers = launch_workers(specs)
        print(f"[serve] {len(workers)} worker process(es) ready")
    else:
        rcfg = RuntimeConfig()
        model = get_model(cfg)
        variants = build_variants(model.param_spec(), jax.random.PRNGKey(0))
        engine = ServingEngine(cfg, variants["q8"], rcfg, config=econfig)
        engine.variant_name = "q8"
        client = engine.client()

    cat = build_catalog(64, seed=0)
    selector = ToolSelector(cat)
    workload = FunctionCallWorkload(cat, seed=7)
    governor = CarbonGovernor(ORIN_MODES)
    switcher = VariantSwitcher(window_s=600.0)
    pm = PowerModel(ORIN_AGX)

    ci = ci_trace(args.week, seed=0)
    fc = forecast_trace(ci)
    state = governor.init(fc[:144])
    switcher.set_reference(20.0)

    total_cf = 0.0
    t_virtual = 0.0
    for qi in range(args.queries):
        idx = int(t_virtual // 600) % len(ci)
        state = governor.update(state, float(ci[idx]))
        mode = governor.mode(state)
        q = workload.sample()
        sel = selector.select(q.text)
        # serve a real request through the engine / a worker
        sreq = SessionRequest(prompt=_prompt_for(q.text, cfg.vocab_size),
                              max_new_tokens=args.max_new_tokens, eos_id=-1)
        if workers:
            w = workers[qi % len(workers)]
            res = w.settle([w.submit(sreq)])[0]
            tokens = len(res.output)
            tps = w.stats().decode_tps
        else:
            h = client.submit(sreq)
            client.settle([h])
            tokens = len(h.request.output)
            tps = client.engine.recent_tps()
        # TPS model at this mode feeds the switcher (CPU wall time is not
        # Orin TPS; scale by the mode ladder)
        mode_tps = 20.0 * (0.3 + 0.7 * mode.f_gpu / ORIN_MODES[0].f_gpu) * \
            (1.9 if switcher.variant == "q4" else 1.0)
        switcher.observe(t_virtual, mode_tps)
        dec = switcher.decide(t_virtual)
        if dec.switch_to:
            switcher.apply(t_virtual, dec)
            if workers:
                for w in workers:
                    w.call("swap", variant=switcher.variant)
            else:
                client.engine.swap_params(variants[switcher.variant],
                                          switcher.variant)
            print(f"  >> variant switch -> {switcher.variant} ({dec.reason})")
        exec_s = args.max_new_tokens / mode_tps
        energy = pm.power(mode) * exec_s
        cf = carbon_footprint(energy, float(ci[idx]))
        total_cf += cf
        print(f"[serve] q{qi:02d} ci={ci[idx]:.0f} mode=m{mode.index} "
              f"variant={switcher.variant} tools={sel.tool_ids[:4]} "
              f"tokens={tokens} engine_tps={tps:.1f} cf={cf*1000:.1f} mgCO2")
        t_virtual += args.minutes_per_query * 60.0
    print(f"[serve] total carbon: {total_cf*1000:.1f} mgCO2 over "
          f"{args.queries} queries")
    if workers:
        agg = EngineStats.merge([w.stats() for w in workers])
        print(f"[serve] fleet stats v{agg.schema_version}: "
              f"admitted={agg.admitted} tokens={agg.tokens_emitted} "
              f"swaps={agg.swap_count}")
        for w in workers:
            w.close()


if __name__ == "__main__":
    main()
