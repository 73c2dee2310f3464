"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.
  week_eval            — Figs 2–5 (normalized T/P/TPS/CF, 5 methods x 4 weeks)
  engine_week          — engine backend: batched-decode TPS scaling + a
                         compressed day through run_week(backend="engine")
  paged_engine         — paged KV + tool-prefix caching: prefill tokens
                         saved vs dense, decode TPS parity per occupancy
  fleet_engine         — shared-engine fleet: decode TPS + carbon/query vs
                         concurrent sessions, per-pod scheduler counters
  qos_fleet            — QoS tiers under pool pressure (deadline-hit/p95 vs
                         the priority-0 baseline) + deadline-aware routing
  chunked_prefill      — chunked prefill vs monolithic admission: interactive
                         p95 under a heavy-batch mix, decode-TPS parity gate
  spec_decode          — Q4-draft/Q8-verify speculative decoding vs both
                         plain engines: decode TPS + carbon/query across
                         draft lengths, byte-parity with plain Q8
  fleet_scale          — sharded multi-host fleet scale-out: aggregate
                         decode TPS 4 vs 16 pods, regional carbon shedding,
                         data-parallel sharded pods (8 forced host devices)
  fleet_workers        — multi-process fleet workers behind the control
                         protocol vs the same topology in-process: wall
                         speedup, aggregate virtual TPS, carbon/query
  variant_utilization  — Fig 6 (Q8 share per weekday, weeks 3/4)
  operating_modes      — Table I + §III-C TPS/power ladder
  tool_selection       — §III-B selection quality/latency
  kernels              — Pallas kernel microbenches + v5e roofline deriveds
  roofline             — dry-run roofline table (from experiments/dryrun)

CI entrypoint: ``--json-dir DIR`` runs every JSON-capable engine suite and
writes one ``<suite>.json`` artifact each (the per-commit perf trajectory
the regression gate in benchmarks/ci_compare.py reads).
"""
from __future__ import annotations

import argparse
import json
import os


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single suite by name")
    ap.add_argument("--json-dir", default=None,
                    help="write <suite>.json per JSON-capable suite into this "
                         "directory (CI benchmark-artifact mode)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (chunked_prefill, engine_week, fleet_engine,
                            fleet_scale, fleet_workers, kernels_bench,
                            operating_modes, paged_engine, qos_fleet,
                            roofline_table, spec_decode, tool_selection,
                            variant_utilization, week_eval)

    if args.json_dir is not None:
        json_suites = {
            "engine_week": engine_week.json_summary,
            "paged_engine": paged_engine.json_summary,
            "fleet_engine": fleet_engine.json_summary,
            "qos_fleet": qos_fleet.json_summary,
            "fleet_scale": fleet_scale.json_summary,
            "chunked_prefill": chunked_prefill.json_summary,
            "spec_decode": spec_decode.json_summary,
            "fleet_workers": fleet_workers.json_summary,
            "kernels": kernels_bench.json_summary,
        }
        if args.only and args.only not in json_suites:
            raise SystemExit(
                f"--json-dir mode only knows {sorted(json_suites)}; "
                f"got {args.only!r}")
        os.makedirs(args.json_dir, exist_ok=True)
        for name, fn in json_suites.items():
            if args.only and args.only != name:
                continue
            path = os.path.join(args.json_dir, f"{name}.json")
            print(f"[bench] {name} -> {path}", flush=True)
            with open(path, "w") as f:
                json.dump(fn(), f, indent=2, sort_keys=True)
        return

    print("name,us_per_call,derived")
    suites = {
        "operating_modes": operating_modes.run,
        "tool_selection": tool_selection.run,
        "kernels": kernels_bench.run,
        "variant_utilization": variant_utilization.run,
        "week_eval": week_eval.run,
        "engine_week": engine_week.run,
        "paged_engine": paged_engine.run,
        "fleet_engine": fleet_engine.run,
        "qos_fleet": qos_fleet.run,
        "fleet_scale": fleet_scale.run,
        "fleet_workers": fleet_workers.run,
        "chunked_prefill": chunked_prefill.run,
        "spec_decode": spec_decode.run,
        "roofline": roofline_table.run,
    }
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        try:
            fn()
        except Exception as e:  # keep the harness running, report the failure
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            raise


if __name__ == "__main__":
    main()
