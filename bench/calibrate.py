#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--controls int8,fp8] [--seconds 20] \
        [--out calibrate.json]

For each seed, in one process: draw the weights, serve the cell's traffic
for `--seconds` through the same window and sample as `bench/run.py`, and
read the widest logit gap of the served tokens against the plain reference
(the lower reading). For the control seeds, also read the gap of the tokens
the reference puts first at each control precision (the upper reading).
Each reading goes through the run's own comparison (`correct.checks`
against the cell's `bench/limits/<cell>.json`): the program's has to come
out correct and each control's not correct.
Only the first seed warms up; later seeds reuse its compiled programs. A
cell name that BENCHMARK.json does not declare is read as
`<config>.<traffic>`.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import correct, harness  # noqa: E402
from bench.harness import log  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    harness.enable_compile_cache()
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        log("calibrate: no TPU found")
        return 2
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    controls = [c for c in args.controls.split(",") if c]
    # a cell that has no limits yet is judged on its window alone
    limits = (cell.limits() if (harness.BENCH / "limits"
                                / f"{cell.name}.json").exists() else {})
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        served = harness.Served(cell, seed, rehearse=args.rehearse)
        if i == 0:
            harness.warm_up(served, seed)
        engine = served.engine()
        win = harness.run_window(engine, served.arrivals(args.seconds, seed),
                                 args.seconds)
        del engine
        sample = harness.sample_for_check(win, seed)
        pairs = correct.served_pairs(sample)
        served.free_program()
        row = {"seed": seed, "requests": len(win.records),
               "unfinished": sum(1 for r in win.records if not r.done),
               "checked_requests": len(pairs),
               "checked_tokens": sum(len(o) for _, o in pairs),
               "program": correct.logit_gaps(
                   cell.arch, served.w, cell.variant, served.dims, pairs,
                   **served.check_shape())}
        row["program_correct"] = correct.passed(
            correct.checks(win, row["program"], limits))
        if seed in ctrl_seeds:
            for act in controls:
                gaps = correct.control_gaps(
                    cell.arch, served.w, cell.variant, served.dims, pairs,
                    act, **served.check_shape())
                row[f"control_{act}"] = gaps
                row[f"control_{act}_correct"] = correct.passed(
                    correct.checks(win, gaps, limits))
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        del served, win, sample, pairs
        gc.collect()
    summary = {"workload": cell.name, "rows": rows}
    for num in rows[0]["program"]:
        summary[f"lower {num}"] = max(r["program"][num] for r in rows)
        for act in controls:
            vals = [r[f"control_{act}"][num] for r in rows
                    if f"control_{act}" in r]
            if vals:
                summary[f"upper {num} {act}"] = min(vals)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
