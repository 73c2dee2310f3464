#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration
(`bench/configs/<config>.json`, which names its architecture's plug-in,
`bench/archs/<bench_arch>.py`) and a traffic mix (`bench/traffic/<mix>.json`).
The run draws the weights from the seed on the device, warms up every shape
the mix uses (set-up, timed as `setup_s`), then offers the mix's open-loop
traffic to `ServingEngine` for `--seconds` on the wall clock. After the
window it waits for every request that was due, reads the device's peak
memory, frees the program and checks a seeded sample of the served tokens
against the plain reference (`bench/correct.py`).

Each metric is computed by `bench/metrics/<name>.py`. With `--trace 0` the
line carries the cell's end-to-end metrics; with `--trace 1` the window runs
under the profiler and the line carries the per-layer metrics listed for the
cell, the device's busy and window seconds, and a breakdown.

The last line of standard output is one JSON object; the compared numbers
and their limits are the last lines of standard error and the last key of
that object. Without a TPU, or with fewer chips than the cell asks for, the
run exits 2 and prints no result. `--rehearse` runs the same steps on any
backend at reduced widths with interpret-mode kernels, and never prints a
result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402
from bench.harness import log  # noqa: E402


class Run:
    """What a metric reader gets: the cell, the window's records and steps,
    set-up seconds, the device's peaks and, in a traced run, the reduced
    trace."""

    def __init__(self, cell, dims, window, setup_s, peaks, trace=None):
        self.cell = cell
        self.dims = dims
        self.window = window
        self.setup_s = setup_s
        self.peaks = peaks
        self.trace = trace


def reader(name: str):
    """`bench/metrics/<name>.py`, loaded by its path (a name may hold dots)."""
    path = harness.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metrics(entries, run: Run) -> dict:
    out = {}
    for m in entries:
        value = reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def listed(entries, cell_name: str):
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced widths and interpret-mode kernels on any "
                         "backend; prints no result line")
    args = ap.parse_args()

    harness.enable_compile_cache()
    import jax
    devices = jax.devices()
    cell = harness.load_cell(args.workload)
    if not args.rehearse:
        if devices[0].platform != "tpu":
            log(f"run: no TPU found (JAX sees {devices[0].platform!r}); "
                "nothing is measured off the chip")
            return 2
        if len(devices) < cell.chips:
            log(f"run: {cell.name} needs {cell.chips} chips, JAX sees "
                f"{len(devices)}")
            return 2
    dev = devices[0]
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    if not args.rehearse and dev.device_kind not in peaks:
        log(f"run: no peaks for device kind {dev.device_kind!r} in "
            "bench/peaks.json")
        return 2
    compiles = harness.CompileCounter()

    t_draw = time.perf_counter()
    served = harness.Served(cell, args.seed, rehearse=args.rehearse)
    t_warm = time.perf_counter()
    warm = harness.warm_up(served, args.seed)
    log(f"run: set-up phases: imports and devices {t_draw - T_START:.3f} s, "
        f"weights {t_warm - t_draw:.3f} s, warm-up "
        f"{time.perf_counter() - t_warm:.3f} s")
    engine = served.engine()
    arrivals = served.arrivals(args.seconds, args.seed)
    trace_dir = harness.BENCH / ".trace" / cell.name
    opened = {}

    def on_open():
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        opened["t"] = time.perf_counter()

    def on_close():
        if args.trace:
            jax.profiler.stop_trace()

    win = harness.run_window(engine, arrivals, args.seconds,
                             compiles=compiles, on_open=on_open,
                             on_close=on_close)
    setup_s = opened["t"] - T_START
    del engine
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    lateness = max((r.submitted - r.due for r in win.records), default=0.0)
    log(f"run: {cell.name} seed {args.seed}: set-up {setup_s:.3f} s "
        f"({warm} warm-up requests), window {args.seconds} s, "
        f"{len(win.records)} requests due, drained at {win.drained_at:.3f} s, "
        f"{win.compiles} compiles in the window, generator at most "
        f"{lateness * 1e3:.3f} ms late, peak {peak} bytes, "
        f"kernel fallbacks {win.kernel_fallbacks}")

    reduced = None
    if args.trace:
        from bench import trace_reduce
        reduced = trace_reduce.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    from bench import correct
    sample = harness.sample_for_check(win, args.seed)
    pairs = correct.served_pairs(sample)
    served.free_program()
    t_ref = time.perf_counter()
    gaps = (correct.logit_gaps(cell.arch, served.w, cell.variant,
                               served.dims, pairs, **served.check_shape())
            if pairs else {"max_logit_gap": float("inf"),
                           "mean_logit_gap": float("inf")})
    log(f"run: reference over {len(pairs)} requests, "
        f"{sum(len(o) for _, o in pairs)} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s; gaps {json.dumps(gaps)}")
    limits = ({"max_logit_gap": 0.0, "mean_logit_gap": 0.0} if args.rehearse
              else cell.limits())
    checks = correct.checks(win, gaps, limits)
    ok = correct.passed(checks)

    run = Run(cell, served.dims, win, setup_s,
              peaks.get(dev.device_kind), reduced)
    spec = cell.spec
    if args.trace:
        metrics = read_metrics(listed(spec["per_layer"], cell.name), run)
    else:
        metrics = read_metrics(listed(spec["end_to_end"], cell.name), run)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": len(win.records),
              "failed": checks["unfinished"]["value"]
              + checks["short_answers"]["value"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        from bench import trace_reduce
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["checks"] = checks
    for name, m in metrics.items():
        log(f"metric {name}: {m['value']} {m['unit']}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    if args.rehearse:
        log("rehearsal: not a chip run, no result line")
        return 0
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
