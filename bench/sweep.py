#!/usr/bin/env python3
"""Find a cell's knee once: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5 \
        [--seconds 30] [--seed 1]

One process draws the weights and warms up once, then offers the cell's
mix at each rate for `--seconds` on a fresh engine. For each rate it
prints the requests due, those finished by the window's close, the backlog
then, the time to first token (median and 90th percentile) of the first
and the second half of the requests by due time and its 90th percentile
over all of them, the queue wait's 90th percentile, and output tokens/s. A
rate is sustained where the backlog at the close stays within one batch
and the second half's time to first token does not run away from the
first half's.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, traffic  # noqa: E402
from bench.harness import log  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    harness.enable_compile_cache()
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        log("sweep: no TPU found")
        return 2
    cell = harness.load_cell(args.workload)
    served = harness.Served(cell, args.seed, rehearse=args.rehearse)
    harness.warm_up(served, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_per_s=rate)
        arrivals = traffic.generate(mix, args.seconds, args.seed,
                                    served.dims["V"], scale=served.scale)
        engine = served.engine()
        win = harness.run_window(engine, arrivals, args.seconds, drain_s=120)
        del engine
        gc.collect()
        recs = sorted(win.records, key=lambda r: r.due)
        half = len(recs) // 2

        def ttft(rs):
            v = [(r.times[0] if r.times else win.drained_at) - r.due for r in rs]
            return [float(np.percentile(v, 50)), float(np.percentile(v, 90))]

        waits = [r.queue_wait_s for r in recs if r.done]
        done_by_close = sum(1 for r in recs if r.times and r.times[-1]
                            <= args.seconds and r.done)
        toks = sum(1 for r in recs for t in r.times if t <= args.seconds)
        print(json.dumps({
            "rate": rate, "due": len(recs), "done_by_close": done_by_close,
            "backlog_at_close": len(recs) - done_by_close,
            "ttft_first_half_s": ttft(recs[:half]),
            "ttft_second_half_s": ttft(recs[half:]),
            "ttft_p90_s": ttft(recs)[1],
            "queue_wait_p90_s": float(np.percentile(waits, 90)),
            "output_tok_s": toks / args.seconds,
            "drained_at_s": win.drained_at}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
