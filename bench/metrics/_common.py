"""Helpers the metric readers share."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """numpy's linear-interpolation percentile, or None with no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def latencies(win, pick):
    """Seconds from each due request's due time to the token `pick` chooses
    (0 = first, -1 = last). A request that never got that token counts up
    to the moment the run stopped waiting for it."""
    out = []
    for r in win.records:
        got = r.times and (pick == 0 or r.done)
        out.append((r.times[pick] if got else win.drained_at) - r.due)
    return out


def steps_in_window(win, kinds):
    return [s for s in win.steps if s.kind in kinds and s.end <= win.seconds]


def kernel_roofline(run, match, cost):
    """Share of the roofline, in %, over every traced call of one kernel:
    the least time the chip could take for all of them (each call's
    operations over the bf16 peak or its bytes over HBM bandwidth, the
    larger) over the device time they took. `match(hlo)` picks the calls
    from the trace's ops, `cost(shapes)` gives one call's (operations,
    bytes) from its result and operand shapes. None where the trace holds
    no such call."""
    from bench import flops
    from bench.trace_reduce import shapes
    if run.trace is None or run.peaks is None:
        return None
    least = took = 0.0
    for hlo, s, e in run.trace.ops:
        if not match(hlo):
            continue
        f, b = cost(shapes(hlo))
        least += flops.roofline_s(f, b, run.peaks["bf16_flops_per_s"],
                                  run.peaks["hbm_bytes_per_s"])
        took += (e - s) / 1e9
    return 100.0 * least / took if took > 0 else None


def quant_matmul_call(hlo: str, code: str) -> bool:
    """A call of the quantized matmul kernel whose weight codes (its second
    operand) have HLO type `code`: s8 for Q8, u8 for packed Q4."""
    from bench.trace_reduce import shapes
    if not hlo.startswith("%quant_matmul"):
        return False
    shp = shapes(hlo)
    return len(shp) >= 3 and shp[2][0] == code
