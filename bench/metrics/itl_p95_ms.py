"""95th percentile of the gaps between consecutive tokens, over every gap
of every request due in the window (host clock)."""
import numpy as np

from bench.metrics._common import percentile


def read(run):
    gaps = [g for r in run.window.records if len(r.times) > 1
            for g in np.diff(r.times)]
    v = percentile(gaps, 95)
    return None if v is None else v * 1e3
