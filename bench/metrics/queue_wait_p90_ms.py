"""90th percentile of the time each request waited in the scheduler's queue
(`Request.queue_wait_s`, the engine's own counter)."""
from bench.metrics._common import percentile


def read(run):
    v = percentile([r.queue_wait_s for r in run.window.records if r.done], 90)
    return None if v is None else v * 1e3
