"""90th percentile of the time from each request's due time to its last
token: what a caller waits before the tool call can run (host clock)."""
from bench.metrics._common import latencies, percentile


def read(run):
    v = percentile(latencies(run.window, -1), 90)
    return None if v is None else v * 1e3
