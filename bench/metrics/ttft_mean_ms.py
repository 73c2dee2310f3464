"""Mean time from each request's due time to its first token, over every
request due in the window (host clock)."""
from bench.metrics._common import latencies


def read(run):
    v = latencies(run.window, 0)
    return 1e3 * sum(v) / len(v) if v else None
