"""Mean wall time of an admission (prefill) step in the window, measured
by the benchmark around `ServingEngine.step()`, which ends in a copy of
the sampled tokens to the host."""
from bench.metrics._common import steps_in_window


def read(run):
    steps = steps_in_window(run.window, ("prefill",))
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps) \
        if steps else None
