"""Wall time of the window's decode steps over their number, measured by
the benchmark around `ServingEngine.step()`."""
from bench.metrics._common import steps_in_window


def read(run):
    steps = steps_in_window(run.window, ("decode",))
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps) \
        if steps else None
