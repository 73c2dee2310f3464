"""Mean number of requests a decode step of the window carried (the
engine's `step_log` `active`)."""
from bench.metrics._common import steps_in_window


def read(run):
    steps = steps_in_window(run.window, ("decode",))
    return sum(s.active for s in steps) / len(steps) if steps else None
