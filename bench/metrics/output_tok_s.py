"""Output tokens emitted inside the window, over the window's length (host
clock)."""


def read(run):
    win = run.window
    n = sum(1 for r in win.records for t in r.times if t <= win.seconds)
    return n / win.seconds
