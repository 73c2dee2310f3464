"""The whole engine step's share of the chip's bf16 peak, in %: model
operations of the work the window's steps completed over the time those
steps took on the host's clock (`ServingEngine.step()` calls ended inside
the window) and the peak. The work is the prompt tokens the model ran over
(not those the prefix cache served, nor padding) and every output token,
each with its weight products and its attention over the positions before
it. Time between steps, where the engine has no work, is left out: below
a cell's knee the work is the offered load, and only faster steps raise
this share. The operations of a position are its architecture's
(`body_flops`, `head_flops`, `mixer_flops` of the configuration's plug-in)."""
from bench.metrics._common import steps_in_window


def read(run):
    if run.peaks is None:
        return None
    arch, dims, win = run.cell.arch, run.dims, run.window
    body, head = arch.body_flops(dims), arch.head_flops(dims)
    work = 0.0
    for s in steps_in_window(win, ("prefill",)):
        if not s.rows:
            continue
        # rows alike: each ran computed/rows tokens after cached/rows ones
        c, p = s.cached / s.rows, s.computed / s.rows
        work += s.computed * body + s.rows * head
        work += s.rows * p * arch.mixer_flops(dims, c + (p + 1) / 2)
    for r in win.records:
        for j, t in enumerate(r.times[1:], start=1):
            if t <= win.seconds:
                work += body + head + arch.mixer_flops(dims, r.prompt_len + j)
    busy = sum(s.end - s.start for s in win.steps if s.end <= win.seconds)
    if busy <= 0:
        return None
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])
