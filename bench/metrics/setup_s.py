"""Seconds from the start of the process to the opening of the window:
imports, drawing the weights, building the engine, warming up (host
clock)."""


def read(run):
    return run.setup_s
