"""Seconds from the start of the process to the start of the window:
weights, compile-cache loads and warm-up."""


def read(run):
    return run.setup_s
