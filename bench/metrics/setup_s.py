"""End to end: process start to the window's open, in s (TPU start-up,
weights from the seed, compile-cache loads, warm-up)."""


def read(run):
    return run.setup_s
