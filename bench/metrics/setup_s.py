"""Seconds from process start to the window: start-up, TPU init, the
deployment and its data built from the seed, compile-cache loads and
compiles, and the warm-up of the window's shapes."""


def read(run):
    return run.setup_s
