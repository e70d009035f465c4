"""Seconds from process start to the window: JAX start-up, corpus and
store, device upload, warm-up (and, in a cold checkout, compiles)."""


def read(run):
    return run.setup_s
