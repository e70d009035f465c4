"""Queries answered inside the window, over the window's length."""


def read(run):
    return run.answered_in_window / run.seconds
