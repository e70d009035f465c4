"""Median client latency: due time (open loop) or send time (closed
loop) to answer, over every query of the window; a query that failed
or never came counts as infinitely late."""


def read(run):
    return 1e3 * run.percentile(50)
