"""95th-percentile client latency, as latency_p50_ms counts it."""


def read(run):
    return 1e3 * run.percentile(95)
