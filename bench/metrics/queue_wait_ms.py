"""Mean time a query waited in the service's queue (``queue.wait``)."""


def read(ctx):
    return ctx.mean_ms("queue.wait")
