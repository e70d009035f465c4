"""Mean time of one plan search or plan-cache lookup (``plan``)."""


def read(ctx):
    return ctx.mean_ms("plan")
