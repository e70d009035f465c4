"""Mean time a query that trained spent fitting its gaps on the
device, summed over its gaps (``train.fit`` spans grouped by trace):
the upload of the doc-term matrix, the jitted fit, and the wait for
its result."""


def read(ctx):
    per = {}
    for s in ctx.spans:
        if s.name == "train.fit":
            per[s.trace_id] = per.get(s.trace_id, 0.0) + s.duration_s
    return 1e3 * sum(per.values()) / len(per) if per else None
