"""Mean gap-training time of a query that trained, summed over its
gaps (``train`` spans grouped by trace)."""


def read(ctx):
    per = {}
    for s in ctx.spans:
        if s.name == "train":
            per[s.trace_id] = per.get(s.trace_id, 0.0) + s.duration_s
    return 1e3 * sum(per.values()) / len(per) if per else None
