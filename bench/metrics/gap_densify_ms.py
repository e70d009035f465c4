"""Mean time a query that trained spent building its gaps' dense
(D, V) doc-term matrices on the host, summed over its gaps
(``train.densify`` spans grouped by trace)."""


def read(ctx):
    per = {}
    for s in ctx.spans:
        if s.name == "train.densify":
            per[s.trace_id] = per.get(s.trace_id, 0.0) + s.duration_s
    return 1e3 * sum(per.values()) / len(per) if per else None
