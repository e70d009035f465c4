"""Mean self time of the executor's ``merge`` span: its duration less
its ``kernel.launch`` child.  That is the copy of the merged statistic
back to the host and its normalization into topics."""


def read(ctx):
    launch = {}
    for s in ctx.spans:
        if s.name == "kernel.launch" and s.parent_id:
            launch[s.parent_id] = launch.get(s.parent_id, 0.0) \
                + s.duration_s
    self_s = [s.duration_s - launch.get(s.span_id, 0.0)
              for s in ctx.spans if s.name == "merge"]
    return 1e3 * sum(self_s) / len(self_s) if self_s else None
