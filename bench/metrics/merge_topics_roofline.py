"""Share of its roofline the merge kernel reaches: the algorithm's
bytes and flops of every merge launched inside the traced window, at
the chip's peaks, over the device time of the merge kernel's ops."""


def read(ctx):
    rl = ctx.roofline("merge_topics")
    launches = [s for s in ctx.traced_spans if s.name == "kernel.launch"
                and str(s.attrs.get("op", "")).startswith("merge_topics")]
    if not launches:
        return None
    flops = bytes_ = 0.0
    for s in launches:
        f, b = rl.cost(int(s.attrs["n_parts"]), ctx.k, ctx.v)
        flops += f
        bytes_ += b
    return ctx.roofline_share(rl.PATTERNS, flops, bytes_,
                              [(s.t0, s.t1) for s in launches])
