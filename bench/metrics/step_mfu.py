"""Share of the chip's peak flops that the whole serving path reached
in the traced window: the algorithm's flops of every merge and every
VB gap E-step inside it, over the window times the peak."""


def read(ctx):
    if ctx.trace is None:
        return None
    flops = 0.0
    merge, vb = ctx.roofline("merge_topics"), ctx.roofline("vb_estep")
    for s in ctx.traced_spans:
        if s.name == "kernel.launch" and \
                str(s.attrs.get("op", "")).startswith("merge_topics"):
            flops += merge.cost(int(s.attrs["n_parts"]), ctx.k, ctx.v)[0]
        elif s.name == "train" and ctx.model["route"] == "vb":
            d = int(round(float(s.attrs["hi"]) - float(s.attrs["lo"])))
            flops += vb.cost(d, ctx.k, ctx.v, ctx.model["e_step_iters"])[0] \
                * ctx.model["max_iters"]
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["flops_per_s"])
