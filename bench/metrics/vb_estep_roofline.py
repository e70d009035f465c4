"""Share of its roofline the fused VB E-step kernel reaches: the
algorithm's flops of every E-step call of the gaps trained inside the
traced window (max_iters calls per gap, true D, K, V), at the chip's
peaks, over the device time of the E-step kernel's ops."""


def read(ctx):
    rl = ctx.roofline("vb_estep")
    trains = [s for s in ctx.traced_spans if s.name == "train"]
    if not trains:
        return None
    flops = bytes_ = 0.0
    for s in trains:
        d = int(round(float(s.attrs["hi"]) - float(s.attrs["lo"])))
        f, b = rl.cost(d, ctx.k, ctx.v, ctx.model["e_step_iters"])
        flops += f * ctx.model["max_iters"]
        bytes_ += b * ctx.model["max_iters"]
    return ctx.roofline_share(rl.PATTERNS, flops, bytes_,
                              [(s.t0, s.t1) for s in trains])
