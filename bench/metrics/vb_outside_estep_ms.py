"""Device time of a VB gap fit outside the E-step kernel: for each
``train.fit`` span of the traced window, the device seconds of the ops
whose midpoint lies inside it, less the E-step kernel's ops and less
control flow (which encloses its body's ops); the mean per gap, in ms.
That is the digamma over lambda, the draw of lambda_0, the E-step's
pads and slices and the M-step add (and any op of a merge that ran at
the same time)."""
from bench import trace_reduce

ENCLOSING = ("while", "conditional", "call")


def read(ctx):
    fits = [(s.t0, s.t1) for s in ctx.traced_spans if s.name == "train.fit"]
    if ctx.trace is None or not fits:
        return None
    estep = ctx.roofline("vb_estep").PATTERNS
    lo, hi = ctx.trace.window
    total = 0.0
    for o in ctx.trace.ops:
        if trace_reduce.matches(o, estep) or \
                o.name.split(".")[0] in ENCLOSING:
            continue
        mid = (o.t0 + o.t1) / 2
        if any(a <= mid <= b for a, b in fits):
            total += min(o.t1, hi) - max(o.t0, lo)
    return 1e3 * total / len(fits)
