"""Work of one fused VB E-step call on a (D, V) document block.

Each of the n_iters coordinate-ascent iterations does two (D, K, V)
contractions (phinorm = E[theta] E[beta] and (x / phinorm) E[beta]^T),
and the closing sufficient-statistics pass two more: 4 D K V (n_iters
+ 1) flops.  Bytes: x, E[beta] and the statistics once each.
"""

PATTERNS = ("mlego.vb_estep",)


def cost(d: int, k: int, v: int, n_iters: int):
    """(flops, bytes) of one E-step call at true shapes."""
    return 4.0 * d * k * v * (n_iters + 1), 4.0 * (d * v + 2 * k * v)
