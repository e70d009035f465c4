"""Work of one merge of n (K, V) float32 statistics (Alg. 1/2).

The algorithm reads every part once and writes the merged matrix once;
it does one multiply and one add per element read.  Padding, the
stack copy and the K/V tile pad are implementation, not work.
"""

PATTERNS = ("mlego.merge_topics",)


def cost(n_parts: int, k: int, v: int):
    """(flops, bytes) of one merge at true shapes."""
    return 2.0 * n_parts * k * v, 4.0 * (n_parts + 1) * k * v
