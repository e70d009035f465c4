"""Pallas TPU kernel: weighted K×V statistic merge (memory-bound).

The paper's Alg. 1/2 merge is one pass over n' topic-word matrices —
pure HBM bandwidth.  The kernel fuses (subtract base, scale by weight
/ decay, accumulate, add bias) into a single read of each (K, V) tile,
so HBM traffic is exactly n'·K·V·4 bytes read + K·V·4 written (the
unfused jnp chain reads/writes intermediates ~3x).

Grid: (K/BK, V/BV, rows); the part (row) axis is the innermost,
accumulating grid axis, so each step holds one (BK, BV) tile and VMEM
use is independent of the part count.

``merge_topics_ragged_pallas`` is the segmented (CSR) form: a batch of
b independent merges with *different* part counts flattened into one
(R, K, V) row stack plus per-row segment ids — one launch, zero pad
rows on any batch shape.  The segment id array rides as a scalar-
prefetch operand so the output index map can route row r's tile to
block ``seg_ids[r]`` (data-dependent output blocking).
``merge_topics_pallas`` is its one-segment case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _batched_kernel(stats_ref, w_ref, out_ref, *, bias: float, base: float):
    s = stats_ref[0].astype(jnp.float32)            # (n, BK, BV)
    w = w_ref[0].astype(jnp.float32)                # (n, 1)
    acc = jnp.sum(w[:, :, None] * (s - base), axis=0)
    out_ref[0] = acc + bias


def merge_topics_batched_pallas(stats, weights, bias: float = 0.0,
                                base: float = 0.0, *, block_k: int = 128,
                                block_v: int = 512, interpret: bool = False):
    """Batch of independent merges in one launch.

    stats: (b, n, K, V) f32; weights: (b, n) f32 -> (b, K, V) f32.
    One grid step per (query, K-tile, V-tile); ragged batches pad the
    n axis with zero-weight rows (0·(s − base) contributes nothing),
    so b queries with different part counts share a single launch.
    """
    b, n, k, v = stats.shape
    bk = min(block_k, k)
    bv = min(block_v, v)
    w3 = weights.reshape(b, n, 1).astype(jnp.float32)
    kernel = functools.partial(_batched_kernel, bias=bias, base=base)
    return pl.pallas_call(
        kernel,
        grid=(b, pl.cdiv(k, bk), pl.cdiv(v, bv)),
        in_specs=[
            pl.BlockSpec((1, n, bk, bv), lambda q, i, j: (q, 0, i, j)),
            pl.BlockSpec((1, n, 1), lambda q, i, j: (q, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bk, bv), lambda q, i, j: (q, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, k, v), jnp.float32),
        interpret=interpret,
    )(stats, w3)


def _ragged_kernel(seg_ref, w_ref, stats_ref, out_ref, *, bias: float,
                   base: float):
    r = pl.program_id(2)
    prev = seg_ref[jnp.maximum(r - 1, 0)]
    is_start = jnp.logical_or(r == 0, seg_ref[r] != prev)
    contrib = w_ref[r] * (stats_ref[0].astype(jnp.float32) - base)

    @pl.when(is_start)
    def _():
        out_ref[0] = contrib + bias

    @pl.when(jnp.logical_not(is_start))
    def _():
        out_ref[0] += contrib


def merge_topics_ragged_pallas(stats, weights, seg_ids, num_segments: int,
                               bias: float = 0.0, base: float = 0.0, *,
                               block_k: int = 128, block_v: int = 2048,
                               interpret: bool = False):
    """Segmented merge: b ragged queries, one launch, zero pad rows.

    stats: (R, K, V) f32 — every query's part rows concatenated;
    weights: (R,) f32; seg_ids: (R,) int32 non-decreasing, seg_ids[r]
    names the query row r belongs to -> (num_segments, K, V) f32.

    The row axis is the *innermost* grid axis, so all rows of one
    segment revisit their shared output block on consecutive grid
    steps — the Pallas TPU requirement for read-modify-write output
    accumulation — and VMEM holds one (BK, BV) tile per operand
    whatever the row count.  ``seg_ids`` and ``weights`` are scalar-
    prefetch operands (SMEM): the output index map reads ``seg_ids`` to
    pick the destination block, and the kernel body compares seg_ids[r]
    against seg_ids[r-1] to detect segment starts (initialize with
    bias) vs continuations (accumulate).
    """
    n_rows, k, v = stats.shape
    bk = min(block_k, k)
    bv = min(block_v, v)
    kernel = functools.partial(_ragged_kernel, bias=bias, base=base)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(k, bk), pl.cdiv(v, bv), n_rows),
        in_specs=[
            pl.BlockSpec((1, bk, bv), lambda i, j, r, seg, w: (r, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bk, bv),
                               lambda i, j, r, seg, w: (seg[r], i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, k, v), jnp.float32),
        interpret=interpret,
    )(seg_ids.astype(jnp.int32), weights.astype(jnp.float32), stats)


def merge_topics_pallas(stats, weights, bias: float = 0.0, base: float = 0.0,
                        *, interpret: bool = False):
    """stats: (n, K, V) f32; weights: (n,) f32 -> (K, V) f32.

    One merge is the one-segment case of the segmented kernel: the part
    axis is the innermost accumulating grid axis, so VMEM use does not
    grow with n.
    """
    seg = jnp.zeros((stats.shape[0],), jnp.int32)
    return merge_topics_ragged_pallas(stats, weights, seg, 1, bias, base,
                                      interpret=interpret)[0]
