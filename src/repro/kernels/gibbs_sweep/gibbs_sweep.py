"""Pallas TPU kernel: doc-blocked collapsed-Gibbs sweep.

One grid row owns one *doc block* and keeps its exact document-topic
counts ``n_kd`` (BD, K) in VMEM for the whole sweep, while every block
samples against the same frozen per-sweep snapshot of the topic-word
counts (``prior`` = local n_kv + global N_kv + β — the DSGS Eq. 8
fixed-prior approximation applied across blocks).  Per token:

    oh      = onehot(z_t)                    (VPU compare on the K lane)
    p       = (n_kd[d] − oh + α)(prior[:,w] − oh)/(prior_k − oh)
    z_t     = inverse-CDF sample: count(prefix_sum(p) < u·Σp)
    n_kd[d] += onehot(z_t) − oh              (one-row ref store)

The per-token scalars (word, local doc, mask, uniform, assignment) are
SMEM blocks of ``block_t`` tokens; the grid is (doc blocks, token
chunks), with the chunk axis innermost so a block's ``n_kd`` stays
resident across its chunks.  The topic-word snapshot is passed
*transposed* as ``prior_t`` (V, K) so the per-token gather is a (1, K)
row read on the lane axis.  Mosaic has no cumsum, so the prefix sum is
the log-step shifted-add scan of ``ref.lane_prefix_sum`` — the
reference runs the same adds, which keeps sampling bit-identical to it.
Uniforms are precomputed outside (one (B, T) array per sweep); the new
assignments' (K, V) counts are reduced after the kernel
(``ref.token_counts``), as in the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gibbs_sweep.ref import lane_prefix_sum


def _kernel(words_ref, ldoc_ref, mask_ref, u_ref, z_ref, nkd_ref,
            prior_t_ref, priork_ref, z_out, nkd_out,
            *, alpha: float, k_real: int, block_t: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        nkd_out[...] = nkd_ref[...]

    k = prior_t_ref.shape[1]
    kiota = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    valid = kiota < k_real
    prior_k = priork_ref[...]                                    # (1, K)

    def token(t, carry):
        w = words_ref[t]
        d = ldoc_ref[t]
        m = mask_ref[t]
        old = z_ref[t]
        oh_old = (kiota == old).astype(jnp.float32) * m          # (1, K)
        nd = nkd_out[0, pl.ds(d, 1), :] - oh_old
        num = prior_t_ref[pl.ds(w, 1), :] - oh_old
        den = prior_k - oh_old
        p = valid.astype(jnp.float32) * (nd + alpha) * num / den
        c = lane_prefix_sum(p, roll=pltpu.roll)
        total = jnp.sum(jnp.where(kiota == k_real - 1, c, 0.0),
                        axis=1, keepdims=True)                   # c[K-1]
        target = u_ref[t] * total
        new = jnp.sum(jnp.logical_and(valid, c < target).astype(jnp.int32))
        new = jnp.minimum(new, k_real - 1)
        new = jnp.where(m > 0, new, old)
        oh_new = (kiota == new).astype(jnp.float32) * m
        nkd_out[0, pl.ds(d, 1), :] = nd + oh_new
        z_out[t] = new
        return carry

    jax.lax.fori_loop(0, block_t, token, 0)


def _vmem_bytes(bd: int, k: int, v: int) -> int:
    """Double-buffered VMEM blocks: the (V, K) snapshot, the (BD, K)
    count blocks in and out, and the (1, K) row sums."""
    return 2 * 4 * (v * k + 2 * bd * k + 8 * k)


def gibbs_sweep_pallas(words, ldoc, mask, u, z, nkd, prior_t, prior_k,
                       alpha: float, k_real: int, *, block_t: int,
                       interpret: bool = False):
    """One blocked CGS sweep; grid = (doc blocks, token chunks).

    words/ldoc/mask/u/z: (B, T) with T a multiple of ``block_t``;
    nkd: (B, BD, K); prior_t: (V, K) transposed snapshot (+global +β);
    prior_k: (1, K) row sums.  Returns (z', nkd').
    """
    b, t = words.shape
    _, bd, k = nkd.shape
    v = prior_t.shape[0]
    n_chunks = t // block_t
    kernel = functools.partial(_kernel, alpha=alpha, k_real=k_real,
                               block_t=block_t)
    # per-token scalars ride flat: a 1-D SMEM block of block_t tokens
    tokens = pl.BlockSpec((block_t,), lambda i, j: (i * n_chunks + j,),
                          memory_space=pltpu.SMEM)
    counts = pl.BlockSpec((1, bd, k), lambda i, j: (i, 0, 0))
    z_new, nkd_new = pl.pallas_call(
        kernel,
        grid=(b, n_chunks),
        in_specs=[
            tokens, tokens, tokens, tokens, tokens, counts,
            pl.BlockSpec((v, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=[tokens, counts],
        out_shape=[
            jax.ShapeDtypeStruct((b * t,), z.dtype),
            jax.ShapeDtypeStruct((b, bd, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(bd, k, v) + (8 << 20)),
        interpret=interpret,
    )(words.ravel(), ldoc.ravel(), mask.ravel(), u.ravel(), z.ravel(), nkd,
      prior_t, prior_k)
    return z_new.reshape(b, t), nkd_new
