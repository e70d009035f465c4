"""Pure-jnp oracle for the doc-blocked CGS sweep kernel.

One *blocked* Gibbs sweep (the DSGS fixed-prior approximation applied
across doc blocks within a partition): every block resamples its
tokens sequentially against a frozen per-sweep snapshot of the
topic-word counts (``prior`` = local ``n_kv`` snapshot + global
``N_kv`` + β), while its document-topic counts ``n_kd`` stay exact —
documents never span blocks, so ``n_kd`` rows are block-private.
Blocks are independent given the snapshot, which is what lets the
sweep vmap across them (sequential chain length drops from Σ tokens to
max tokens-per-block); the kernel runs the identical math with one
grid step per block.

The only cross-block coupling is the *decrement* of the current
token's own assignment (it is still in the snapshot, so ``num``/``den``
stay ≥ β > 0) and the count reduction after the sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def lane_prefix_sum(p, roll=jnp.roll):
    """Inclusive prefix sum over the last axis by log-step shifted adds.

    The Pallas kernel runs the same adds with ``pltpu.roll`` (Mosaic
    lowers no cumsum), so kernel and reference round identically.
    Lanes past a vector's true length only ever add to later lanes, so
    a K-lane prefix is the same whether the vector is padded or not.
    """
    k = p.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, p.shape, p.ndim - 1)
    s = 1
    while s < k:
        p = p + jnp.where(lane >= s, roll(p, s, p.ndim - 1), 0.0)
        s *= 2
    return p


def token_counts(z, words, mask, k: int, v: int):
    """(K, V) token counts of the assignments ``z`` (pad tokens carry
    mask 0)."""
    return jnp.zeros((k, v), jnp.float32).at[
        z.ravel(), words.ravel()].add(mask.ravel())


def _sweep_block(words, ldoc, mask, u, z, nkd, prior, prior_k,
                 alpha: float, k_real: int):
    """Resample one doc block's tokens sequentially.

    words/ldoc/mask/u/z: (T,); nkd: (BD, K); prior: (K, V) snapshot
    counts + global counts + β; prior_k: (K,) its row sums (with Vβ).
    Returns (z', nkd').
    """
    k = prior.shape[0]
    kidx = jnp.arange(k)
    valid = (kidx < k_real).astype(jnp.float32)

    def token_step(carry, t):
        z, nkd = carry
        w = words[t]
        d = ldoc[t]
        m = mask[t]
        old = z[t]
        oh_old = (kidx == old).astype(jnp.float32) * m
        nd = nkd[d] - oh_old                      # exact doc-topic counts
        num = prior[:, w] - oh_old                # stale n_kv, own token out
        den = prior_k - oh_old
        p = valid * (nd + alpha) * num / den      # Eq. 7 w/ DSGS prior
        c = lane_prefix_sum(p)
        target = u[t] * c[k_real - 1]
        new = jnp.sum(((c < target) & (kidx < k_real)).astype(jnp.int32))
        new = jnp.minimum(new, k_real - 1)
        new = jnp.where(m > 0, new, old).astype(z.dtype)
        oh_new = (kidx == new).astype(jnp.float32) * m
        nkd = nkd.at[d].add(oh_new - oh_old)
        z = z.at[t].set(new)
        return (z, nkd), None

    (z, nkd), _ = jax.lax.scan(token_step, (z, nkd),
                               jnp.arange(words.shape[0]))
    return z, nkd


def gibbs_sweep_ref(words, ldoc, mask, u, z, nkd, prior, prior_k,
                    alpha: float, k_real: int = None):
    """One blocked CGS sweep over all doc blocks (vmapped).

    words/ldoc/mask/u/z: (B, T); nkd: (B, BD, K); prior: (K, V);
    prior_k: (K,).  Returns (z', nkd', nkv) with nkv (K, V) the token
    counts of the *new* assignments summed over blocks — the caller
    turns these into the next sweep's snapshot / the final ΔN_kv.
    """
    k, v = prior.shape
    k_real = k if k_real is None else k_real
    block = functools.partial(_sweep_block, alpha=alpha, k_real=k_real)
    z, nkd = jax.vmap(block, in_axes=(0, 0, 0, 0, 0, 0, None, None))(
        words, ldoc, mask, u, z, nkd, prior, prior_k)
    return z, nkd, token_counts(z, words, mask, k, v)
