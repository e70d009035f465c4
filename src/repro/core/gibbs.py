"""Collapsed Gibbs Sampling for LDA + DSGS partition deltas (paper Eq. 7–9).

The exact token sweep is genuinely sequential (each draw conditions on
all other assignments), so ``cgs_fit`` expresses it as a ``lax.scan``
over tokens — exactly the per-partition CGS that DSGS assumes.
Distribution comes from *partitioning*, not from parallelizing the
sweep: each worker runs CGS on its partition against a fixed global
``N_kv`` prior (Eq. 8) and emits ``ΔN_kv``; merging deltas (Alg. 2) is
an all-reduce.

``cgs_fit_blocked`` applies the same fixed-prior independence one
level down: documents are sharded into *doc blocks*, each block keeps
its ``n_kd`` exact and resamples its tokens sequentially against a
per-sweep snapshot of ``n_kv + global N_kv``, and block-local count
deltas are reduced between sweeps (kernels/gibbs_sweep).  The
sequential chain per sweep shrinks from Σ tokens to max tokens per
block, which is what makes device-resident Gibbs gap training viable
in the query hot path; ``cgs_fit`` remains the exact-scan parity
reference (and the HostBackend default).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.lda_default import LDAConfig
from repro.obs import trace as obs


@functools.partial(jax.jit, static_argnames=("n_topics", "n_docs", "vocab",
                                             "sweeps"))
def _cgs_sweeps(tokens, doc_ids, key, global_nkv, n_topics: int,
                n_docs: int, vocab: int, sweeps: int, alpha: float,
                beta: float):
    """Run ``sweeps`` full CGS sweeps.  Returns (z, local n_kv).

    global_nkv is the fixed prior count matrix (Eq. 8's β + N_kv);
    the sampler's conditional uses (n_kv_local + global_nkv + β).
    """
    t = tokens.shape[0]
    k0, key = jax.random.split(key)
    z0 = jax.random.randint(k0, (t,), 0, n_topics)

    nkd = jnp.zeros((n_docs, n_topics), jnp.float32).at[doc_ids, z0].add(1.0)
    nkv = jnp.zeros((n_topics, vocab), jnp.float32).at[z0, tokens].add(1.0)
    nk = jnp.zeros((n_topics,), jnp.float32).at[z0].add(1.0)
    gk = global_nkv.sum(axis=1)

    def token_step(carry, inp):
        z, nkd, nkv, nk = carry
        idx, u = inp
        d = doc_ids[idx]
        w = tokens[idx]
        old = z[idx]
        # decrement
        nkd = nkd.at[d, old].add(-1.0)
        nkv = nkv.at[old, w].add(-1.0)
        nk = nk.at[old].add(-1.0)
        # conditional  (Eq. 7, with the DSGS global prior)
        p = (nkd[d] + alpha) * (nkv[:, w] + global_nkv[:, w] + beta) / (
            nk + gk + vocab * beta)
        c = jnp.cumsum(p)
        new = jnp.searchsorted(c, u * c[-1])
        new = jnp.clip(new, 0, n_topics - 1)
        z = z.at[idx].set(new)
        nkd = nkd.at[d, new].add(1.0)
        nkv = nkv.at[new, w].add(1.0)
        nk = nk.at[new].add(1.0)
        return (z, nkd, nkv, nk), None

    def sweep(carry, key_s):
        u = jax.random.uniform(key_s, (t,))
        carry, _ = jax.lax.scan(token_step, carry,
                                (jnp.arange(t), u))
        return carry, None

    keys = jax.random.split(key, sweeps)
    (z, nkd, nkv, nk), _ = jax.lax.scan(sweep, (z0, nkd, nkv, nk), keys)
    return z, nkv


def cgs_fit(tokens: np.ndarray, doc_ids: np.ndarray, cfg: LDAConfig, key,
            global_nkv: Optional[np.ndarray] = None,
            sweeps: Optional[int] = None) -> np.ndarray:
    """Train a CGS partition model.  Returns ΔN_kv (K, V) float32.

    With ``global_nkv`` provided this is one DSGS step (Eq. 8):
    ΔN_kv = CGS(α, β + N_kv, W^t).
    """
    if tokens.size == 0:
        return np.zeros((cfg.n_topics, _vocab(cfg, global_nkv)), np.float32)
    vocab = _vocab(cfg, global_nkv)
    gnkv = (jnp.zeros((cfg.n_topics, vocab), jnp.float32)
            if global_nkv is None else jnp.asarray(global_nkv, jnp.float32))
    n_docs = int(doc_ids.max()) + 1
    _, nkv = _cgs_sweeps(
        jnp.asarray(tokens, jnp.int32), jnp.asarray(doc_ids, jnp.int32),
        key, gnkv, cfg.n_topics, n_docs, vocab,
        sweeps if sweeps is not None else cfg.gibbs_sweeps,
        cfg.alpha, cfg.eta,
    )
    return np.asarray(nkv)


def _vocab(cfg: LDAConfig, global_nkv) -> int:
    return cfg.vocab_size if global_nkv is None else global_nkv.shape[1]


# ---------------------------------------------------------------------------
# doc-blocked sweeps (device route; kernels/gibbs_sweep)
# ---------------------------------------------------------------------------

def blocked_layout(tokens: np.ndarray, doc_ids: np.ndarray, n_docs: int,
                   block_docs: int
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack a CSR-ordered token stream into (n_blocks, T) doc blocks.

    Block b owns the contiguous documents [b·BD, (b+1)·BD); its tokens
    are a contiguous ``doc_ids`` slice (the stream is sorted by doc).
    Returns ``(words, ldoc, mask)`` each (n_blocks, T) with T the
    widest block's token count — pad slots carry mask 0 and word/doc 0.
    """
    n_blocks = max(1, math.ceil(n_docs / block_docs))
    edges = np.searchsorted(
        doc_ids, np.arange(n_blocks + 1) * block_docs, side="left")
    t_max = max(1, int(np.diff(edges).max()))
    words = np.zeros((n_blocks, t_max), np.int32)
    ldoc = np.zeros((n_blocks, t_max), np.int32)
    mask = np.zeros((n_blocks, t_max), np.float32)
    for b in range(n_blocks):
        t0, t1 = int(edges[b]), int(edges[b + 1])
        n = t1 - t0
        words[b, :n] = tokens[t0:t1]
        ldoc[b, :n] = doc_ids[t0:t1] - b * block_docs
        mask[b, :n] = 1.0
    return words, ldoc, mask


@functools.partial(jax.jit, static_argnames=("n_topics", "block_docs",
                                             "vocab", "sweeps", "alpha",
                                             "beta", "use_kernel",
                                             "interpret"))
def _blocked_sweeps(words, ldoc, mask, key, global_nkv, n_topics: int,
                    block_docs: int, vocab: int, sweeps: int, alpha: float,
                    beta: float, use_kernel: bool, interpret: bool):
    """Run ``sweeps`` blocked sweeps.  Returns the final local n_kv."""
    from repro.kernels.gibbs_sweep.ops import gibbs_sweep

    b, t = words.shape
    k0, key = jax.random.split(key)
    z0 = jax.random.randint(k0, (b, t), 0, n_topics)
    nkd0 = jax.vmap(
        lambda l, zz, m: jnp.zeros((block_docs, n_topics),
                                   jnp.float32).at[l, zz].add(m)
    )(ldoc, z0, mask)
    nkv0 = jnp.zeros((n_topics, vocab), jnp.float32).at[
        z0.ravel(), words.ravel()].add(mask.ravel())
    gk = global_nkv.sum(axis=1)

    def sweep(carry, key_s):
        z, nkd, nkv = carry
        u = jax.random.uniform(key_s, (b, t))
        prior = nkv + global_nkv + beta           # frozen for this sweep
        prior_k = nkv.sum(axis=1) + gk + vocab * beta
        z, nkd, nkv = gibbs_sweep(words, ldoc, mask, u, z, nkd, prior,
                                  prior_k, alpha, use_kernel=use_kernel,
                                  interpret=interpret)
        return (z, nkd, nkv), None

    keys = jax.random.split(key, sweeps)
    (_, _, nkv), _ = jax.lax.scan(sweep, (z0, nkd0, nkv0), keys)
    return nkv


def cgs_fit_blocked(tokens: np.ndarray, doc_ids: np.ndarray, cfg: LDAConfig,
                    key, global_nkv: Optional[np.ndarray] = None,
                    sweeps: Optional[int] = None, *, block_docs: int = 64,
                    use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None) -> np.ndarray:
    """Doc-blocked CGS partition model.  Returns ΔN_kv (K, V) float32.

    Same contract as :func:`cgs_fit` (a DSGS step when ``global_nkv``
    is given) but sampled with the blocked sweep: per-sweep-stale
    ``n_kv`` across doc blocks, exact ``n_kd`` within each.  Not
    bit-comparable to the exact scan — parity is *statistical*
    (perplexity / top-word overlap; see tests/test_gibbs_blocked.py).

    ``use_kernel=None`` routes to the Pallas kernel on TPU (or when
    ``MLEGO_KERNEL_INTERPRET=1``) and to the vmapped jnp sweep
    elsewhere; both run the identical blocked math.
    """
    from repro.kernels.gibbs_sweep.ops import default_use_kernel
    from repro.kernels.common import default_interpret

    if tokens.size == 0:
        return np.zeros((cfg.n_topics, _vocab(cfg, global_nkv)), np.float32)
    vocab = _vocab(cfg, global_nkv)
    n_docs = int(doc_ids.max()) + 1
    with obs.span("train.densify", "backend", d=n_docs):
        if np.any(np.diff(doc_ids) < 0):
            # blocked_layout needs the CSR doc-sorted stream cgs_fit
            # does not; token order within a doc is immaterial to the
            # sampler
            order = np.argsort(doc_ids, kind="stable")
            tokens, doc_ids = tokens[order], doc_ids[order]
        host = blocked_layout(tokens, doc_ids, n_docs, block_docs)
        if global_nkv is not None:
            host += (np.asarray(global_nkv, np.float32),)
    use_kernel = default_use_kernel(use_kernel)
    with obs.span("train.fit", "backend",
                  bytes_in=sum(int(a.nbytes) for a in host)):
        words, ldoc, mask, *prior = jax.device_put(host)
        gnkv = prior[0] if prior else \
            jnp.zeros((cfg.n_topics, vocab), jnp.float32)
        nkv = _blocked_sweeps(
            words, ldoc, mask, key, gnkv, cfg.n_topics, block_docs, vocab,
            sweeps if sweeps is not None else cfg.gibbs_sweeps,
            cfg.alpha, cfg.eta, use_kernel,
            default_interpret(interpret) if use_kernel else False)
        nkv.block_until_ready()
    with obs.span("train.fetch", "backend", bytes_out=int(nkv.nbytes)):
        return np.asarray(nkv)
