"""Batch mean-field Variational Bayes for LDA (Hoffman-style), in JAX.

The E-step inner loop is two MXU matmuls per iteration over the
doc-term matrix — this is LDA's compute hot spot and maps onto
``kernels/vb_estep`` (Pallas) on TPU; the pure-jnp path here doubles as
its reference and as the CPU execution path.

Distribution: ``vb_fit_sharded`` shards documents over the data axes
(DP) and the vocabulary over the ``model`` axis (TP).  The M-step's
sufficient-statistic reduction **is the paper's model merge** (Alg. 1)
executed as a psum — merging materialized models and merging per-device
partial models are the same exponential-family addition.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.lda_default import LDAConfig
from repro.distributed.sharding import MeshEnv


def _exp_dirichlet_expectation(x):
    """exp(E[log p]) for Dirichlet rows: exp(ψ(x) − ψ(Σx))."""
    return jnp.exp(
        jax.scipy.special.digamma(x)
        - jax.scipy.special.digamma(x.sum(-1, keepdims=True))
    )


def vb_estep(x, exp_elog_beta, gamma0, alpha: float, n_iters: int,
             *, use_kernel: bool = False):
    """Coordinate-ascent E-step over a doc-block.

    x:              (D, V) counts, f32
    exp_elog_beta:  (K, V) f32
    gamma0:         (D, K) f32 initial document-topic Dirichlet params
    Returns (gamma, sstats) with sstats (K, V) = Σ_d n_dw φ_dwk
    (already multiplied by expElogbeta).
    """
    if use_kernel:
        from repro.kernels.vb_estep import ops as _ops
        return _ops.vb_estep(x, exp_elog_beta, gamma0, alpha, n_iters)

    def body(gamma, _):
        exp_elog_theta = _exp_dirichlet_expectation(gamma)  # (D, K)
        phinorm = exp_elog_theta @ exp_elog_beta + 1e-30    # (D, V)
        gamma = alpha + exp_elog_theta * ((x / phinorm) @ exp_elog_beta.T)
        return gamma, None

    gamma, _ = jax.lax.scan(body, gamma0, None, length=n_iters)
    exp_elog_theta = _exp_dirichlet_expectation(gamma)
    phinorm = exp_elog_theta @ exp_elog_beta + 1e-30
    sstats = (exp_elog_theta.T @ (x / phinorm)) * exp_elog_beta
    return gamma, sstats


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel"))
def vb_fit(x, key, cfg: LDAConfig, *, use_kernel: bool = False):
    """Batch VB on a dense doc-term matrix.  Returns λ (K, V) f32.

    The named scopes put each phase's ops under its name in the HLO
    metadata (``mlego.vb_init``, ``mlego.vb_expect``, ``mlego.vb_mstep``;
    the E-step kernel brings ``mlego.vb_estep``), so a profiler trace
    can tell them apart."""
    k = cfg.n_topics
    d, v = x.shape
    with jax.named_scope("mlego.vb_init"):
        lam0 = jax.random.gamma(key, 100.0, (k, v), jnp.float32) * 0.01

    def outer(lam, _):
        gamma0 = jnp.ones((d, k), jnp.float32)
        with jax.named_scope("mlego.vb_expect"):
            exp_elog_beta = _exp_dirichlet_expectation(lam)
        _, sstats = vb_estep(x, exp_elog_beta, gamma0, cfg.alpha,
                             cfg.e_step_iters, use_kernel=use_kernel)
        with jax.named_scope("mlego.vb_mstep"):
            lam = cfg.eta + sstats
        return lam, None

    lam, _ = jax.lax.scan(outer, lam0, None, length=cfg.max_iters)
    return lam


# ---------------------------------------------------------------------------
# sharded training: docs over DP axes, vocab over `model`
# ---------------------------------------------------------------------------

def vb_fit_sharded(x, key, cfg: LDAConfig, env: MeshEnv,
                   max_iters: Optional[int] = None):
    """Distributed batch VB.

    x is (D, V) with D sharded over (pod?, data) and V sharded over
    `model`.  Each step:
      - phinorm needs the full Σ_k over local V — local matmul
      - the γ update sums over V         — psum over `model`
      - the λ update sums over documents — psum over DP axes
    The DP psum of per-shard sufficient statistics is exactly the
    paper's Alg. 1 merge of per-partition models.
    """
    iters = max_iters if max_iters is not None else cfg.max_iters
    dp = env.dp_axes
    tp = env.tp_axis
    k = cfg.n_topics

    def local(x_l, key):
        d_l, v_l = x_l.shape
        lam_l = jax.random.gamma(key, 100.0, (k, v_l), jnp.float32) * 0.01

        # NOTE: Dirichlet expectation over a V-sharded λ needs the *global*
        # row sum — one small psum per outer iteration.
        def outer(lam_l, _):
            row = lam_l.sum(-1, keepdims=True)
            if tp is not None and env.tp_size > 1:
                row = jax.lax.psum(row, tp)
            ee_beta = jnp.exp(jax.scipy.special.digamma(lam_l)
                              - jax.scipy.special.digamma(row))
            gamma = jnp.ones((d_l, k), jnp.float32)

            def estep(gamma, _):
                ee_theta = _exp_dirichlet_expectation(gamma)
                phinorm = ee_theta @ ee_beta + 1e-30
                dot = (x_l / phinorm) @ ee_beta.T            # (D_l, K) partial over V
                if tp is not None and env.tp_size > 1:
                    dot = jax.lax.psum(dot, tp)
                gamma = cfg.alpha + ee_theta * dot
                return gamma, None

            gamma, _ = jax.lax.scan(estep, gamma, None, length=cfg.e_step_iters)
            ee_theta = _exp_dirichlet_expectation(gamma)
            phinorm = ee_theta @ ee_beta + 1e-30
            sstats = (ee_theta.T @ (x_l / phinorm)) * ee_beta  # (K, V_l)
            if dp and env.dp_size > 1:
                sstats = jax.lax.psum(sstats, dp)   # <- Alg.1 merge as psum
            return cfg.eta + sstats, None

        lam_l, _ = jax.lax.scan(outer, lam_l, None, length=iters)
        return lam_l

    if env.dp_size == 1 and env.tp_size == 1:
        return local(x, key)
    return jax.shard_map(
        local, mesh=env.mesh,
        in_specs=(P(dp, tp), P()),
        out_specs=P(None, tp),
        check_vma=False,
    )(x, key)
