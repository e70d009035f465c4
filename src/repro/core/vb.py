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


def _psi_parts(x):
    """(z, r) with ψ(x) = log z + r, for x > 0.

    ψ(x) = ψ(x + 4) − Σ_{i<4} 1/(x + i), the four reciprocals summed in
    pairs (1/a + 1/b = (a + b)/(ab): one division a pair), and ψ(z) at
    z = x + 4 by its asymptotic series, whose first omitted term is
    below 2e-9 there.  No reflection branch: every argument here (λ ≥ η,
    γ ≥ α, their sums) is positive."""
    shift = 0.0
    for i in (0.0, 2.0):
        a = x + i
        b = a + 1.0
        shift = shift + (a + b) / (a * b)
    z = x + 4.0
    inv = 1.0 / z
    inv2 = inv * inv
    # ψ(z) − log z ≈ −1/(2z) − 1/(12z²) + 1/(120z⁴) − 1/(252z⁶)
    #                + 1/(240z⁸) − 1/(132z¹⁰)
    series = -0.5 * inv - inv2 * (1.0 / 12.0 - inv2 * (
        1.0 / 120.0 - inv2 * (1.0 / 252.0 - inv2 * (
            1.0 / 240.0 - inv2 / 132.0))))
    return z, series - shift


def _digamma(x):
    """ψ(x) for x > 0."""
    z, r = _psi_parts(x)
    return jnp.log(z) + r


def _exp_dirichlet_expectation(x, total=None):
    """exp(E[log p]) for Dirichlet rows of positive x: exp(ψ(x) − ψ(Σx)),
    taken as z·exp(r − ψ(Σx)) to save the log.  ``total`` stands in for
    the row sums Σx (a V-sharded λ passes its global ones)."""
    if total is None:
        total = x.sum(-1, keepdims=True)
    z, r = _psi_parts(x)
    return z * jnp.exp(r - _digamma(total))


def vb_estep(x, exp_elog_beta, gamma0, alpha: float, n_iters: int):
    """Coordinate-ascent E-step over a doc-block.

    x:              (D, V) counts, f32
    exp_elog_beta:  (K, V) f32
    gamma0:         (D, K) f32 initial document-topic Dirichlet params
    Returns (gamma, sstats) with sstats (K, V) = Σ_d n_dw φ_dwk
    (already multiplied by expElogbeta).
    """
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
    can tell them apart.

    On the kernel route x never changes inside the fit, so x and γ₀ are
    padded to the kernel's layout once, before the loop; each step
    computes E[β] over the true (K, V) entries only and pads it."""
    k = cfg.n_topics
    d, v = x.shape
    with jax.named_scope("mlego.vb_init"):
        lam0 = jax.random.gamma(key, 100.0, (k, v), jnp.float32) * 0.01
        gamma0 = jnp.ones((d, k), jnp.float32)
        if use_kernel:
            from repro.kernels.vb_estep import ops as _ops
            dp, vp, kp = _ops.padded_dims(d, v, k)
            x = jnp.pad(x, ((0, dp - d), (0, vp - v)))
            gamma0 = jnp.pad(gamma0, ((0, dp - d), (0, kp - k)),
                             constant_values=cfg.alpha)

    def outer(lam, _):
        with jax.named_scope("mlego.vb_expect"):
            exp_elog_beta = _exp_dirichlet_expectation(lam)
            if use_kernel:
                # pad topics get ~0 (tiny positive keeps phinorm finite)
                exp_elog_beta = jnp.pad(exp_elog_beta,
                                        ((0, kp - k), (0, vp - v)),
                                        constant_values=1e-30)
        if use_kernel:
            _, sstats = _ops.vb_estep_padded(x, exp_elog_beta, gamma0,
                                             cfg.alpha, cfg.e_step_iters)
            sstats = sstats[:k, :v]
        else:
            _, sstats = vb_estep(x, exp_elog_beta, gamma0, cfg.alpha,
                                 cfg.e_step_iters)
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
            ee_beta = _exp_dirichlet_expectation(lam_l, row)
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
