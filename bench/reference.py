"""Plain references for what an MLego answer must equal.

Nothing here imports the system under test.  The merge reference works
from the generator's own counts in float64; the VB reference is batch
mean-field VB for LDA (Blei et al. 2003; Hoffman et al. 2010) written
out in ``jax.numpy``: a trained gap model is judged by how far one more
VB step on its own documents moves it.  The merge's control sums in
bfloat16, one step below the float32 the merge states; the VB
control's matmul operands are one step below those the configuration
states (float8 below bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def topics_vb(lam: np.ndarray) -> np.ndarray:
    """Posterior-mean topics of Dirichlet parameters."""
    return lam / lam.sum(axis=1, keepdims=True)


def topics_gs(nkv: np.ndarray, eta: float) -> np.ndarray:
    """Smoothed topics of topic-word counts."""
    return (nkv + eta) / (nkv.sum(axis=1, keepdims=True)
                          + nkv.shape[1] * eta)


def merged_topics(route: str, eta: float, parts, low: bool = False
                  ) -> np.ndarray:
    """The topics of a plan merge, in float64.

    ``parts`` are the merged models' statistics above the prior: counts
    for stored partitions and Gibbs gaps, lambda - eta for VB gaps (the
    stored partitions may come summed, as one part).  ``low`` is the
    control: every model's statistic and the running sum in bfloat16."""
    if low:
        acc = jnp.zeros(np.shape(parts[0]), jnp.bfloat16)
        for p in parts:
            acc = acc + jnp.asarray(p, jnp.bfloat16)
        acc = np.asarray(acc, np.float64)
    else:
        acc = np.zeros(np.shape(parts[0]), np.float64)
        for p in parts:
            acc += np.asarray(p, np.float64)
    if route == "vb":
        return topics_vb(acc + eta)
    return topics_gs(acc, eta)


def _exp_dirichlet(x):
    return jnp.exp(jax.scipy.special.digamma(x)
                   - jax.scipy.special.digamma(x.sum(-1, keepdims=True)))


# Operand types of the VB matmuls, and the control's for each: one
# precision step below the stated one.  The configuration states its
# operands (``model.precision.matmul_operands``); the enron deployment
# states bfloat16 operands with float32 sums, JAX's default matmul
# precision on a TPU, which MLego's VB route runs at.
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _round(a, operands: str):
    """``a`` rounded to the operand type (a float8 value as bfloat16,
    which holds it exactly), and the scale to multiply the product by.
    float8 has too little range for these operands, so each tensor is
    scaled to its largest value first, as a float8 matmul would be."""
    t = jnp.dtype(operands)
    if t.itemsize > 1:
        return a.astype(t), jnp.float32(1.0)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / float(
        jnp.finfo(t).max)
    return (a / scale).astype(t).astype(jnp.bfloat16), scale


def _dot(a, b, operands: str):
    """a @ b with both operands rounded to ``operands``, float32 sums."""
    (ra, sa), (rb, sb) = _round(a, operands), _round(b, operands)
    return jnp.dot(ra, rb, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32) * (sa * sb)


def _vb_outer(x, lam, k, alpha, eta, e_iters, operands):
    """One VB step: E-step from gamma = 1 against E[log beta] of lam,
    then lam' = eta + sufficient statistics.  Zero rows of x add
    nothing."""
    eeb = _exp_dirichlet(lam)

    def inner(gamma, _):
        eet = _exp_dirichlet(gamma)
        phinorm = _dot(eet, eeb, operands) + 1e-30
        return alpha + eet * _dot(x / phinorm, eeb.T, operands), None

    gamma, _ = jax.lax.scan(inner, jnp.ones((x.shape[0], k), jnp.float32),
                            None, length=e_iters)
    eet = _exp_dirichlet(gamma)
    phinorm = _dot(eet, eeb, operands) + 1e-30
    return eta + _dot(eet.T, x / phinorm, operands) * eeb


@functools.partial(jax.jit, static_argnames=("k", "e_iters", "operands"))
def vb_step(x, lam, *, k: int, alpha: float, eta: float, e_iters: int,
            operands: str):
    return _vb_outer(x, lam, k, alpha, eta, e_iters, operands)


@functools.partial(jax.jit, static_argnames=("k", "max_iters", "e_iters",
                                             "operands"))
def vb_fit(x, key, *, k: int, alpha: float, eta: float, max_iters: int,
           e_iters: int, operands: str):
    """Batch VB: ``max_iters`` steps from lambda_0 = Gamma(100, 0.01)."""
    lam0 = jax.random.gamma(key, 100.0, (k, x.shape[1]), jnp.float32) * 0.01

    def outer(lam, _):
        return _vb_outer(x, lam, k, alpha, eta, e_iters, operands), None

    lam, _ = jax.lax.scan(outer, lam0, None, length=max_iters)
    return lam


def stand_in(name: str, max_iters: int, e_iters: int, operands: str
             ) -> dict:
    """How the reference is fitted when it is put in the program's
    place: the control, one precision step below the stated
    ``operands``, or a planted fault, a fit that trained less than the
    configuration states."""
    return {"control": dict(max_iters=max_iters, e_iters=e_iters,
                            operands=LOWER[operands]),
            "fewer_steps": dict(max_iters=max_iters // 5, e_iters=e_iters,
                                operands=operands),
            "fewer_e_iters": dict(max_iters=max_iters, e_iters=e_iters // 4,
                                  operands=operands)}[name]


STAND_INS = ("control", "fewer_steps", "fewer_e_iters")


def vb_mass_err(x, lam, eta: float) -> float:
    """How far a gap model's statistic above the prior is from holding
    every token of its documents once: a VB step's sufficient
    statistics sum, over topics, to each word's count.  The relative L1
    distance between those sums and the word counts; a model that left
    documents out, counted them twice, or learned nothing reads near 1
    or more."""
    n = np.asarray(x, np.float64).sum(axis=0)
    got = (np.asarray(lam, np.float64) - eta).sum(axis=0)
    return float(np.abs(got - n).sum() / max(n.sum(), 1.0))


def vb_residual(x, lam, *, k: int, alpha: float, eta: float, e_iters: int,
                operands: str) -> float:
    """How far a trained gap model is from a fixed point of VB on its
    own documents: one more step, at the stated precision, relative to
    the model's statistic above the prior (Frobenius)."""
    lam = jnp.asarray(lam, jnp.float32)
    nxt = vb_step(x, lam, k=k, alpha=alpha, eta=eta, e_iters=e_iters,
                  operands=operands)
    a, b = np.asarray(nxt, np.float64), np.asarray(lam, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b - eta),
                                            1e-30))
