"""``core.vb``'s ψ for positive arguments, its Dirichlet expectation, and
``vb_fit`` against the formulation it replaced: XLA's digamma for E[β]
and the kernel wrapper padding x and E[β] on every VB step."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.special import digamma as digamma64

from repro.configs.lda_default import LDAConfig
from repro.core import vb
from repro.data.corpus import doc_term_matrix, make_corpus
from repro.kernels.vb_estep import ops
from repro.kernels.vb_estep.ref import exp_dirichlet_expectation as eeb_xla
from repro.kernels.vb_estep.ref import vb_estep_ref

RNG = np.random.default_rng(7)


def test_digamma_matches_float64_as_well_as_xla():
    x = np.logspace(-2, 6, 200_001).astype(np.float32)
    want = digamma64(x.astype(np.float64))
    err = np.abs(np.asarray(jax.jit(vb._digamma)(x), np.float64) - want).max()
    xla = np.abs(np.asarray(jax.jit(jax.scipy.special.digamma)(x),
                            np.float64) - want).max()
    assert err <= 1.5 * xla, (err, xla)


def test_exp_dirichlet_expectation_matches_xla_digamma():
    eta, k, v = 0.01, 50, 4000
    lam = eta + RNG.gamma(0.3, 40.0, (k, v))
    lam[:, RNG.random(v) < 0.3] = eta             # words no document has
    lam[0] = eta + RNG.gamma(2.0, 500.0, v)       # a row summing to ~4e6
    lam[1] = eta                                  # a row at the prior
    lam = jnp.asarray(lam, jnp.float32)
    assert float(lam.sum(-1).max()) > 1e6
    got = np.asarray(jax.jit(vb._exp_dirichlet_expectation)(lam), np.float64)
    want = np.asarray(jax.jit(eeb_xla)(lam), np.float64)
    # exp(ψ(η) − ψ(Σλ)) at η = 0.01 lies near or below float32's normal
    # range (ψ(0.01) ≈ −100.6), where exp's result flushes to zero before
    # the new form multiplies it by x + 4.  Below 1e-30, the floor the
    # E-step adds to every phinorm, an entry changes nothing: there both
    # forms must read below it.
    live = want >= 1e-30
    assert live.mean() > 0.5
    assert np.abs(got[live] / want[live] - 1.0).max() <= 1e-4
    assert (got[~live] < 1e-30).all()


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel"))
def _fit_as_before(x, key, cfg, use_kernel):
    """The fit as it was: XLA's digamma for E[β] and, on the kernel
    route, the wrapper that pads x and E[β] on every step."""
    k = cfg.n_topics
    d, v = x.shape
    lam0 = jax.random.gamma(key, 100.0, (k, v), jnp.float32) * 0.01
    gamma0 = jnp.ones((d, k), jnp.float32)

    def outer(lam, _):
        eeb = eeb_xla(lam)
        if use_kernel:
            _, s = ops.vb_estep(x, eeb, gamma0, cfg.alpha, cfg.e_step_iters,
                                interpret=True)
        else:
            _, s = vb_estep_ref(x, eeb, gamma0, cfg.alpha, cfg.e_step_iters)
        return cfg.eta + s, None

    lam, _ = jax.lax.scan(outer, lam0, None, length=cfg.max_iters)
    return lam


def _corpus(d, v, k):
    """x drawn from LDA with k topics.  On structureless counts (Poisson
    noise) VB's fixed point is not stable: there the previous
    formulation's kernel and jnp routes, which differ only in the order
    of their sums, end 100 steps up to 7e-4 apart, and any change in
    rounding can carry a fit further."""
    cfg = LDAConfig(n_topics=k, vocab_size=v, max_iters=100, e_step_iters=5)
    corpus, _ = make_corpus(d, v, k, mean_doc_len=60, seed=0)
    x = jnp.asarray(doc_term_matrix(corpus), jnp.float32)
    return x, jax.random.PRNGKey(0), cfg


SHAPES = [(40, 300, 100), (135, 1100, 20)]


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("d,v,k", SHAPES)
def test_vb_fit_matches_the_previous_formulation(d, v, k, use_kernel):
    x, key, cfg = _corpus(d, v, k)
    one = LDAConfig(n_topics=k, vocab_size=v, max_iters=1,
                    e_step_iters=cfg.e_step_iters)
    got = np.asarray(vb.vb_fit(x, key, one, use_kernel=use_kernel))
    want = np.asarray(_fit_as_before(x, key, one, use_kernel))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * want.max())
    got = np.asarray(vb.vb_fit(x, key, cfg, use_kernel=use_kernel))
    want = np.asarray(_fit_as_before(x, key, cfg, use_kernel))
    assert np.abs(got - want).max() / want.max() <= 1e-3


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def test_vb_fit_pads_x_once_per_fit_not_once_per_step():
    d, v, k = 40, 300, 100
    x, key, cfg = _corpus(d, v, k)
    assert ops.padded_dims(d, v, k)[:2] != (d, v)     # x needs padding
    jaxpr = jax.make_jaxpr(functools.partial(
        vb.vb_fit, cfg=cfg, use_kernel=True))(x, key).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.max_iters]
    assert len(scans) == 1
    pads = [e for e in _eqns(jaxpr) if e.primitive.name == "pad"
            and e.invars[0].aval.shape == (d, v)]
    assert pads, "x is padded before the loop"
    in_loop = [e for e in _eqns(scans[0].params["jaxpr"].jaxpr)
               if e.primitive.name == "pad"
               and e.invars[0].aval.shape == (d, v)]
    assert not in_loop
