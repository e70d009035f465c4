"""``correct`` comes out false when the timed path is broken underneath
a whole run (the harness's look for a chip skipped), and when the
control, the reference one precision step down, stands in the
program's place."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.tests import tiny
from repro.api.backend import DeviceBackend
from repro.data.corpus import doc_term_matrix

CELLS = ["enron-vb.explore", "enron-vb.covered", "nytimes-gs.covered"]


class HalfParts(DeviceBackend):
    """Half of the parts left out of every merge."""

    def merge(self, parts, kind, cfg):
        return super().merge(list(parts)[:max(1, len(parts) // 2)], kind,
                             cfg)


class AlteredAnswer(DeviceBackend):
    """Two words of one topic swapped in every answer."""

    def merge(self, parts, kind, cfg):
        beta = np.array(super().merge(parts, kind, cfg))
        beta[0, [0, 1]] = beta[0, [1, 0]]
        return beta


class UnchangedState(DeviceBackend):
    """Gap training returns its initial state unchanged."""

    def trainer(self, kind):
        def fit(corpus, cfg, key):
            lam0 = jax.random.gamma(key, 100.0, (cfg.n_topics,
                                                 cfg.vocab_size),
                                    jnp.float32) * 0.01
            return {"lam": np.asarray(lam0)}
        return fit if kind == "vb" else super().trainer(kind)


class HalfGapDocs(DeviceBackend):
    """Gap training sees the first half of its documents only."""

    def trainer(self, kind):
        fit = super().trainer(kind)

        def half(corpus, cfg, key):
            mid = corpus.attr[max(1, corpus.n_docs // 2) - 1] + 0.5
            return fit(corpus.subset(corpus.attr[0], mid), cfg, key)
        return half


class FewerEStepIters(DeviceBackend):
    """Gap training runs a quarter of the E-step iterations it states."""

    def trainer(self, kind):
        fit = super().trainer(kind)

        def fewer(corpus, cfg, key):
            return fit(corpus, dataclasses.replace(
                cfg, e_step_iters=cfg.e_step_iters // 4), key)
        return fewer


class Control(DeviceBackend):
    """The reference one precision step lower in the program's place:
    merges summed in bfloat16, VB matmuls one step below the tiny
    cell's stated operands."""

    def merge(self, parts, kind, cfg):
        stat, base = ("lam", cfg.eta) if kind == "vb" else ("delta_nkv", 0)
        return reference.merged_topics(
            kind, cfg.eta, [np.asarray(m.theta[stat], np.float64) - base
                            for m in parts], low=True)

    def trainer(self, kind):
        def fit(corpus, cfg, key):
            lam = reference.vb_fit(
                doc_term_matrix(corpus), key, k=cfg.n_topics,
                alpha=cfg.alpha, eta=cfg.eta, max_iters=cfg.max_iters,
                e_iters=cfg.e_step_iters,
                operands=reference.LOWER[tiny.OPERANDS])
            return {"lam": np.asarray(lam)}
        return fit if kind == "vb" else super().trainer(kind)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    r = tiny.run(cell, monkeypatch)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell,fault,check", [
    ("enron-vb.explore", HalfParts, "merge_l1"),
    ("enron-vb.explore", AlteredAnswer, "merge_l1"),
    ("enron-vb.explore", UnchangedState, "gap_residual"),
    ("enron-vb.explore", UnchangedState, "gap_mass_err"),
    ("enron-vb.explore", HalfGapDocs, "gap_mass_err"),
    ("enron-vb.explore", FewerEStepIters, "gap_residual"),
    ("enron-vb.covered", HalfParts, "merge_l1"),
    ("enron-vb.covered", AlteredAnswer, "merge_l1"),
    ("nytimes-gs.covered", HalfParts, "merge_l1"),
    ("nytimes-gs.covered", AlteredAnswer, "merge_l1"),
])
def test_fault_is_caught(cell, fault, check, monkeypatch):
    r = tiny.run(cell, monkeypatch, base=fault)
    assert not r["correct"]
    c = r["checks"][check]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell, monkeypatch):
    r = tiny.run(cell, monkeypatch, base=Control)
    assert not r["correct"], r["checks"]
