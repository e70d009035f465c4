"""Quickstart: one session, typed queries, growing reuse capital.

    PYTHONPATH=src python examples/quickstart.py

MLego in 60 seconds, through the unified session API (``repro.api``):

  1. Open an ``MLegoSession`` over a corpus — the session owns the
     dataset D, the model store, the cost model, and the RNG stream
     from the paper's Def. 1 query tuple q = {F, alpha, D, sigma, M}.
  2. Materialize LDA models for two time windows (offline capital).
  3. Submit a typed ``QuerySpec`` — predicate sigma, accuracy
     preference alpha, backend kind, plan-search method, and
     materialization policy — and get a ``QueryReport`` back: the
     query spanning both windows is answered *without retraining*
     (the paper's Fig. 1 scenario end to end).
  4. Submit a narrower query that is only partially covered: the
     planner reuses what it can, trains just the gap, and (policy
     ``persist``) materializes the fresh model so the *next* query is
     faster — the interactivity flywheel.
  5. Bonus over the legacy API: a union-of-intervals predicate is a
     single query.

The old ``QueryEngine.execute(interval, alpha)`` path still exists as
a deprecated shim; see src/repro/api/README.md for the migration
table.
"""
import numpy as np

from repro.api import Interval, MLegoSession, QuerySpec
from repro.configs.lda_default import LDAConfig
from repro.core.lda import log_predictive_probability
from repro.data.corpus import doc_term_matrix, make_corpus, train_test_split


def main():
    cfg = LDAConfig(n_topics=12, vocab_size=400, max_iters=25,
                    e_step_iters=10)
    corpus, _ = make_corpus(1000, cfg.vocab_size, cfg.n_topics,
                            mean_doc_len=40, seed=0)
    train, test = train_test_split(corpus, test_frac=0.1)
    x_test = doc_term_matrix(test)

    session = MLegoSession(train, cfg, kind="vb")

    print("== materializing models for two time windows ==")
    m1 = session.train_range(0.0, 500.0)
    m2 = session.train_range(500.0, 1000.0)
    print(f"  m1: {m1.o} ({m1.n_docs} docs)   m2: {m2.o} ({m2.n_docs} docs)")

    print("\n== analytic query over the union (alpha=0.5) ==")
    rep = session.submit(QuerySpec(sigma=Interval(0.0, 1000.0), alpha=0.5))
    print(f"  plan: models {rep.model_ids}, "
          f"trained {rep.n_trained_tokens} tokens, "
          f"search {rep.search_s*1e3:.1f}ms, merge {rep.merge_s*1e3:.1f}ms")
    print(f"  held-out lpp: {log_predictive_probability(rep.beta, x_test):.4f}")

    print("\n== top words per topic (first 3 topics) ==")
    for k in range(3):
        top = np.argsort(-rep.beta[k])[:8]
        print(f"  topic {k}: words {top.tolist()}")

    print("\n== a narrower ad-hoc query (partial coverage) ==")
    rep2 = session.submit(QuerySpec(sigma=Interval(250.0, 750.0), alpha=0.2))
    print(f"  plan: {rep2.model_ids} + {rep2.n_trained_tokens} "
          f"fresh tokens -> lpp "
          f"{log_predictive_probability(rep2.beta, x_test):.4f}")
    print(f"  store now holds {len(session.store)} models "
          f"({session.store.nbytes()/1e6:.1f} MB) — reuse capital grows")

    print("\n== union predicate: two disjoint windows, one query ==")
    rep3 = session.submit(QuerySpec(
        sigma=[Interval(0.0, 250.0), Interval(750.0, 1000.0)], alpha=0.5))
    print(f"  components: {len(rep3.plans)}, merged {rep3.n_merged} parts, "
          f"lpp {log_predictive_probability(rep3.beta, x_test):.4f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
