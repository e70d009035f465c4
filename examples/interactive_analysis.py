"""End-to-end interactive topic-exploration session (the paper's §VI.C
usage scenario, driver form) — on the unified session API.

Simulates an analyst (Oliver) exploring a geo-tagged review corpus:
a sequence of ad-hoc range queries with different latency/accuracy
preferences (alpha), a union-of-intervals query over two disjoint
districts, a batch of queries optimized together (Alg. 4, with
shared costs reported at the batch level), a node failure recovered
by local retraining, and an elastic repartition — all against one
growing model store, with every query answered at interactive speed
once coverage builds up.

    PYTHONPATH=src python examples/interactive_analysis.py
"""
import time

import numpy as np

from repro.api import Interval, MLegoSession, QuerySpec
from repro.configs.lda_default import LDAConfig
from repro.core.lda import log_predictive_probability
from repro.data.corpus import doc_term_matrix, make_corpus, train_test_split
from repro.distributed.elastic import (
    apply_repartition,
    plan_repartition,
    recover_failed,
)


def main():
    cfg = LDAConfig(n_topics=16, vocab_size=600, max_iters=20,
                    e_step_iters=10)
    corpus, _ = make_corpus(2000, cfg.vocab_size, cfg.n_topics,
                            mean_doc_len=40, seed=42)
    train, test = train_test_split(corpus, test_frac=0.1)
    x_test = doc_term_matrix(test)
    session = MLegoSession(train, cfg, kind="vb")
    lpp = lambda beta: log_predictive_probability(beta, x_test)

    print("== session: exploratory range queries ==")
    script = [
        (Interval(0.0, 400.0), 0.0, "first look at district A (speed)"),
        (Interval(300.0, 900.0), 0.0, "pan east"),
        (Interval(0.0, 900.0), 0.5, "zoom out, balanced"),
        (Interval(100.0, 800.0), 0.8, "re-check, accuracy-leaning"),
        (Interval(0.0, 2000.0), 0.0, "whole city, fast"),
    ]
    for q, alpha, label in script:
        t0 = time.perf_counter()
        rep = session.submit(QuerySpec(sigma=q, alpha=alpha))
        dt = time.perf_counter() - t0
        print(f"  [{label:34s}] q={q.lo:6.0f}..{q.hi:6.0f} a={alpha}: "
              f"{dt*1e3:7.1f}ms  plan={rep.n_reused} models "
              f"+{rep.n_trained_tokens:6d} tok  lpp={lpp(rep.beta):.3f}")
    print(f"  store: {len(session.store)} models")

    print("\n== union predicate: districts A and C, one query ==")
    rep = session.submit(QuerySpec(
        sigma=[Interval(0.0, 400.0), Interval(1400.0, 1800.0)], alpha=0.5))
    print(f"  components={len(rep.plans)} merged={rep.n_merged} parts "
          f"+{rep.n_trained_tokens} tok  lpp={lpp(rep.beta):.3f}")

    print("\n== batch of three queries (Alg. 4 shared training) ==")
    batch = [Interval(900.0, 1500.0), Interval(1200.0, 1900.0),
             Interval(1000.0, 1700.0)]
    t0 = time.perf_counter()
    br = session.submit_many([QuerySpec(sigma=q) for q in batch])
    dt = time.perf_counter() - t0
    print(f"  {len(br)} queries in {dt*1e3:.1f}ms; "
          f"benefit={br.benefit:.4f} (saved training), "
          f"naive={br.opt.naive_time:.4f} shared={br.opt.total_time:.4f}")
    print(f"  batch costs: search {br.shared_search_s*1e3:.1f}ms + train "
          f"{br.shared_train_s*1e3:.1f}ms shared; per-query merges "
          + " ".join(f"{r.merge_s*1e3:.1f}ms" for r in br))

    print("\n== node failure: range [400, 800) models lost ==")
    lost = [m for m in session.store.models()
            if Interval(400.0, 800.0).contains(m.o)]
    for m in lost:
        session.store.remove(m.model_id)
    t0 = time.perf_counter()
    fresh = recover_failed(session.store, [Interval(400.0, 800.0)],
                           session.train_range)
    print(f"  retrained {len(fresh)} gap models in "
          f"{time.perf_counter()-t0:.2f}s (only the lost ranges)")

    print("\n== elastic scale-out: repartition store to 4 workers ==")
    parts = plan_repartition(session.store, Interval(0.0, 2000.0), 4)
    worker_models = apply_repartition(parts, session.store, cfg,
                                      session.train_range)
    for w, m in sorted(worker_models.items()):
        print(f"  worker {w}: span {m.o.lo:6.0f}..{m.o.hi:6.0f} "
              f"({m.n_docs} docs merged, lpp covered)")

    print("\nsession complete — every repeat query was answered from the "
          "store at millisecond scale.")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
