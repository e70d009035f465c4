"""Seeded, vectorized LDA corpus generator and the store built from it.

The generator draws a corpus with the shape of a public bag-of-words
collection (documents, vocabulary, mean document length) from an LDA
model with a Zipf-tailed vocabulary:

* topics: beta_k ~ Dirichlet(c * p), p a Zipf(s) law over the
  vocabulary (word id = frequency rank), c = topic_concentration * V;
* document lengths: log-normal with the source's mean and a stated
  sigma, at least one token;
* document mixtures: theta_d ~ Dirichlet(doc_alpha);
* ordering: documents in time order, attr = document index.

Every draw is vectorized NumPy over float64 cumulative tables, so a
corpus of some millions of tokens takes seconds.  The generator's own
topic assignments ``z`` are kept: the store is built from them as
per-partition (topic, word) counts, and the correctness reference
recomputes merged statistics from the same counts.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Synthetic:
    """A generated corpus: CSR tokens in document order plus the
    generator's topic assignment of every token."""

    words: np.ndarray          # int32 (T,)
    topics: np.ndarray         # int32 (T,)
    doc_ids: np.ndarray        # int32 (T,)
    doc_offsets: np.ndarray    # int64 (D + 1,)
    n_topics: int
    vocab_size: int

    @property
    def n_docs(self) -> int:
        return len(self.doc_offsets) - 1

    @property
    def n_tokens(self) -> int:
        return int(self.doc_offsets[-1])

    def token_range(self, d0: int, d1: int) -> tuple:
        return int(self.doc_offsets[d0]), int(self.doc_offsets[d1])

    def counts(self, d0: int, d1: int, dtype=np.float64) -> np.ndarray:
        """(K, V) (topic, word) counts of documents [d0, d1)."""
        t0, t1 = self.token_range(d0, d1)
        k, v = self.n_topics, self.vocab_size
        flat = self.topics[t0:t1].astype(np.int64) * v + self.words[t0:t1]
        return np.bincount(flat, minlength=k * v).reshape(k, v).astype(
            dtype)

    def doc_term(self, d0: int, d1: int, rows: int = 0) -> np.ndarray:
        """Dense (max(rows, d1 - d0), V) float32 document-term counts;
        rows past d1 - d0 are zero."""
        t0, t1 = self.token_range(d0, d1)
        n = max(rows, d1 - d0)
        flat = (self.doc_ids[t0:t1].astype(np.int64) - d0) * \
            self.vocab_size + self.words[t0:t1]
        return np.bincount(flat, minlength=n * self.vocab_size).reshape(
            n, self.vocab_size).astype(np.float32)


def zipf_law(v: int, s: float) -> np.ndarray:
    p = np.arange(1, v + 1, dtype=np.float64) ** -s
    return p / p.sum()


def _row_cdf(probs: np.ndarray) -> np.ndarray:
    """Row-wise cumulative tables, each row offset by its index so that
    one flat sorted array answers every row: the value r + u lands in
    row r."""
    cdf = np.cumsum(probs, axis=1)
    cdf /= cdf[:, -1:]
    cdf[:, -1] = 1.0
    cdf += np.arange(probs.shape[0], dtype=np.float64)[:, None]
    return cdf.ravel()


def _draw_rows(rng, cdf_flat: np.ndarray, rows: np.ndarray,
               width: int) -> np.ndarray:
    """One categorical draw per entry of ``rows`` from that row's law."""
    u = rng.random(rows.shape[0])
    idx = np.searchsorted(cdf_flat, rows + u, side="right")
    return np.minimum(idx - rows.astype(np.int64) * width, width - 1)


def generate(seed: int, n_docs: int, vocab_size: int, n_topics: int,
             mean_doc_len: float, doc_len_sigma: float, zipf_s: float,
             topic_concentration: float, doc_alpha: float) -> Synthetic:
    rng = np.random.default_rng(seed)
    k, v = n_topics, vocab_size
    shape = topic_concentration * v * zipf_law(v, zipf_s)
    g = rng.standard_gamma(np.broadcast_to(shape, (k, v)))
    g += 1e-300
    beta = g / g.sum(axis=1, keepdims=True)

    mu = np.log(mean_doc_len) - doc_len_sigma ** 2 / 2.0
    lengths = np.maximum(1, np.rint(
        rng.lognormal(mu, doc_len_sigma, n_docs))).astype(np.int64)
    offsets = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    doc_ids = np.repeat(np.arange(n_docs, dtype=np.int32), lengths)

    theta = rng.standard_gamma(doc_alpha, (n_docs, k)) + 1e-300
    theta /= theta.sum(axis=1, keepdims=True)
    topics = _draw_rows(rng, _row_cdf(theta), doc_ids, k).astype(np.int32)
    del theta
    words = _draw_rows(rng, _row_cdf(beta), topics, v).astype(np.int32)
    return Synthetic(words=words, topics=topics, doc_ids=doc_ids,
                     doc_offsets=offsets, n_topics=k, vocab_size=v)


def from_config(corpus_cfg: dict, model_cfg: dict, seed: int) -> Synthetic:
    return generate(seed, corpus_cfg["n_docs"], model_cfg["vocab_size"],
                    model_cfg["n_topics"], corpus_cfg["mean_doc_len"],
                    corpus_cfg["doc_len_sigma"], corpus_cfg["zipf_s"],
                    corpus_cfg["topic_concentration"],
                    corpus_cfg["doc_alpha"])
