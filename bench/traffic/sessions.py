"""Analysts' range sessions over the ordered corpus.

One general generator; a workload's JSON file gives its parameters:

  tenants        number of analysts, each with a session state
  tenant_zipf    Zipf exponent of how often each analyst sends
  ops            probabilities of drill (halve the width around a
                 point in the range), pan (shift by a quarter width),
                 zoom_out (double the width), jump (new range of a
                 log-uniform width at a uniform position)
  width_parts    [min, max] range width, in partitions
  grid_docs      endpoints snap to this many documents

Every run draws the same pool of sessions from ``POOL_SEED``, and the
same inter-arrival gaps; the run's seed only interleaves the analysts
(each analyst's queries keep their session order) and orders the gaps.
So a run's work is the same from seed to seed.  An open loop's pool is
as long as its arrivals; a closed-loop client cycles through a pool of
``CLOSED_POOL`` queries.  Ranges are in document units over
[0, domain) with domain a whole number of partitions.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

import numpy as np

OPS = ("drill", "pan", "zoom_out", "jump")
POOL_SEED = 1207
CLOSED_POOL = 1024


@dataclass(frozen=True)
class Query:
    tenant: str
    lo: int           # first document
    hi: int           # one past the last document


class Sessions:
    """An endless query stream from one random stream: the pool's
    source."""

    def __init__(self, params: Dict, domain_docs: int, part_docs: int,
                 rng: np.random.Generator):
        self.grid = int(params["grid_docs"])
        if part_docs % self.grid or domain_docs % part_docs:
            raise ValueError("grid must divide the partition, and the "
                             "partition the domain")
        self.n = domain_docs // self.grid
        lo_p, hi_p = params["width_parts"]
        self.w_min = lo_p * part_docs // self.grid
        self.w_max = min(hi_p * part_docs // self.grid, self.n)
        self.rng = rng
        n_t = int(params["tenants"])
        self.tenants = [f"analyst{i:03d}" for i in range(n_t)]
        w = np.arange(1, n_t + 1, dtype=np.float64) ** -params["tenant_zipf"]
        self.tenant_p = w / w.sum()
        self.op_p = np.array([params["ops"][o] for o in OPS], np.float64)
        self.op_p /= self.op_p.sum()
        self.state: Dict[int, Tuple[int, int]] = {}

    def _clip(self, lo: int, w: int) -> Tuple[int, int]:
        w = max(self.w_min, min(self.w_max, w))
        return max(0, min(self.n - w, lo)), w

    def _jump(self) -> Tuple[int, int]:
        w = int(round(math.exp(self.rng.uniform(math.log(self.w_min),
                                                math.log(self.w_max)))))
        w = max(self.w_min, min(self.w_max, w))
        return int(self.rng.integers(0, self.n - w + 1)), w

    def next(self) -> Query:
        t = int(self.rng.choice(len(self.tenants), p=self.tenant_p))
        op = OPS[int(self.rng.choice(len(OPS), p=self.op_p))]
        if t not in self.state or op == "jump":
            lo, w = self._jump()
        else:
            lo, w = self.state[t]
            if op == "drill":
                c = lo + int(self.rng.integers(0, w))
                w2 = max(self.w_min, w // 2)
                lo, w = self._clip(c - w2 // 2, w2)
            elif op == "pan":
                step = max(1, w // 4) * (1 if self.rng.random() < 0.5
                                         else -1)
                lo, w = self._clip(lo + step, w)
            else:
                w2 = min(self.w_max, 2 * w)
                lo, w = self._clip(lo - (w2 - w) // 2, w2)
        self.state[t] = (lo, w)
        return Query(self.tenants[t], lo * self.grid, (lo + w) * self.grid)


class Pool:
    """A fixed pool of session queries, the analysts interleaved in the
    order a seed gives; each analyst's queries keep their order."""

    def __init__(self, queries: List[Query], rng: np.random.Generator):
        turns = [q.tenant for q in queries]
        queue: Dict[str, List[Query]] = {}
        for q in reversed(queries):
            queue.setdefault(q.tenant, []).append(q)
        self.queries = [queue[turns[i]].pop() for i in
                        rng.permutation(len(turns))]
        self.i = 0

    def next(self) -> Query:
        q = self.queries[self.i % len(self.queries)]
        self.i += 1
        return q


def stream(params: Dict, domain_docs: int, part_docs: int, seed: int,
           stream_id: int, n: int = 0) -> Pool:
    """Stream ``stream_id`` (a client) of a run with this seed: a pool
    of ``n`` queries, or of ``CLOSED_POOL`` where n is 0."""
    src = Sessions(params, domain_docs, part_docs,
                   np.random.default_rng([POOL_SEED, stream_id]))
    return Pool([src.next() for _ in range(n or CLOSED_POOL)],
                np.random.default_rng([seed, stream_id]))


def arrivals(params: Dict, rate_qps: float, seconds: float,
             seed: int) -> List[float]:
    """Poisson arrival offsets (s) in [0, seconds): the pool's gaps, in
    the order the seed gives."""
    rng = np.random.default_rng([POOL_SEED, 1 << 20])
    gaps, t = [], 0.0
    while t < seconds:
        gaps.append(rng.exponential(1.0 / rate_qps))
        t += gaps[-1]
    gaps = np.random.default_rng([seed, 1 << 20]).permutation(gaps)
    out = list(np.cumsum(gaps))
    return [float(x) for x in out if x < seconds]


def tenants(params: Dict) -> List[str]:
    return [f"analyst{i:03d}" for i in range(int(params["tenants"]))]


def warmup(params: Dict, domain_docs: int, part_docs: int
           ) -> Iterator[Tuple[int, int]]:
    """Ranges that meet every compiled shape the stream can ask for:
    each merge width (1 .. max + 1 parts: a range that is not aligned
    holds max - 1 whole partitions and two edge gaps) and each gap size
    (edge gaps below a partition, and single gaps of one to two
    partitions that hold no whole partition)."""
    b, g = part_docs, int(params["grid_docs"])
    lo_p, hi_p = params["width_parts"]
    hi_p = min(hi_p, domain_docs // b)
    for n in range(lo_p, hi_p + 1):
        yield 0, n * b
    if g < b:
        yield g, g + hi_p * b
        for gap in range(g, b, g):
            yield b - gap, 2 * b
        for w in range(b, 2 * b - g, g):
            yield g, g + w
