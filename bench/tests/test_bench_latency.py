"""Latency is timed from when a query was due, and a query that failed
or never came counts as infinitely late."""
import math
import threading
import time
from concurrent.futures import Future

import pytest

from bench import harness


def test_percentile_counts_failures_as_late():
    st = harness.RunStats([0.01, 0.02, 0.03, math.inf], 3, 1.0, 0.0)
    assert st.percentile(50) == 0.02
    assert st.percentile(95) == math.inf
    st = harness.RunStats([0.05] * 19 + [math.inf], 19, 1.0, 0.0)
    assert st.percentile(95) == 0.05
    assert harness.RunStats([], 0, 1.0, 0.0).percentile(50) == math.inf


class _Report:
    backend, fallback_from, n_merged, materialized = "device", None, 1, []


class _SlowService:
    """Answers each query 50 ms after it was submitted, one at a time,
    and refuses the third."""

    def __init__(self):
        self.n = 0
        self.busy_until = 0.0

    def submit(self, spec, tenant):
        self.n += 1
        if self.n == 3:
            raise RuntimeError("shed")
        fut = Future()
        start = max(time.perf_counter(), self.busy_until)
        self.busy_until = start + 0.05
        threading.Timer(self.busy_until - time.perf_counter(),
                        lambda: fut.set_result(_Report())).start()
        return fut


def test_due_time_latency_charges_the_stall():
    win = harness.Window(_SlowService(), lambda q: q, keep_every=1, seed=1)
    t0 = time.perf_counter()
    q = harness.load_module("traffic", "sessions").Query("a", 0, 1)
    # all four due at once: the queue makes the later ones wait
    late = harness.open_loop(win, type("S", (), {"next": lambda s: q})(),
                             [0.0, 0.0, 0.0, 0.0], t0)
    win.drain(time.perf_counter() + 5)
    st = harness.run_stats(win.recs, time.perf_counter(), 1.0, 0.0)
    lat = sorted(st.latencies)
    assert lat[-1] == math.inf                      # the refused one
    assert lat[0] == pytest.approx(0.05, abs=0.03)
    assert lat[2] == pytest.approx(0.15, abs=0.05)  # waited for two
    assert late < 0.05
    assert sum(1 for r in win.recs if r.error) == 1


def test_expected_plan():
    assert harness.expected_plan(32, 600, 256) == ((256, 512),
                                                   [(32, 256), (512, 600)])
    assert harness.expected_plan(256, 768, 256) == ((256, 768), [])
    # no whole partition inside: one gap over the whole range
    assert harness.expected_plan(32, 480, 256) == ((0, 0), [(32, 480)])
