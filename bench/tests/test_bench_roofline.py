"""Roofline counts against hand-worked numbers."""
import pytest

from bench import harness


def test_merge_topics_cost():
    rl = harness.load_module("roofline", "merge_topics")
    flops, bytes_ = rl.cost(64, 100, 28102)
    # 64 parts of 100 x 28102 float32 read, one written
    assert bytes_ == 65 * 100 * 28102 * 4 == 730652000
    assert flops == 2 * 64 * 100 * 28102 == 359705600


def test_vb_estep_cost():
    rl = harness.load_module("roofline", "vb_estep")
    flops, bytes_ = rl.cost(448, 100, 28102, 20)
    # 21 passes of two (448 x 100 x 28102) contractions
    assert flops == 4 * 448 * 100 * 28102 * 21 == 105753446400
    assert bytes_ == 4 * (448 * 28102 + 2 * 100 * 28102)


class _Trace:
    window_s = 2.0


class _Span:
    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs
        self.t0, self.t1 = 0.0, 1.0


def test_step_mfu_counts_merges_and_gaps():
    ctx = harness.LayerContext(
        spans=[], traced_spans=[
            _Span("kernel.launch", op="merge_topics", n_parts=10),
            _Span("train", lo=0.0, hi=64.0)],
        trace=_Trace(), config={"model": {
            "n_topics": 100, "vocab_size": 1000, "route": "vb",
            "e_step_iters": 20, "max_iters": 100}},
        peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        compiles_in_window=0)
    got = harness.load_module("metrics", "step_mfu").read(ctx)
    flops = 2 * 10 * 100 * 1000 + 4 * 64 * 100 * 1000 * 21 * 100
    assert got == pytest.approx(100 * flops / (2.0 * 1e12))
