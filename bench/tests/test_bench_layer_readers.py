"""The readers of the gap-training phases, of device time outside the
E-step kernel and of host-to-device bytes, on hand-made spans and a
hand-made reduced trace."""
from dataclasses import dataclass, field

import pytest

from bench import harness
from bench import trace_reduce as tr


@dataclass
class _Span:
    name: str
    t0: float
    t1: float
    trace_id: str = "t1"
    attrs: dict = field(default_factory=dict)

    @property
    def duration_s(self):
        return self.t1 - self.t0


def _ctx(spans=(), trace=None):
    spans = list(spans)
    return harness.LayerContext(
        spans=spans, traced_spans=spans, trace=trace,
        config={"model": {"n_topics": 100, "vocab_size": 1000}},
        peak={}, compiles_in_window=0)


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("phase", ["densify", "fit", "fetch"])
def test_gap_phase_sums_per_query_then_means(phase):
    other = {"densify": "fit", "fit": "fetch", "fetch": "densify"}[phase]
    spans = [_Span(f"train.{phase}", 0.0, 0.001, "a"),
             _Span(f"train.{phase}", 0.5, 0.502, "a"),     # a's 2nd gap
             _Span(f"train.{phase}", 1.0, 1.003, "b"),
             _Span(f"train.{other}", 2.0, 2.5, "b"),
             _Span("train", 0.0, 3.0, "c")]
    # (1 + 2) ms for query a, 3 ms for query b
    assert _read(f"gap_{phase}_ms", _ctx(spans)) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", ["gap_densify_ms", "gap_fit_ms",
                                    "gap_fetch_ms", "vb_outside_estep_ms",
                                    "h2d_mb_per_query"])
def test_readers_give_nothing_without_spans(metric):
    red = tr.reduce([], 0.0, (0.0, 1.0))
    assert _read(metric, _ctx([], red)) is None


def _trace():
    # (name, start_ns, dur_ns, device), offset 0: ns * 1e-9 is seconds
    ops = [("%mlego.vb_estep.1 = custom-call(...)", 1_100_000_000,
            300_000_000, 0),
           ("%while.2 = while(...)", 1_000_000_000, 900_000_000, 0),
           ("%fusion.3 = fusion(...)", 1_500_000_000, 40_000_000, 0),
           # starts before the span, midpoint inside: counted whole
           ("%xor_reduce_fusion = fusion(...)", 960_000_000,
            100_000_000, 0),
           ("%pad.4 = pad(...)", 2_500_000_000, 10_000_000, 0),
           ("%call.5 = call(...)", 3_100_000_000, 50_000_000, 0),
           ("%add_add_fusion = fusion(...)", 3_200_000_000,
            20_000_000, 0)]
    return tr.reduce(ops, 0.0, (0.0, 4.0))


def test_vb_outside_estep_counts_only_non_kernel_ops_inside_fits():
    fits = [_Span("train.fit", 1.0, 2.0), _Span("train.fit", 3.0, 3.5),
            _Span("train", 0.9, 3.6)]
    # fit 1: fusion 40 ms + xor_reduce_fusion 100 ms; the kernel and
    # the while are left out.  fit 2: add_add_fusion 20 ms; the call
    # is control flow.  The pad lies outside both fits.
    got = _read("vb_outside_estep_ms", _ctx(fits, _trace()))
    assert got == pytest.approx((40 + 100 + 20) / 2)


def test_vb_outside_estep_needs_a_trace_and_a_fit():
    fits = [_Span("train.fit", 1.0, 2.0)]
    assert _read("vb_outside_estep_ms", _ctx(fits, None)) is None
    assert _read("vb_outside_estep_ms",
                 _ctx([_Span("train", 1.0, 2.0)], _trace())) is None


def _query(error=False, **attrs):
    return _Span("serve.query", 0.0, 1.0, attrs=dict(error=error, **attrs))


def test_h2d_bytes_per_answered_query():
    spans = [_Span("device.upload", 0.1, 0.2, attrs={"bytes": 3_000_000}),
             _Span("device.upload", 0.3, 0.4,
                   attrs={"bytes": 1_000_000, "warm": True}),
             _Span("train", 0.0, 0.5),
             _Span("train.fit", 0.1, 0.4, attrs={"bytes_in": 2_000_000}),
             _query(), _query(),
             _query(error=True),                   # failed: not answered
             _query(outcome="shed")]               # shed: not answered
    assert _read("h2d_mb_per_query", _ctx(spans)) == pytest.approx(3.0)


def test_h2d_gives_nothing_where_fits_carry_no_bytes():
    # gaps trained, but no train.fit span says what they uploaded
    spans = [_Span("device.upload", 0.1, 0.2, attrs={"bytes": 3_000_000}),
             _Span("train", 0.0, 0.5), _query()]
    assert _read("h2d_mb_per_query", _ctx(spans)) is None
    # no gap trained: the uploads alone are the whole count
    spans = [_Span("device.upload", 0.1, 0.2, attrs={"bytes": 3_000_000}),
             _query(), _query()]
    assert _read("h2d_mb_per_query", _ctx(spans)) == pytest.approx(1.5)
