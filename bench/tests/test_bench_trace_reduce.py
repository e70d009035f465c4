"""The trace reducer on small recorded and hand-made traces."""
import glob
import time

import pytest

from bench import trace_reduce as tr


def test_union_and_gaps():
    busy, gaps = tr.union_length([(1.0, 2.0), (1.5, 3.0), (4.0, 5.0),
                                  (9.0, 12.0)], 0.0, 10.0)
    assert busy == pytest.approx(2.0 + 1.0 + 1.0)
    assert gaps == [(0.0, 1.0), (3.0, 4.0), (5.0, 9.0)]


def _ops():
    # (name, start_ns, dur_ns, device) on the trace clock
    return [("%mlego.merge_topics.1 = f32[104,28160] custom-call(...)",
             1_000_000_000, 100_000_000, 0),
            ("%fusion.2 = f32[100,28102] fusion(...)", 1_200_000_000,
             50_000_000, 0),
            ("%mlego.vb_estep.7 = (f32[256,128]) custom-call(...)",
             1_300_000_000, 200_000_000, 0),
            ("%while.3 = (s32[]) while(...) mlego.vb_estep", 1_290_000_000,
             220_000_000, 0),
            ("%mlego.merge_topics.4 = f32[104,28160] custom-call(...)",
             1_900_000_000, 100_000_000, 0)]


def test_busy_idle_and_kernel_time():
    red = tr.reduce(_ops(), offset=10.0, window=(11.0, 12.0))
    assert red.busy_s == pytest.approx(0.47)
    assert red.window_s == pytest.approx(1.0)
    assert tr.kernel_seconds(red, ("mlego.merge_topics",)) == pytest.approx(0.2)
    assert tr.kernel_seconds(red, ("mlego.vb_estep",)) == pytest.approx(0.2)
    # only the ops inside the given intervals
    assert tr.kernel_seconds(red, ("mlego.merge_topics",),
                             within=[(10.9, 11.2)]) == pytest.approx(0.1)
    top = tr.top_ops(red)
    assert [t[0] for t in top] == ["mlego.merge_topics", "mlego.vb_estep",
                                   "fusion"]
    assert top[0][1] == pytest.approx(0.2)


def test_window_clips_ops():
    red = tr.reduce(_ops(), offset=10.0, window=(11.05, 11.95))
    assert tr.kernel_seconds(red, ("mlego.merge_topics",)) == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.05 + 0.05 + 0.22 + 0.05)


def test_gap_labels_pick_innermost_span():
    gaps = [(0.0, 1.0), (2.0, 2.5), (3.0, 3.2)]
    spans = [("queue.wait", 0.0, 0.9), ("plan", 0.4, 0.6),
             ("merge", 1.9, 2.6), ("kernel.launch", 2.1, 2.4)]
    got = tr.label_gaps(gaps, spans)
    assert got == pytest.approx({"plan": 1.0, "kernel.launch": 0.5,
                                 "none": 0.2})
    assert tr.top_gaps(got)[0] == ["plan", pytest.approx(1.0)]


def test_reads_a_recorded_trace(tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.SYNC):
        sync = time.perf_counter()
    jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    dev, host = tr.read_xplane(path[0])
    assert any(name == tr.SYNC for name, _, _ in host)
    off = tr.clock_offset(host, sync)
    (start,) = [s + d / 2 for n, s, d in host if n == tr.SYNC]
    assert start * 1e-9 + off == pytest.approx(sync)
    with pytest.raises(ValueError):
        tr.clock_offset([("other", 0, 1)], sync)
