"""Observability layer: the tracing core (span nesting, ambient
context, Chrome export), the metrics registry (labels, exposition,
snapshot), and their integration through MLegoSession / MLegoService —
trace ids surviving coalescing and α-splits, retry instants on the
span tree, Prometheus exposition agreeing with the same-run
ServiceReport, the breaker fed from *direct* session use, per-query
train_device_ms attribution, the phase spans of gap training and of
the device merge, compile events as spans, and the mirror of spans
onto the profiler's clock under ``profile=True``."""
import glob
import json

import jax

import numpy as np
import pytest

from repro.testing.faults import FaultRule, injected

from repro.api import (
    Interval,
    MetricsRegistry,
    MLegoSession,
    QuerySpec,
    RetryPolicy,
    Tracer,
    TransientExecutionError,
)
from repro.configs.lda_default import LDAConfig
from repro.data.corpus import make_corpus, train_test_split
from repro.obs import trace as obs
from repro.serve import MLegoService, SLOPolicy

CFG = LDAConfig(n_topics=6, vocab_size=150, alpha=0.5, eta=0.05,
                max_iters=8, e_step_iters=5, gibbs_sweeps=6)


@pytest.fixture(scope="module")
def train():
    corpus, _ = make_corpus(300, CFG.vocab_size, CFG.n_topics,
                            mean_doc_len=30, seed=3)
    train, _ = train_test_split(corpus, test_frac=0.1, seed=1)
    return train


def _hi(train):
    return float(train.attr[-1]) + 1.0


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------

def test_tracer_span_nesting_and_ambient_context():
    tr = Tracer()
    with tr.span("root", "test") as root:
        with obs.span("child", "test", foo=1):
            obs.set_attrs(bar=2)
    spans = tr.spans()
    assert [s.name for s in spans] == ["root", "child"]
    child = spans[1]
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.attrs["foo"] == 1 and child.attrs["bar"] == 2
    assert root.t0 <= child.t0 and child.t1 <= root.t1


def test_ambient_helpers_are_noops_without_enclosing_span():
    # must neither raise nor leak state when no Tracer.span is active
    with obs.span("orphan", "test", x=1):
        obs.set_attrs(y=2)
    obs.instant("orphan.event", z=3)
    assert obs.current_tracer() is None
    assert obs.current_span() is None


def test_tracer_record_external_interval():
    tr = Tracer()
    tid = tr.new_trace_id()
    sid = tr.new_span_id()
    tr.record("queue.wait", "serve", 1.0, 1.5, trace_id=tid,
              span_id=sid, attrs={"tenant": "ana"})
    (s,) = tr.spans(trace_id=tid)
    assert s.name == "queue.wait" and s.span_id == sid
    assert s.t1 - s.t0 == pytest.approx(0.5)


def test_chrome_export_loads_and_carries_ids(tmp_path):
    tr = Tracer()
    with tr.span("root", "test"):
        with obs.span("child", "test"):
            obs.instant("tick", n=1)
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert {e["name"] for e in events} >= {"root", "child", "tick"}
    for e in events:
        assert e["ph"] in ("X", "i")
        assert e["ts"] >= 0                      # µs, rebased to epoch
        assert "trace_id" in e["args"]
    durs = [e for e in events if e["ph"] == "X"]
    assert all("dur" in e for e in durs)


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("root", "test"):
        obs.instant("tick")
    assert len(tr.spans()) == 0


def test_retry_lands_instant_on_ambient_span():
    pol = RetryPolicy(max_attempts=3, base_delay_s=0.0)
    tr = Tracer()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise TransientExecutionError("boom")
        return 7

    with tr.span("op", "test"):
        assert pol.run(flaky, site="test.site",
                       sleep=lambda s: None) == 7
    (ev,) = tr.spans(name="retry")
    assert ev.attrs["site"] == "test.site"
    assert ev.attrs["error"] == "TransientExecutionError"
    assert ev.t0 == ev.t1                        # zero-duration instant


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("mlego_test_total", "help text",
                    labelnames=("backend",))
    c.inc(backend="host")
    c.inc(2, backend="device")
    text = reg.exposition()
    assert "# HELP mlego_test_total help text" in text
    assert "# TYPE mlego_test_total counter" in text
    assert 'mlego_test_total{backend="host"} 1' in text
    assert 'mlego_test_total{backend="device"} 2' in text
    assert c.total() == 3


def test_histogram_exposition_is_cumulative_with_inf():
    reg = MetricsRegistry()
    h = reg.histogram("mlego_lat_seconds", "lat",
                      labelnames=("backend",), window=8)
    h.observe(0.01, backend="host")
    h.observe(0.3, backend="host")
    text = reg.exposition()
    assert "# TYPE mlego_lat_seconds histogram" in text
    assert 'mlego_lat_seconds_bucket{backend="host",le="+Inf"} 2' in text
    assert 'mlego_lat_seconds_count{backend="host"} 2' in text
    assert 'mlego_lat_seconds_sum{backend="host"} 0.31' in text
    # cumulative: every bucket count is >= its predecessor
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("mlego_lat_seconds_bucket")]
    assert counts == sorted(counts)


def test_histogram_view_feeds_slo_policy():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "", labelnames=("backend",), window=64)
    view = h.view(backend="host")
    pol = SLOPolicy(p95_slo_s=0.1, min_samples=8)
    assert pol.level(view) == 0                  # cold window
    for _ in range(20):
        h.observe(0.01, backend="host")
    assert len(view) == 20
    assert pol.level(view) == 0                  # well under SLO
    for _ in range(60):
        h.observe(1.0, backend="host")
    assert view.p95 == pytest.approx(1.0)
    assert pol.level(view) == 3                  # 10x the SLO -> severe


def test_registry_snapshot_mirrors_exposition():
    reg = MetricsRegistry()
    c = reg.counter("mlego_things_total", "things")
    c.inc(5)
    snap = reg.snapshot()
    assert snap["mlego_things_total"]["type"] == "counter"
    assert list(snap["mlego_things_total"]["series"].values()) == [5.0]
    assert "mlego_things_total 5" in reg.exposition()


def test_registry_factories_are_idempotent():
    reg = MetricsRegistry()
    a = reg.counter("mlego_x_total", "x")
    b = reg.counter("mlego_x_total", "x")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("mlego_x_total", "type clash")


# ---------------------------------------------------------------------------
# session integration
# ---------------------------------------------------------------------------

def test_session_submit_roots_a_trace(train):
    sess = MLegoSession(train, CFG, seed=0)
    hi = _hi(train)
    rep = sess.submit(QuerySpec(sigma=Interval(0.0, hi)))
    assert rep.trace is not None
    spans = sess.tracer.spans(trace_id=rep.trace)
    names = [s.name for s in spans]
    assert "session.submit" in names and "plan" in names
    root = next(s for s in spans if s.name == "session.submit")
    assert root.parent_id is None
    plan = next(s for s in spans if s.name == "plan")
    assert plan.parent_id == root.span_id
    # every query gets its own trace
    rep2 = sess.submit(QuerySpec(sigma=Interval(0.0, hi)))
    assert rep2.trace is not None and rep2.trace != rep.trace


def test_session_alpha_split_shares_the_batch_trace(train):
    sess = MLegoSession(train, CFG, seed=0)
    hi = _hi(train)
    sess.train_range(0.0, hi)
    br = sess.submit_many([QuerySpec(sigma=Interval(0.0, hi), alpha=a)
                           for a in (0.0, 1.0)])
    assert br.trace is not None
    assert all(r.trace == br.trace for r in br.reports)
    roots = sess.tracer.spans(trace_id=br.trace,
                              name="session.submit_many")
    assert len(roots) == 1, "the α-split must not nest a second root"


def test_device_query_emits_kernel_spans_with_device_ms(train):
    sess = MLegoSession(train, CFG, seed=0, backend="device")
    hi = _hi(train)
    sess.train_range(0.0, hi / 2)
    sess.train_range(hi / 2, hi)
    rep = sess.submit(QuerySpec(sigma=Interval(0.0, hi), alpha=1.0))
    spans = sess.tracer.spans(trace_id=rep.trace)
    launches = [s for s in spans if s.name == "kernel.launch"]
    assert launches, "a device merge must land a kernel.launch span"
    assert launches[0].attrs.get("merge_device_ms", 0.0) > 0.0
    root = next(s for s in spans if s.name == "session.submit")
    # the launch sits somewhere under the query root
    by_id = {s.span_id: s for s in spans}
    cur = launches[0]
    while cur.parent_id is not None:
        cur = by_id[cur.parent_id]
    assert cur is root


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def test_vb_gap_training_splits_into_phase_spans(train):
    sess = MLegoSession(train, CFG, seed=0, backend="device")
    rep = sess.submit(QuerySpec(sigma=Interval(0.0, _hi(train) / 3)))
    spans = sess.tracer.spans(trace_id=rep.trace)
    (tr,) = [s for s in spans if s.name == "train"]
    phases = {s.name: s for s in _children(spans, tr)}
    assert list(phases) == ["train.densify", "train.fit", "train.fetch"]
    d = phases["train.densify"].attrs["d"]
    assert d == rep.materialized[0].n_docs
    assert phases["train.fit"].attrs["bytes_in"] == d * CFG.vocab_size * 4
    assert phases["train.fetch"].attrs["bytes_out"] \
        == CFG.n_topics * CFG.vocab_size * 4
    assert tr.t0 <= phases["train.densify"].t0 \
        <= phases["train.fetch"].t1 <= tr.t1


def _merge_phases(spans):
    merges = [s for s in spans if s.name == "merge"]
    assert len(merges) == 1
    launch = next(s for s in spans if s.name == "kernel.launch")
    assert launch.parent_id == merges[0].span_id
    inner = [s.name for s in _children(spans, launch)
             if s.name.startswith("merge.")]
    (finish,) = [s for s in _children(spans, merges[0])
                 if s.name == "merge.finish"]
    assert finish.t0 >= launch.t1
    return inner, finish


def test_device_merge_splits_into_phase_spans(train):
    sess = MLegoSession(train, CFG, seed=0, backend="device")
    hi = _hi(train)
    sess.train_range(0.0, hi / 2)
    sess.train_range(hi / 2, hi)
    rep = sess.submit(QuerySpec(sigma=Interval(0.0, hi), alpha=1.0))
    inner, finish = _merge_phases(sess.tracer.spans(trace_id=rep.trace))
    assert inner == ["merge.stack", "merge.kernel"]
    assert finish.attrs["bytes_out"] == CFG.n_topics * CFG.vocab_size * 4


def test_device_merge_many_splits_into_phase_spans(train):
    sess = MLegoSession(train, CFG, seed=0, backend="device")
    hi = _hi(train)
    for a, b in ((0.0, hi / 2), (hi / 2, hi)):
        sess.train_range(a, b)
    br = sess.submit_many([QuerySpec(sigma=Interval(0.0, hi / 2)),
                           QuerySpec(sigma=Interval(0.0, hi))])
    inner, finish = _merge_phases(sess.tracer.spans(trace_id=br.trace))
    assert inner == ["merge.stack", "merge.kernel"]
    assert finish.attrs["bytes_out"] \
        == 2 * CFG.n_topics * CFG.vocab_size * 4


def _twice_plus_one(x):
    return 2 * x + 1


def test_fresh_jit_shape_records_one_compile_span():
    f = jax.jit(_twice_plus_one)
    x = np.ones(13, np.float32)
    tr = Tracer()
    with tr.span("root", "test") as root:
        f(x).block_until_ready()
        f(x).block_until_ready()              # cached: no second compile
    (c,) = tr.spans(name="jax.compile")
    assert c.parent_id == root.span_id and c.kind == "compile"
    assert "_twice_plus_one" in c.attrs["fun_name"]
    assert root.t0 <= c.t0 < c.t1 <= root.t1


def test_disabled_tracer_registers_no_compile_listener(monkeypatch):
    registered = []
    monkeypatch.setattr(obs, "_compile_listener", False)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    tr = Tracer(enabled=False)
    with tr.span("root", "test"):
        jax.jit(lambda x: x - 3)(np.ones(7, np.float32))
    assert registered == [] and len(tr.spans()) == 0
    # an enabled tracer registers the one listener on its first span
    on = Tracer()
    for _ in range(2):
        with on.span("root", "test"):
            pass
    assert registered == [obs._on_compile]


def test_profile_mode_mirrors_spans_onto_the_profiler(train, tmp_path):
    sess = MLegoSession(train, CFG, seed=0, backend="device",
                        profile=True)
    hi = _hi(train)
    sess.train_range(0.0, hi / 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.submit(QuerySpec(sigma=Interval(0.0, 0.8 * hi)))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = {e.name for p in jax.profiler.ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events}
    assert {"train.fit", "merge.kernel", "kernel.launch"} <= host


def test_fallback_replay_stays_in_the_query_trace(train):
    """A device-loss fallback replays the plan downstream inside the
    *same* trace: one root, a ``fallback`` instant naming both ends of
    the hop, and the answer's trace id unchanged."""
    sess = MLegoSession(train, CFG, backend="device", seed=0)
    hi = _hi(train)
    sess.train_range(0.0, hi / 2)
    spec = QuerySpec(sigma=Interval(0.0, hi / 2))
    with injected(FaultRule("backend.merge.device", rate=1.0,
                            kind="device_lost", max_failures=1), seed=2):
        rep = sess.submit(spec)
    assert rep.fallback_from == "device" and rep.backend == "host"
    spans = sess.tracer.spans(trace_id=rep.trace)
    roots = [s for s in spans if s.name == "session.submit"]
    assert len(roots) == 1, "the replay must not mint a second root"
    (fb,) = [s for s in spans if s.name == "fallback"]
    assert fb.attrs["from_backend"] == "device"
    assert fb.attrs["to_backend"] == "host"
    sess._backend_for(QuerySpec(sigma=Interval(0.0, hi / 2),
                                backend="device")).unquarantine()


def test_train_device_ms_is_attributed_per_query(train):
    sess = MLegoSession(train, CFG, seed=0, backend="device")
    hi = _hi(train)
    first = sess.submit(QuerySpec(sigma=Interval(0.0, hi / 2)))
    assert first.train_device_ms > 0.0, "gap training ran on device"
    # identical query is fully capital-served: no training happened on
    # its behalf, so no device training time may be billed to it (the
    # retired shared-counter diff charged whatever ran concurrently)
    second = sess.submit(QuerySpec(sigma=Interval(0.0, hi / 2)))
    assert second.train_device_ms == 0.0


def test_host_queries_never_bill_device_training(train):
    sess = MLegoSession(train, CFG, seed=0, backend="host")
    hi = _hi(train)
    rep = sess.submit(QuerySpec(sigma=Interval(0.0, hi / 3)))
    assert rep.train_device_ms == 0.0


# ---------------------------------------------------------------------------
# service integration
# ---------------------------------------------------------------------------

def test_service_trace_ids_survive_coalescing(train):
    hi = _hi(train)
    with MLegoService(train, CFG, window_s=0.5, max_width=8) as svc:
        svc.train_range(0.0, hi)
        futs = [svc.submit(QuerySpec(sigma=Interval(0.0, hi)),
                           tenant=f"t{i}") for i in range(4)]
        reps = [f.result(timeout=60) for f in futs]
        tracer = svc.tracer
        rep = svc.report()
    traces = [r.trace for r in reps]
    assert len(set(traces)) == 4, "each coalesced query keeps its own id"
    assert rep.max_coalesce_width == 4
    for tid in traces:
        spans = tracer.spans(trace_id=tid)
        names = {s.name for s in spans}
        assert {"serve.query", "queue.wait", "serve.execute"} <= names
        root = next(s for s in spans if s.name == "serve.query")
        for s in spans:
            if s.name in ("queue.wait", "serve.execute"):
                assert s.parent_id == root.span_id
    # one group span fused them, cross-linked from each member
    fuses = tracer.spans(name="serve.fuse")
    assert any(s.attrs.get("width") == 4 for s in fuses)
    execs = [s for t in traces for s in tracer.spans(trace_id=t)
             if s.name == "serve.execute"]
    assert all(s.attrs.get("fused") for s in execs)
    group_ids = {s.attrs.get("group_trace") for s in execs}
    assert len(group_ids) == 1 and group_ids != {""}


def test_service_trace_export_has_five_span_kinds(train, tmp_path):
    hi = _hi(train)
    with MLegoService(train, CFG, backend="device",
                      window_s=0.2, max_width=8) as svc:
        futs = [svc.submit(QuerySpec(sigma=Interval(0.0, hi / 2),
                                     alpha=1.0)) for _ in range(3)]
        for f in futs:
            f.result(timeout=120)
        path = tmp_path / "trace.json"
        svc.export_trace(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    names = {e["name"] for e in events}
    assert len(names & {"serve.query", "queue.wait", "serve.fuse",
                        "serve.execute", "session.submit",
                        "session.submit_many", "plan",
                        "kernel.launch", "device.upload"}) >= 5


def test_service_exposition_matches_same_run_report(train):
    hi = _hi(train)
    with MLegoService(train, CFG, window_s=0.2, max_width=8) as svc:
        svc.train_range(0.0, hi)
        futs = [svc.submit(QuerySpec(sigma=Interval(0.0, hi)),
                           tenant="ana") for _ in range(3)]
        futs.append(svc.submit(
            QuerySpec(sigma=Interval(hi + 10.0, hi + 20.0))))
        for f in futs[:-1]:
            f.result(timeout=60)
        with pytest.raises(ValueError):
            futs[-1].result(timeout=60)
        rep = svc.report()
        text = svc.metrics_text()

    def value(metric, **labels):
        want = metric
        if labels:
            want += "{" + ",".join('%s="%s"' % kv
                                   for kv in sorted(labels.items())) + "}"
        for line in text.splitlines():
            if line.startswith(want + " "):
                return float(line.rsplit(" ", 1)[1])
        # declared but never observed: no sample line, reads as zero
        assert "# TYPE %s " % metric in text
        return 0.0

    assert value("mlego_queries_total") == rep.queries == 4
    assert value("mlego_query_errors_total") == rep.errors == 1
    assert value("mlego_groups_total") == rep.groups
    assert value("mlego_plan_cache_hits_total") == rep.plan_cache_hits
    assert value("mlego_plan_cache_misses_total") == rep.plan_cache_misses
    assert value("mlego_active_sessions") == rep.active_sessions
    # the report embeds the registry snapshot — same objects, no drift
    assert rep.metrics is not None
    assert sum(rep.metrics["mlego_queries_total"]["series"].values()) \
        == rep.queries
    # latency is only observed for answered queries, not failures
    lat = rep.metrics["mlego_serve_latency_seconds"]["series"]
    assert sum(s["count"] for s in lat.values()) == rep.queries - rep.errors


def test_service_slo_snapshot_reads_the_latency_histogram(train):
    hi = _hi(train)
    with MLegoService(train, CFG, window_s=0.0) as svc:
        svc.train_range(0.0, hi)
        for _ in range(3):
            svc.submit(QuerySpec(sigma=Interval(0.0, hi))) \
               .result(timeout=60)
        rep = svc.report()
        view = svc._m_latency.view(backend=svc.backend.name)
    slo = rep.slo[svc.backend.name]
    assert slo.samples == 3 == len(view)
    assert slo.p95_s == pytest.approx(view.p95)
    assert slo.p50_s > 0.0


def test_direct_session_use_feeds_the_breaker(train):
    """Satellite: a tenant holding ``svc.session(...)`` and calling it
    directly used to bypass breaker accounting entirely — the outcome
    hook now fires inside the session itself."""
    hi = _hi(train)
    with MLegoService(train, CFG, window_s=0.0) as svc:
        sess = svc.session("direct")
        sess.train_range(0.0, hi)
        sess.submit(QuerySpec(sigma=Interval(0.0, hi)))
        cb = svc._breaker_for(svc._instance_for(svc.backend.name))
        snap = cb.snapshot()
    assert snap.window >= 1, \
        "direct session success must land in the breaker window"
    assert snap.error_rate == 0.0


def test_service_queries_feed_breaker_exactly_once(train):
    """The worker path must not double-count now that the session hook
    is the single feed: N answered queries -> N breaker outcomes."""
    hi = _hi(train)
    with MLegoService(train, CFG, window_s=0.0) as svc:
        svc.train_range(0.0, hi)
        for _ in range(3):
            svc.submit(QuerySpec(sigma=Interval(0.0, hi))) \
               .result(timeout=60)
        cb = svc._breaker_for(svc._instance_for(svc.backend.name))
        snap = cb.snapshot()
    # train_range is also a session call but goes through submit only
    # for queries; exactly the 3 query outcomes may be in the window
    assert snap.window == 3
