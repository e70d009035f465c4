"""Fault-tolerance: the deterministic injection harness, the typed
retry policy, crash-safe store degradation (checksum quarantine), and
the chaos acceptance trace — an open-loop serve run under 10%+
transient injection on the merge/fetch sites must complete with zero
worker deaths and every future resolved to a report or a typed error.

This file (with ``test_breaker.py``) is the CI chaos-smoke leg.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.api import (
    CorruptModelError,
    DeviceLostError,
    Interval,
    MLegoSession,
    PermanentExecutionError,
    QuerySpec,
    RetryPolicy,
    TransientExecutionError,
)
from repro.configs.lda_default import LDAConfig
from repro.core.store import ModelStore
from repro.data.corpus import make_corpus
from repro.distributed.elastic import recover_quarantined
from repro.serve import MLegoService
from repro.testing.faults import (
    FaultInjector,
    FaultRule,
    active_injector,
    from_env,
    injected,
    maybe_fail,
)

CFG = LDAConfig(n_topics=4, vocab_size=100, alpha=0.5, eta=0.05,
                max_iters=5, e_step_iters=4, gibbs_sweeps=4)


@pytest.fixture(scope="module")
def corpus():
    c, _ = make_corpus(200, CFG.vocab_size, CFG.n_topics,
                       mean_doc_len=25, seed=11)
    return c


def _hi(corpus):
    return float(corpus.attr[-1]) + 1.0


# ---------------------------------------------------------------------------
# injector
# ---------------------------------------------------------------------------

def test_injector_verdicts_are_deterministic_per_seed_and_site():
    def verdicts(seed):
        inj = FaultInjector([FaultRule("s.a", rate=0.5),
                             FaultRule("s.b", rate=0.5)], seed=seed)
        out = []
        for site in ["s.a", "s.b"] * 20:
            try:
                inj.check(site)
                out.append(0)
            except TransientExecutionError:
                out.append(1)
        return out

    assert verdicts(7) == verdicts(7)
    assert verdicts(7) != verdicts(8)       # seed actually matters
    assert any(verdicts(7))                 # rate=0.5 fires sometimes
    assert not all(verdicts(7))


def test_site_streams_are_independent():
    """Adding calls at one site never shifts another site's verdicts."""
    def b_verdicts(extra_a_calls):
        inj = FaultInjector([FaultRule("s", rate=0.5)], seed=3)
        for _ in range(extra_a_calls):
            try:
                inj.check("s.a")
            except TransientExecutionError:
                pass
        out = []
        for _ in range(20):
            try:
                inj.check("s.b")
                out.append(0)
            except TransientExecutionError:
                out.append(1)
        return out

    assert b_verdicts(0) == b_verdicts(17)


def test_rule_prefix_after_and_max_failures():
    inj = FaultInjector([FaultRule("backend.merge", rate=1.0,
                                   kind="permanent", after=2,
                                   max_failures=2)], seed=0)
    # prefix match: backend.merge.device is covered, store.get is not
    inj.check("store.get")
    inj.check("backend.merge.device")       # after=2 exempts calls 1..2
    inj.check("backend.merge.device")
    for _ in range(2):                      # then exactly max=2 firings
        with pytest.raises(PermanentExecutionError):
            inj.check("backend.merge.device")
    inj.check("backend.merge.device")       # budget exhausted: clean
    assert inj.total_failures == 2
    assert inj.calls["backend.merge.device"] == 5


def test_kinds_raise_the_right_types():
    for kind, exc in [("transient", TransientExecutionError),
                      ("permanent", PermanentExecutionError),
                      ("device_lost", DeviceLostError),
                      ("corrupt", CorruptModelError),
                      ("io", IOError)]:
        inj = FaultInjector([FaultRule("x", rate=1.0, kind=kind)])
        with pytest.raises(exc):
            inj.check("x")


def test_injected_scope_nests_and_restores():
    assert active_injector() is None
    with injected(FaultRule("a", rate=1.0), seed=1) as outer:
        assert active_injector() is outer
        with injected(FaultRule("b", rate=1.0), seed=2) as inner:
            assert active_injector() is inner
        assert active_injector() is outer
    assert active_injector() is None
    maybe_fail("a")                         # no injector: free no-op


def test_from_env_parses_seed_and_rules():
    inj = from_env("seed=7, backend.merge:0.1, "
                   "store.load:1:corrupt:max=1, s:0.5:io:after=3")
    assert inj.seed == 7
    assert [r.site for r in inj.rules] == ["backend.merge", "store.load",
                                           "s"]
    assert inj.rules[1].kind == "corrupt"
    assert inj.rules[1].max_failures == 1
    assert inj.rules[2].after == 3 and inj.rules[2].kind == "io"
    with pytest.raises(ValueError):
        from_env("justasite")
    with pytest.raises(ValueError):
        from_env("x:2.0")                   # rate out of range


def test_env_hook_installs_at_import():
    """MLEGO_FAULTS is parsed once at module import (the CI hook)."""
    env = dict(os.environ,
               MLEGO_FAULTS="seed=3,store.get:1:io:max=1",
               PYTHONPATH="src")
    code = ("from repro.testing.faults import active_injector\n"
            "inj = active_injector()\n"
            "assert inj is not None and inj.seed == 3, inj\n"
            "assert inj.rules[0].site == 'store.get'\n"
            "print('env-hook-ok')\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "env-hook-ok" in out.stdout


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

def test_retry_absorbs_transients_within_budget():
    pol = RetryPolicy(max_attempts=3, base_delay_s=0.0)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientExecutionError("flake")
        return "ok"

    assert pol.run(flaky, site="s", sleep=lambda _: None) == "ok"
    assert len(calls) == 3
    assert pol.snapshot() == {"s": 2}
    assert pol.total_retries == 2


def test_retry_budget_exhaustion_reraises():
    pol = RetryPolicy(max_attempts=2)

    def always():
        raise TransientExecutionError("never clears")

    with pytest.raises(TransientExecutionError):
        pol.run(always, site="s", sleep=lambda _: None)
    assert pol.snapshot() == {"s": 1}       # one retry, then surfaced


def test_retry_never_retries_permanent_or_no_retry_types():
    pol = RetryPolicy(max_attempts=5)
    n = [0]

    def perm():
        n[0] += 1
        raise CorruptModelError("bad blob")

    with pytest.raises(CorruptModelError):
        pol.run(perm, site="s", sleep=lambda _: None)
    assert n[0] == 1

    def lost():
        n[0] += 1
        raise DeviceLostError("gone", backend="device")

    with pytest.raises(DeviceLostError):
        pol.run(lost, site="s", sleep=lambda _: None,
                no_retry=(DeviceLostError,))
    assert n[0] == 2                        # no blind retry of device loss
    assert pol.total_retries == 0


def test_backoff_is_capped_exponential_with_deterministic_jitter():
    pol = RetryPolicy(base_delay_s=0.01, max_delay_s=0.04, jitter=0.5)
    d = [pol.delay_s(i, "site") for i in range(1, 6)]
    assert d == [pol.delay_s(i, "site") for i in range(1, 6)]  # no RNG
    # monotone-ish growth up to the cap; jitter only shrinks
    for i, di in enumerate(d, start=1):
        nominal = min(0.04, 0.01 * 2 ** (i - 1))
        assert 0.5 * nominal <= di <= nominal
    assert pol.delay_s(1, "a") != pol.delay_s(1, "b")  # site-salted


def test_per_site_budgets_longest_prefix_wins():
    pol = RetryPolicy(max_attempts=3,
                      site_attempts={"backend": 5,
                                     "backend.merge": 1})
    assert pol.attempts_for("backend.train_gap.host") == 5
    assert pol.attempts_for("backend.merge.device") == 1
    assert pol.attempts_for("store.get") == 3


# ---------------------------------------------------------------------------
# executor/session retry integration
# ---------------------------------------------------------------------------

def test_session_absorbs_transient_merge_and_fetch_faults(corpus):
    hi = _hi(corpus)
    sess = MLegoSession(corpus, CFG, seed=0,
                        retry=RetryPolicy(base_delay_s=0.0))
    sess.train_range(0.0, hi / 2)
    with injected(FaultRule("backend.merge", rate=1.0, max_failures=1),
                  FaultRule("store.get", rate=1.0, max_failures=1),
                  seed=5) as inj:
        rep = sess.submit(QuerySpec(sigma=Interval(0.0, hi / 2)))
    assert rep.beta.shape == (CFG.n_topics, CFG.vocab_size)
    assert inj.total_failures == 2          # both faults fired ...
    assert sess.retry.total_retries >= 2    # ... and were retried away


def test_session_surfaces_permanent_fault_immediately(corpus):
    hi = _hi(corpus)
    sess = MLegoSession(corpus, CFG, seed=0,
                        retry=RetryPolicy(base_delay_s=0.0))
    sess.train_range(0.0, hi / 2)
    with injected(FaultRule("backend.merge", rate=1.0, kind="permanent"),
                  seed=5):
        with pytest.raises(PermanentExecutionError):
            sess.submit(QuerySpec(sigma=Interval(0.0, hi / 2)))
    assert sess.retry.total_retries == 0


@pytest.mark.parametrize("failure", ["lowering", "compile"])
def test_device_kernel_failure_is_permanent_and_never_replayed(
        corpus, monkeypatch, failure):
    """A kernel the device cannot lower or compile fails the query: no
    retry and no host replay, which would hide a broken device path.
    Real device loss keeps its fallback chain."""
    import jax
    import repro.api.backend as backend_mod
    from repro.api import DeviceBackend

    hi = _hi(corpus)
    spec = QuerySpec(sigma=Interval(0.0, hi / 2))
    if failure == "lowering":
        # a real refusal: compiled Pallas does not lower for the CPU
        backend = DeviceBackend(interpret=False)
    else:
        backend = DeviceBackend()

        def refused(*args, **kwargs):
            raise jax.errors.JaxRuntimeError(
                "INVALID_ARGUMENT: Mosaic failed to compile TPU kernel")
        monkeypatch.setattr(backend_mod, "merge_topics", refused)
    sess = MLegoSession(corpus, CFG, seed=0, backend=backend,
                        retry=RetryPolicy(base_delay_s=0.0))
    sess.train_range(0.0, hi / 2)
    with pytest.raises(PermanentExecutionError,
                       match=r"failed to (lower|compile)"):
        sess.submit(spec)
    assert sess.retry.total_retries == 0
    assert not backend.quarantined
    assert backend.stats.host_fallbacks == 0

    with injected(FaultRule("backend.merge.device", rate=1.0,
                            kind="device_lost", max_failures=1), seed=2):
        rep = sess.submit(spec)
    assert rep.fallback_from == "device" and rep.backend == "host"
    assert backend.quarantined


# ---------------------------------------------------------------------------
# crash-safe store: checksums, quarantine, planning around the hole
# ---------------------------------------------------------------------------

def _filled_store():
    store = ModelStore()
    rng = np.random.default_rng(0)
    for lo in (0.0, 10.0, 20.0):
        store.add(Interval(lo, lo + 10.0), 10, 100, "vb",
                  {"lam": rng.random((4, 32)).astype(np.float32)})
    return store


def test_load_verify_detects_checksum_mismatch(tmp_path):
    store = _filled_store()
    store.save(str(tmp_path))
    blob = tmp_path / "model_1.npz"
    raw = bytearray(blob.read_bytes())
    raw[len(raw) // 2] ^= 0xFF              # flip one byte mid-file
    blob.write_bytes(bytes(raw))

    with pytest.raises(CorruptModelError) as ei:
        ModelStore.load(str(tmp_path), verify=True)
    assert ei.value.model_id == 1
    assert "checksum" in str(ei.value)
    # legacy callers catch IOError — the taxonomy keeps that contract
    with pytest.raises(IOError):
        ModelStore.load(str(tmp_path), verify=True)
    # verify=False skips the hash; the flipped byte still loads or
    # fails as a zip error, but must not raise a *checksum* error
    try:
        ModelStore.load(str(tmp_path), verify=False)
    except CorruptModelError as exc:
        assert "checksum" not in str(exc)


def test_load_quarantines_truncated_blob_and_keeps_the_rest(tmp_path):
    store = _filled_store()
    store.save(str(tmp_path))
    blob = tmp_path / "model_1.npz"
    blob.write_bytes(blob.read_bytes()[:20])  # truncated write / crash

    loaded = ModelStore.load(str(tmp_path), on_corrupt="quarantine")
    assert len(loaded) == 2
    assert len(loaded.quarantined) == 1
    q = loaded.quarantined[0]
    assert q.model_id == 1 and q.o == Interval(10.0, 20.0)
    assert q.kind == "vb" and "checksum" in q.reason
    # healthy blobs are intact
    assert {m.model_id for m in loaded.models()} == {0, 2}

    # without checksums the truncation is caught at deserialization
    raw = ModelStore.load(str(tmp_path), verify=False,
                          on_corrupt="quarantine")
    assert len(raw) == 2
    assert "unreadable" in raw.quarantined[0].reason

    with pytest.raises(ValueError):
        ModelStore.load(str(tmp_path), on_corrupt="nonsense")


def test_save_is_atomic_under_injected_crash(tmp_path):
    """A save that dies mid-write never corrupts the previous good
    snapshot: blobs/manifest go through tmp+fsync+rename."""
    store = _filled_store()
    store.save(str(tmp_path))
    good = ModelStore.load(str(tmp_path))
    assert len(good) == 3

    with injected(FaultRule("store.save", rate=1.0, kind="io"), seed=0):
        with pytest.raises(IOError):
            store.save(str(tmp_path))
    again = ModelStore.load(str(tmp_path), verify=True)
    assert len(again) == 3                  # old snapshot still whole


def test_quarantined_store_still_answers_covering_query(corpus, tmp_path):
    """The acceptance property: one blob lost, queries over its range
    still answer — the planner plans around the hole (alternate cover
    or gap training), it does not error."""
    hi = _hi(corpus)
    sess = MLegoSession(corpus, CFG, seed=0)
    sess.train_range(0.0, hi / 2)
    sess.train_range(hi / 2, hi)
    sess.store.save(str(tmp_path))
    # corrupt the second range's blob on disk
    mid = max(m.model_id for m in sess.store.models())
    blob = tmp_path / f"model_{mid}.npz"
    blob.write_bytes(b"not a zip at all")

    loaded = ModelStore.load(str(tmp_path), on_corrupt="quarantine")
    assert len(loaded.quarantined) == 1
    fresh = MLegoSession(corpus, CFG, store=loaded, seed=1)
    rep = fresh.submit(QuerySpec(sigma=Interval(0.0, hi)))
    assert rep.beta.shape == (CFG.n_topics, CFG.vocab_size)
    assert np.all(np.isfinite(rep.beta))
    # the hole was not silently ignored: the missing range was re-covered
    assert rep.n_trained_tokens > 0


def test_runtime_quarantine_and_elastic_recovery():
    store = _filled_store()
    store.quarantine(1, reason="device loss mid-read")
    assert {m.model_id for m in store.models()} == {0, 2}
    assert store.quarantined[0].o == Interval(10.0, 20.0)

    trained = []

    def train_fn(lo, hi):
        trained.append((lo, hi))
        rng = np.random.default_rng(99)
        return store.add(Interval(lo, hi), 10, 100, "vb",
                         {"lam": rng.random((4, 32)).astype(np.float32)})

    fresh = recover_quarantined(store, train_fn)
    assert trained == [(10.0, 20.0)]        # exactly the hole, nothing else
    assert len(fresh) == 1
    assert store.quarantined == []          # ledger drained (clear=True)
    assert len(store) == 3

    # already-covered holes are not retrained (local recovery only)
    store.quarantine(fresh[0].model_id, reason="again")
    store.add(Interval(10.0, 20.0), 10, 100, "vb",
              {"lam": np.zeros((4, 32), np.float32)})
    trained.clear()
    recover_quarantined(store, train_fn)
    assert trained == []


def test_recover_quarantined_can_keep_ledger():
    store = _filled_store()
    store.quarantine(0)
    recover_quarantined(store, lambda lo, hi: None, clear=False)
    assert len(store.quarantined) == 1


# ---------------------------------------------------------------------------
# calibration sidecar corruption
# ---------------------------------------------------------------------------

def test_corrupt_calibration_sidecar_cold_starts_with_warning(
        corpus, tmp_path):
    path = tmp_path / "calibration.json"
    path.write_text("{ this is not json")
    with pytest.warns(RuntimeWarning, match="cold-starting"):
        sess = MLegoSession(corpus, CFG, cost="calibrated",
                            calibration_path=str(path))
    # the session is usable at analytic prices
    hi = _hi(corpus)
    sess.train_range(0.0, hi / 4)
    rep = sess.submit(QuerySpec(sigma=Interval(0.0, hi / 4)))
    assert np.all(np.isfinite(rep.beta))


def test_missing_calibration_sidecar_stays_silent(corpus, tmp_path):
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")            # any warning would raise
        MLegoSession(corpus, CFG, cost="calibrated",
                     calibration_path=str(tmp_path / "absent.json"))


# ---------------------------------------------------------------------------
# serve-layer chaos acceptance
# ---------------------------------------------------------------------------

def _alive_workers(svc):
    return sum(t.is_alive() for p in svc._pools_snapshot()
               for t in p.threads)


def test_worker_survives_injected_worker_faults(corpus):
    hi = _hi(corpus)
    svc = MLegoService(corpus, CFG, backend="host", window_s=0.0)
    try:
        svc.train_range(0.0, hi / 2)
        n0 = _alive_workers(svc)
        spec = QuerySpec(sigma=Interval(0.0, hi / 2))
        with injected(FaultRule("serve.worker", rate=1.0, kind="io",
                                max_failures=2), seed=1):
            futs = [svc.submit(spec) for _ in range(4)]
            outcomes = []
            for f in futs:
                try:
                    outcomes.append(f.result(timeout=60))
                except IOError:
                    outcomes.append("failed")
        assert "failed" in outcomes         # the fault did land
        assert _alive_workers(svc) == n0    # ... and killed no thread
        # the pool still answers after the chaos window
        rep = svc.submit(spec).result(timeout=60)
        assert np.all(np.isfinite(rep.beta))
    finally:
        svc.close()


def test_open_loop_chaos_trace_completes(corpus):
    """Acceptance: 10%+ transient injection on the merge and fetch
    sites; an open-loop trace completes with zero worker deaths and
    every future resolved to a report or a typed error."""
    hi = _hi(corpus)
    svc = MLegoService(corpus, CFG, backend="host", window_s=0.002)
    try:
        svc.train_range(0.0, hi / 2)
        svc.train_range(hi / 2, hi)
        n0 = _alive_workers(svc)
        specs = [QuerySpec(sigma=Interval(0.0, hi * (0.3 + 0.1 * (i % 6))))
                 for i in range(24)]
        with injected(FaultRule("backend.merge", rate=0.1),
                      FaultRule("backend.fetch", rate=0.1),
                      FaultRule("store.get", rate=0.1),
                      seed=13) as inj:
            futs = [svc.submit(s, tenant=f"t{i % 3}")
                    for i, s in enumerate(specs)]
            reports, typed_errors = [], []
            for f in futs:
                try:
                    reports.append(f.result(timeout=120))
                except (TransientExecutionError,
                        PermanentExecutionError) as exc:
                    typed_errors.append(exc)
        assert len(reports) + len(typed_errors) == len(specs)
        assert inj.total_failures > 0       # chaos actually happened
        for rep in reports:
            assert np.all(np.isfinite(rep.beta))
        assert _alive_workers(svc) == n0    # zero worker deaths
        r = svc.report()
        # absorbed transients surface on the report's retry ledger
        assert sum(r.retries.values()) >= 1
        assert "host" in r.breaker          # breaker telemetry present
    finally:
        svc.close()
