"""One run of one benchmark cell: set-up, warm-up, window, check.

Everything a cell needs is found by name: ``workloads/<cell>.json``
names its configuration (``configs/<config>.json``) and its traffic
generator (``traffic/<generator>.py``); ``BENCHMARK.json`` names the
metrics it reports, each read by ``e2e/<metric>.py`` or
``metrics/<metric>.py``.  The system under test is driven only through
``MLegoService.submit`` on a ``DeviceBackend``; its spans and
counters feed the per-layer metrics of a traced run.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus as gen
from bench import reference, trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WAIT_AFTER_S = 60.0          # how long past the close an answer may come


class BenchError(Exception):
    """The run cannot be measured (no chip, a missing file, a query the
    warm-up could not answer)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(cell: str) -> Tuple[List[Dict], List[Dict]]:
    """The end-to-end and per-layer metrics BENCHMARK.json gives this
    cell (a metric without ``workloads`` applies to every cell)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)

    def mine(m):
        return "workloads" not in m or cell in m["workloads"]
    return ([m for m in spec["end_to_end"] if mine(m)],
            [m for m in spec["per_layer"] if mine(m)])


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program stored."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs compiled, and programs loaded from the persistent cache,
    as ``jax.monitoring`` reports them."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1


# ---------------------------------------------------------------------------
# the deployment: corpus, store, backend, service
# ---------------------------------------------------------------------------

@dataclass
class World:
    syn: gen.Synthetic
    corpus: object               # repro Corpus
    store: object                # repro ModelStore
    lda: object                  # repro LDAConfig
    part_docs: int
    n_parts: int

    @property
    def domain(self) -> int:
        return self.part_docs * self.n_parts


def build_world(config: Dict, seed: int) -> World:
    from repro.configs.lda_default import LDAConfig
    from repro.core.plans import Interval
    from repro.core.store import ModelStore
    from repro.data.corpus import Corpus

    m = config["model"]
    syn = gen.from_config(config["corpus"], m, seed)
    corpus = Corpus(tokens=syn.words, doc_ids=syn.doc_ids,
                    doc_offsets=syn.doc_offsets,
                    attr=np.arange(syn.n_docs, dtype=np.float64),
                    vocab_size=syn.vocab_size)
    lda = LDAConfig(n_topics=m["n_topics"], vocab_size=m["vocab_size"],
                    alpha=m["alpha"], eta=m["eta"], max_iters=m["max_iters"],
                    e_step_iters=m["e_step_iters"],
                    gibbs_sweeps=m["gibbs_sweeps"])
    b = config["store"]["partition_docs"]
    n_parts = config["store"]["partitions"]
    if n_parts * b > syn.n_docs:
        raise BenchError("store holds more documents than the corpus")
    store = ModelStore()
    route = m["route"]
    for p in range(n_parts):
        counts = syn.counts(p * b, (p + 1) * b, np.float32)
        t0, t1 = syn.token_range(p * b, (p + 1) * b)
        theta = {"lam": counts + np.float32(m["eta"])} if route == "vb" \
            else {"delta_nkv": counts}
        store.add(Interval(p * b, (p + 1) * b), b, t1 - t0, route, theta)
    return World(syn, corpus, store, lda, b, n_parts)


def make_backend(n_models: int, profile: bool, base=None):
    """A ``DeviceBackend`` (or the given subclass) that holds every
    stored model."""
    if base is None:
        from repro.api.backend import DeviceBackend as base
    return base(capacity=n_models, profile=profile)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Rec:
    tenant: str
    lo: int
    hi: int
    due: float
    keep: bool
    done: Optional[float] = None
    error: Optional[str] = None
    backend: str = ""
    fallback: Optional[str] = None
    n_merged: int = 0
    gap_docs: int = 0
    report: object = None


class Window:
    """Submits, times and keeps the answers of one window."""

    def __init__(self, svc, spec_of: Callable, keep_every: int, seed: int):
        self.svc = svc
        self.spec_of = spec_of
        self.keep_every = max(1, keep_every)
        self.rng = np.random.default_rng([seed, 7])
        self.recs: List[Rec] = []
        self.lock = threading.Lock()
        self.pending = 0
        self.idle = threading.Condition(self.lock)
        self.longest: Optional[Rec] = None

    def submit(self, q, due: float, on_done=None) -> Rec:
        with self.lock:
            rec = Rec(q.tenant, q.lo, q.hi, due,
                      keep=bool(self.rng.integers(self.keep_every) == 0))
            self.recs.append(rec)
            self.pending += 1
        try:
            fut = self.svc.submit(self.spec_of(q), tenant=q.tenant)
        except Exception as exc:             # shed or closed: a failure
            self._finish(rec, None, exc, on_done)
            return rec
        fut.add_done_callback(
            lambda f: self._finish(rec, f, None, on_done))
        return rec

    def _finish(self, rec: Rec, fut, exc, on_done) -> None:
        now = time.perf_counter()
        if fut is not None:
            exc = fut.exception()
        if exc is not None:
            rec.error = f"{type(exc).__name__}: {exc}"
        else:
            rep = fut.result()
            rec.backend, rec.fallback = rep.backend, rep.fallback_from
            rec.n_merged = rep.n_merged
            rec.gap_docs = sum(m.n_docs for m in rep.materialized)
            with self.lock:
                cur = self.longest
                if cur is None or (rec.n_merged, rec.gap_docs) > \
                        (cur.n_merged, cur.gap_docs):
                    if cur is not None and not cur.keep:
                        cur.report = None
                    self.longest = rec
                    rec.report = rep
                elif rec.keep:
                    rec.report = rep
        rec.done = now
        with self.lock:
            self.pending -= 1
            self.idle.notify_all()
        if on_done is not None:
            on_done(rec)

    def drain(self, until: float) -> None:
        with self.lock:
            while self.pending and time.perf_counter() < until:
                self.idle.wait(timeout=0.1)


def open_loop(win: Window, stream, offsets: List[float], t0: float) -> float:
    """Send each query when it is due; returns the latest send's delay."""
    late = 0.0
    for off in offsets:
        due = t0 + off
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late = max(late, time.perf_counter() - due)
        win.submit(stream.next(), due)
    return late


def closed_loop(win: Window, streams, t_end: float) -> None:
    """Each client sends its next query when its answer has come."""

    def client(stream):
        while time.perf_counter() < t_end:
            got = threading.Event()
            win.submit(stream.next(), time.perf_counter(),
                       on_done=lambda _r: got.set())
            got.wait(timeout=max(0.0, t_end - time.perf_counter())
                     + WAIT_AFTER_S)

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in streams]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, t_end - time.perf_counter())
               + WAIT_AFTER_S + 5)


@dataclass
class RunStats:
    latencies: List[float]
    answered_in_window: int
    seconds: float
    setup_s: float

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile; failures are infinitely late."""
        xs = sorted(self.latencies)
        if not xs:
            return math.inf
        return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def run_stats(recs: List[Rec], t_end: float, seconds: float,
              setup_s: float) -> RunStats:
    lat = [r.done - r.due if r.done is not None and r.error is None
           else math.inf for r in recs]
    answered = sum(1 for r in recs if r.error is None and r.done is not None
                   and r.done <= t_end)
    return RunStats(lat, answered, seconds, setup_s)


def quarter_medians(recs: List[Rec], t0: float, seconds: float
                    ) -> List[float]:
    """Median latency of the queries due in each quarter of the window:
    a backlog that grows shows as a rising sequence."""
    out = []
    for i in range(4):
        lo, hi = t0 + seconds * i / 4, t0 + seconds * (i + 1) / 4
        xs = sorted(r.done - r.due if r.done is not None and r.error is None
                    else math.inf for r in recs if lo <= r.due < hi)
        out.append(round(1e3 * xs[len(xs) // 2], 1) if xs else None)
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def expected_plan(lo: int, hi: int, b: int) -> Tuple[Tuple[int, int],
                                                     List[Tuple[int, int]]]:
    """The plan a range's answer merges when every partition is stored
    and the query asks for the fastest plan (alpha = 0, maximal
    coverage): the whole partitions inside it, and the uncovered edges
    trained as gaps."""
    p0, p1 = -(-lo // b), hi // b
    if p1 <= p0:
        return (0, 0), [(lo, hi)]
    gaps = [(a, c) for a, c in ((lo, p0 * b), (p1 * b, hi)) if c > a]
    return (p0 * b, p1 * b), gaps


def worst(a: float, b: float) -> float:
    """The larger reading; one that is not a number is infinitely bad."""
    return max(a if np.isfinite(a) else math.inf,
               b if np.isfinite(b) else math.inf)


def check_answers(world: World, config: Dict, recs: List[Rec],
                  seed: int, expect_gaps: bool,
                  stand_in: Optional[str] = None
                  ) -> Dict[str, Tuple[float, float]]:
    """Compare a seeded sample of the window's answers, the longest
    among them, with the plain reference: each answer's topics against
    the merge of the parts its range covers, and each gap model it
    trained against a fixed point of VB on the gap's documents and
    against the gap's word counts, each of which it must hold once.
    ``stand_in`` puts the reference in the program's place, fitted as
    ``reference.stand_in`` says: the control (one precision step lower,
    its merge summed in bfloat16) or a planted fault."""
    m, chk = config["model"], config["check"]
    route, eta, b = m["route"], m["eta"], world.part_docs
    answered = [r for r in recs if r.error is None and r.done is not None]
    not_device = sum(1 for r in answered
                     if r.backend != "device" or r.fallback is not None)
    kept = [r for r in answered if r.report is not None]
    rng = np.random.default_rng([seed, 11])
    longest = max(kept, key=lambda r: (r.n_merged, r.gap_docs),
                  default=None)
    rest = [r for r in kept if r is not longest]
    pick = rng.choice(len(rest), size=min(len(rest), chk["queries"] - 1),
                      replace=False) if rest else []
    sample = ([longest] if longest is not None else []) + \
        [rest[i] for i in sorted(pick)]

    stat = "lam" if route == "vb" else "delta_nkv"
    operands = m["precision"]["matmul_operands"] if route == "vb" else ""
    vb = dict(k=m["n_topics"], alpha=m["alpha"], eta=eta,
              e_iters=m["e_step_iters"], operands=operands)
    rows = config["store"]["max_gap_docs"]
    merge_l1 = row_sum = residual = mass = 0.0
    plan_mismatch = n_gaps = 0
    for r in sample:
        (s0, s1), want = expected_plan(r.lo, r.hi, b)
        gaps = sorted(r.report.materialized, key=lambda g: g.o.lo)
        if [(int(g.o.lo), int(g.o.hi)) for g in gaps] != want or \
                r.n_merged != (s1 - s0) // b + len(want):
            plan_mismatch += 1
            continue
        gap_stats = []
        for g in gaps:
            lo, hi = int(g.o.lo), int(g.o.hi)
            if route == "vb":
                x = jnp.asarray(world.syn.doc_term(lo, hi, rows=rows))
                lam = g.theta[stat]
                if stand_in is not None:
                    key = jax.random.fold_in(jax.random.PRNGKey(
                        seed % (1 << 31)), lo)
                    lam = reference.vb_fit(
                        x, key, k=vb["k"], alpha=vb["alpha"], eta=eta,
                        **reference.stand_in(stand_in, m["max_iters"],
                                             m["e_step_iters"], operands))
                residual = worst(residual,
                                 reference.vb_residual(x, lam, **vb))
                mass = worst(mass, reference.vb_mass_err(x, lam, eta))
                n_gaps += 1
                gap_stats.append(np.asarray(lam, np.float64) - eta)
            else:
                gap_stats.append(np.asarray(g.theta[stat], np.float64))
        if stand_in is not None:
            parts = [world.syn.counts(p * b, (p + 1) * b)
                     for p in range(s0 // b, s1 // b)] + gap_stats
            got = reference.merged_topics(route, eta, parts,
                                          low=stand_in == "control")
        else:
            got = np.asarray(r.report.beta, np.float64)
        parts = ([world.syn.counts(s0, s1)] if s1 > s0 else []) + gap_stats
        ref = reference.merged_topics(route, eta, parts)
        merge_l1 = worst(merge_l1,
                         float(np.abs(got - ref).sum(axis=1).max()))
        row_sum = worst(row_sum, float(np.abs(got.sum(axis=1) - 1.0).max()))

    out = {"answers_not_from_device": (float(not_device), 0.0),
           "plan_mismatch": (float(plan_mismatch), 0.0),
           "merge_l1": (merge_l1, chk["limits"]["merge_l1"]),
           "row_sum_err": (row_sum, chk["limits"]["row_sum_err"])}
    if expect_gaps and route == "vb":
        out["gap_residual"] = (residual, chk["limits"]["gap_residual"])
        out["gap_mass_err"] = (mass, chk["limits"]["gap_mass_err"])
        out["gaps_checked"] = (float(n_gaps), 1.0)
    out["answers_checked"] = (float(len(sample)), 1.0)
    return out


def verdict(checks: Dict[str, Tuple[float, float]]) -> bool:
    ok = True
    for name, (value, limit) in checks.items():
        if name in ("answers_checked", "gaps_checked"):
            ok &= value >= limit
        else:
            ok &= math.isfinite(value) and value <= limit
    return bool(ok)


# ---------------------------------------------------------------------------
# the traced run's per-layer readings
# ---------------------------------------------------------------------------

class LayerContext:
    def __init__(self, spans, traced_spans, trace, config, peak,
                 compiles_in_window):
        self.spans = spans
        self.traced_spans = traced_spans
        self.trace = trace
        self.model = config["model"]
        self.k = self.model["n_topics"]
        self.v = self.model["vocab_size"]
        self.peak = peak
        self.compiles_in_window = compiles_in_window

    def roofline(self, kernel: str):
        return load_module("roofline", kernel)

    def mean_ms(self, name: str) -> Optional[float]:
        d = [s.duration_s for s in self.spans if s.name == name]
        return 1e3 * sum(d) / len(d) if d else None

    def roofline_share(self, patterns, flops, bytes_, within):
        if self.trace is None:
            return None
        dev = trace_reduce.kernel_seconds(self.trace, patterns, within)
        if dev <= 0:
            return None
        least = max(flops / self.peak["flops_per_s"],
                    bytes_ / self.peak["hbm_bytes_per_s"])
        return 100.0 * least / dev


class Profiler:
    """A JAX profiler trace of ``seconds`` starting ``lead`` seconds
    into the window, taken on a thread of its own."""

    def __init__(self, jax, lead: float, seconds: float):
        self.jax, self.lead, self.seconds = jax, lead, seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.window = (0.0, 0.0)
        self.sync_pc = 0.0
        self.error: Optional[BaseException] = None
        self.thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(t0,),
                                       daemon=True)
        self.thread.start()

    def _run(self, t0: float) -> None:
        try:
            time.sleep(max(0.0, t0 + self.lead - time.perf_counter()))
            self.jax.profiler.start_trace(self.dir)
            a = time.perf_counter()
            with self.jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                self.sync_pc = time.perf_counter()
            time.sleep(self.seconds)
            b = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.window = (a, b)
        except BaseException as exc:          # noqa: BLE001 - reported
            self.error = exc

    def reduce(self):
        self.thread.join()
        if self.error is not None:
            raise BenchError(f"profiler failed: {self.error!r}")
        found = []
        for dirpath, _, files in os.walk(self.dir):
            found += [os.path.join(dirpath, f) for f in files
                      if f.endswith(".xplane.pb")]
        if not found:
            raise BenchError("the profiler wrote no trace")
        dev, host = trace_reduce.read_xplane(found[0])
        offset = trace_reduce.clock_offset(host, self.sync_pc)
        return trace_reduce.reduce(dev, offset, self.window)

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(jax) -> Dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:            # a backend with no memory stats
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, workload: Optional[Dict] = None,
             config: Optional[Dict] = None, backend_base=None,
             require_tpu: bool = True, stand_ins: Tuple[str, ...] = ()
             ) -> Dict:
    """Run one cell and return its result line (a dict).

    ``backend_base`` replaces the ``DeviceBackend`` class (the tests
    plant faults through it); each of ``stand_ins`` also reads the
    numbers of the reference put in the program's place (the control or
    a planted fault) on the same sample, under ``stand_in_checks``."""
    from repro.api import QuerySpec
    from repro.core.plans import Interval
    from repro.obs.trace import Tracer
    from repro.serve import MLegoService

    workload = workload or load_json("workloads", cell + ".json")
    config = config or load_json("configs", workload["config"] + ".json")
    e2e, layers = cell_metrics(cell)
    if require_tpu:
        from repro.kernels.common import default_interpret
        if default_interpret():
            raise BenchError("the kernels would run interpreted")
    enable_compile_cache()
    counter = CompileCounter()
    traffic = load_module("traffic", workload["generator"])
    params = workload["params"]
    m, svc_cfg = config["model"], config["service"]

    world = build_world(config, seed)
    log(f"corpus: {world.syn.n_docs} docs, {world.syn.n_tokens} tokens, "
        f"K={m['n_topics']} V={m['vocab_size']}; store {world.n_parts} x "
        f"{world.part_docs} docs ({time.perf_counter() - t_start:.1f} s)")
    backend = make_backend(world.n_parts, trace, backend_base)
    backend.bind_store(world.store)
    stat = "lam" if m["route"] == "vb" else "delta_nkv"
    for model in world.store.models():
        backend.cache.get(model, stat)
    tracer = Tracer(capacity=1 << 20, enabled=trace)
    svc = MLegoService(world.corpus, world.lda, store=world.store,
                       kind=m["route"], backend=backend,
                       window_s=svc_cfg["window_s"],
                       max_width=svc_cfg["max_width"],
                       workers_per_pool=svc_cfg["workers_per_pool"],
                       seed=seed % (1 << 31), tracer=tracer, profile=trace)
    profiler = None
    try:
        def spec_of(q):
            return QuerySpec(sigma=Interval(q.lo, q.hi), kind=m["route"],
                             alpha=svc_cfg["alpha"],
                             materialize=svc_cfg["materialize"])

        names = traffic.tenants(params)
        for t in names:
            svc.session(t)
        n_warm = 0
        for lo, hi in traffic.warmup(params, world.domain, world.part_docs):
            q = traffic.Query(names[n_warm % len(names)], lo, hi)
            svc.submit(spec_of(q), tenant=q.tenant).result()
            n_warm += 1
        compiles0 = counter.compiles + counter.hits
        log(f"warm-up: {n_warm} shape queries, {counter.compiles} compiles "
            f"({counter.hits} persistent-cache hits)")

        win = Window(svc, spec_of, config["check"]["keep_every"], seed)
        if trace:
            profiler = Profiler(jax, workload["profile_lead_s"],
                                workload["profile_s"])
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        t_end = t0 + seconds
        if profiler is not None:
            profiler.start(t0)
        late = 0.0
        if workload["loop"] == "open":
            offsets = traffic.arrivals(params, workload["rate_qps"],
                                       seconds, seed)
            late = open_loop(win, traffic.stream(
                params, world.domain, world.part_docs, seed, 0,
                n=len(offsets)), offsets, t0)
        else:
            closed_loop(win, [traffic.stream(params, world.domain,
                                             world.part_docs, seed, c + 1)
                              for c in range(workload["clients"])], t_end)
        win.drain(t_end + WAIT_AFTER_S)
        compiles = counter.compiles + counter.hits - compiles0
        stats = run_stats(win.recs, t_end, seconds, setup_s)
        failed = sum(1 for r in win.recs if r.error is not None
                     or r.done is None)
        log(f"window: {len(win.recs)} queries, {failed} failed, "
            f"{stats.answered_in_window} answered inside; latest send "
            f"{late * 1e3:.3f} ms after due; {compiles} compiles inside; "
            f"median latency by quarter of the window (ms): "
            f"{quarter_medians(win.recs, t0, seconds)}")
        device = device_info(jax)

        result = {"correct": False, "attempted": len(win.recs),
                  "failed": failed, "metrics": {}, "device": device}
        if trace:
            red = profiler.reduce()
            spans = [s for s in tracer.spans() if t0 <= s.t0 <= t_end]
            a, b = red.window
            traced = [s for s in spans if s.t0 >= a and s.t1 <= b]
            ctx = LayerContext(spans, traced, red, config,
                               peak_for(device["kind"]), compiles)
            for mt in layers:
                v = load_module("metrics", mt["name"]).read(ctx)
                if v is not None:
                    result["metrics"][mt["name"]] = {"value": v,
                                                     "unit": mt["unit"]}
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            labelled = trace_reduce.label_gaps(
                red.gaps, [(s.name, s.t0, s.t1) for s in spans
                           if s.t1 >= a and s.t0 <= b])
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(red),
                "idle_gaps": trace_reduce.top_gaps(labelled)}
        else:
            for mt in e2e:
                v = load_module("e2e", mt["name"]).read(stats)
                result["metrics"][mt["name"]] = {"value": v,
                                                 "unit": mt["unit"]}
    finally:
        svc.close()
        backend.cache.clear()
        if profiler is not None:
            profiler.close()
    del svc, backend
    recs = win.recs
    checks = check_answers(world, config, recs, seed,
                           params["grid_docs"] < world.part_docs)
    if failed:
        checks["unanswered"] = (float(failed), 0.0)
    result["correct"] = verdict(checks)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    if stand_ins:
        result["stand_in_checks"] = {}
    for name in stand_ins:
        ctl = check_answers(world, config, recs, seed,
                            params["grid_docs"] < world.part_docs,
                            stand_in=name)
        result["stand_in_checks"][name] = {
            "correct": verdict(ctl),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in ctl.items()}}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return result


def peak_for(kind: str) -> Dict:
    peaks = load_json("peaks.json")["devices"]
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]
