"""Smoke run of MLego's query path on a TPU at the default LDA width.

With no option it drives ``MLegoService(backend="device")`` through
the calls a user makes, at ``LDAConfig()`` width (K=100, V=8192):

1. ``train_range`` builds a VB store of 64 partitions of 256 documents
   each (fused VB E-step kernel; one compiled shape);
2. a fully covered query merges 48 stored parts (merge kernel);
3. a partly covered query trains a 100-document VB gap and merges it
   with 40 stored parts;
4. a burst of three fully covered queries coalesces into one ragged
   ``merge_many`` launch;
5. a ``gs`` query on a five-partition Gibbs store trains its gap with
   the Gibbs sweep kernel (a DSGS step) and merges.

Each query runs cold (it compiles) and then warm at the same shapes.
Every answer must come from the device with no fallback; its β rows
must sum to 1, its held-out lpp must be finite, and its merge must
equal ``HostBackend``'s merge of the same parts to 1e-5.  The run then
checks that no query fell back to the host, was retried or tripped a
breaker, and that each of the four kernels compiles to a Mosaic custom
call on this chip.

``--chips 4`` runs only the vocab-sharded path: ``ShardedDeviceBackend``
merge and ``merge_many`` on a (1, 4) mesh against the single-device
``DeviceBackend`` on the same store, with each device holding a quarter
of every cached model.

Run from the checkout root::

    python3 chip_smoke.py [--seed N] [--chips 4]

The program runs in this one process.  It exits non-zero, and prints no
result, unless JAX's first device is a TPU.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DOCS_PER_PART = 256
VB_PARTS = 64
GS_PARTS = 5
GAP_DOCS = 100
DOC_LEN = 100
TEST_DOCS = 256
PARITY_TOL = 1e-5


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounter:
    """XLA compile requests, persistent-cache hits and compile seconds,
    read from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits, self.seconds

    def since(self, snap) -> str:
        req, hits, secs = (a - b for a, b in zip(self.snapshot(), snap))
        return (f"compiles={req - hits} cache_hits={hits} "
                f"compile_s={secs:.3f}")


def make_world(cfg, seed: int, n_train: int):
    """Train corpus with attr = document index (so partition i is
    exactly documents [256 i, 256 (i+1))) and a held-out doc-term
    matrix drawn from the same topics."""
    from repro.data.corpus import doc_term_matrix, make_corpus

    corpus, _ = make_corpus(n_train + TEST_DOCS, cfg.vocab_size,
                            cfg.n_topics, mean_doc_len=DOC_LEN, seed=seed)
    corpus = dataclasses.replace(
        corpus, attr=np.arange(corpus.n_docs, dtype=np.float64))
    return (corpus.subset(0, n_train),
            doc_term_matrix(corpus.subset(n_train, n_train + TEST_DOCS)))


def verify_answer(name, rep, store, cfg, x_test, kind: str) -> str:
    """Checks every answer must pass; returns a summary line."""
    from repro.api.backend import HostBackend
    from repro.core.lda import log_predictive_probability

    check(rep.backend == "device",
          f"{name}: answered by {rep.backend!r}, not the device")
    check(rep.fallback_from is None,
          f"{name}: fell back from {rep.fallback_from!r}")
    beta = np.asarray(rep.beta)
    check(beta.shape == (cfg.n_topics, cfg.vocab_size),
          f"{name}: beta shape {beta.shape}")
    check(bool(np.isfinite(beta).all()), f"{name}: non-finite beta")
    row_err = float(np.abs(beta.sum(axis=1) - 1.0).max())
    check(row_err <= 1e-4, f"{name}: beta rows sum to 1 ± {row_err}")
    lpp = log_predictive_probability(beta, x_test)
    check(bool(np.isfinite(lpp)), f"{name}: held-out lpp {lpp}")
    parts = [store.get(f.model_id) for p in rep.plans for f in p.ir.fetches]
    parts += list(rep.materialized)
    check(len(parts) == rep.n_merged,
          f"{name}: {len(parts)} parts found for {rep.n_merged} merged")
    host = HostBackend().merge(parts, kind, cfg)
    err = float(np.abs(host - beta).max())
    check(err <= PARITY_TOL,
          f"{name}: device merge differs from host by {err}")
    return (f"parts={rep.n_merged} trained_tokens={rep.n_trained_tokens} "
            f"lpp={lpp:.6f} host_max_abs_err={err:.3e}")


def kernel_custom_calls(cfg) -> dict:
    """Compile each kernel entry at smoke shapes for this chip and count
    the Mosaic custom calls in the program (0 would mean interpret)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.gibbs_sweep.ops import gibbs_sweep
    from repro.kernels.merge_topics.ops import (_merge_topics_ragged_impl,
                                                merge_topics)
    from repro.kernels.vb_estep.ops import vb_estep

    k, v, f32, i32 = cfg.n_topics, cfg.vocab_size, jnp.float32, jnp.int32
    s = jax.ShapeDtypeStruct
    t = DOCS_PER_PART // 4 * DOC_LEN
    lowered = {
        "merge_topics": merge_topics.lower(
            s((48, k, v), f32), s((48,), f32), bias=cfg.eta, base=cfg.eta),
        "merge_topics_ragged": _merge_topics_ragged_impl.lower(
            s((48, k, v), f32), s((48,), f32), s((48,), i32), 3,
            cfg.eta, cfg.eta),
        "vb_estep": vb_estep.lower(
            s((GAP_DOCS, v), f32), s((k, v), f32), s((GAP_DOCS, k), f32),
            cfg.alpha, cfg.e_step_iters),
        "gibbs_sweep": gibbs_sweep.lower(
            s((4, t), i32), s((4, t), i32), s((4, t), f32), s((4, t), f32),
            s((4, t), i32), s((4, 64, k), f32), s((k, v), f32),
            s((k,), f32), cfg.alpha, use_kernel=True),
    }
    return {name: low.compile().as_text().count("tpu_custom_call")
            for name, low in lowered.items()}


def run_one_chip(cfg, seed: int) -> None:
    import jax
    from repro.api import Interval, QuerySpec
    from repro.serve import MLegoService

    d = DOCS_PER_PART
    counter = CompileCounter(jax)
    t0 = time.perf_counter()
    train, x_test = make_world(cfg, seed, VB_PARTS * d)
    print(f"corpus: {train.n_docs} docs, {train.n_tokens} tokens, "
          f"K={cfg.n_topics} V={cfg.vocab_size}, held-out {TEST_DOCS} "
          f"docs ({time.perf_counter() - t0:.2f}s)")

    with MLegoService(train, cfg, backend="device", seed=seed,
                      window_s=0.25, workers_per_pool=1) as svc:
        backend, store = svc.backend, svc.store

        def timed(label, fn):
            snap, t = counter.snapshot(), time.perf_counter()
            out = fn()
            print(f"{label}: {(time.perf_counter() - t) * 1e3:.1f} ms "
                  f"{counter.since(snap)}")
            return out

        timed(f"build vb store ({VB_PARTS} x {d} docs)", lambda: [
            svc.train_range(i * d, (i + 1) * d) for i in range(VB_PARTS)])
        timed(f"build gs store ({GS_PARTS} x {d} docs)", lambda: [
            svc.train_range(i * d, (i + 1) * d, kind="gs")
            for i in range(GS_PARTS)])
        check(len(store.models("vb")) == VB_PARTS
              and len(store.models("gs")) == GS_PARTS,
              "store does not hold the partitions just trained")

        def one(name, sigma, kind="vb"):
            spec = QuerySpec(sigma=Interval(*sigma), kind=kind)
            rep = timed(name, lambda: svc.submit(spec).result(timeout=900))
            print(f"  {verify_answer(name, rep, store, cfg, x_test, kind)}")
            return rep

        def burst(name, sigmas):
            specs = [QuerySpec(sigma=Interval(*s)) for s in sigmas]
            snap = backend.stats
            reps = timed(name, lambda: [
                f.result(timeout=900)
                for f in [svc.submit(sp) for sp in specs]])
            delta = backend.stats.delta(snap)
            check(delta.merges == len(specs) and delta.device_launches == 1,
                  f"{name}: {delta.merges} merges in "
                  f"{delta.device_launches} launches, not one ragged "
                  f"launch")
            for i, rep in enumerate(reps):
                print(f"  [{i}] "
                      f"{verify_answer(name, rep, store, cfg, x_test, 'vb')}")

        for phase, shift in (("cold", 0), ("warm", d)):
            rep = one(f"{phase} covered", (shift, shift + 48 * d))
            check(rep.n_merged == 48 and rep.n_trained_tokens == 0,
                  "covered query did not merge 48 stored parts")
            gaps = backend.stats.gap_device_trains
            rep = one(f"{phase} partly covered",
                      (shift, shift + 40 * d + GAP_DOCS))
            check(rep.n_trained_tokens > 0
                  and backend.stats.gap_device_trains > gaps,
                  "partly covered query trained no gap on the device")
            burst(f"{phase} burst", [(0, 8 * d), (8 * d, 24 * d),
                                     (24 * d, 48 * d)])
            gaps = backend.stats.gap_device_trains
            rep = one(f"{phase} gs partly covered",
                      (shift, shift + 4 * d + GAP_DOCS), kind="gs")
            check(rep.n_trained_tokens > 0
                  and backend.stats.gap_device_trains > gaps,
                  "gs query trained no gap on the device")

        report = svc.report()
        stats = report.backend
        print(f"backend: launches={stats.device_launches} "
              f"merges={stats.merges} gap_device_trains="
              f"{stats.gap_device_trains} host_fallbacks="
              f"{stats.host_fallbacks} retries={report.retries} "
              f"max_coalesce_width={report.max_coalesce_width}")
        check(stats.device_launches > 0 and stats.gap_device_trains > 0,
              "no kernel launches or device gap trains counted")
        check(stats.host_fallbacks == 0, "a merge fell back to the host")
        check(sum(report.retries.values()) == 0,
              f"retries: {report.retries}")
        check(report.errors == 0, f"{report.errors} queries failed")
        for name, snap in report.breaker.items():
            check(snap.opens == 0 and snap.state == "closed",
                  f"breaker {name}: {snap.state}, opened {snap.opens}x")

    print(f"compiles total: {counter.requests - counter.hits} "
          f"({counter.seconds:.3f}s), persistent cache hits: "
          f"{counter.hits}")


def run_four_chips(seed: int) -> None:
    import jax
    from repro.api.backend import DeviceBackend, ShardedDeviceBackend
    from repro.configs.lda_default import LDAConfig
    from repro.core.plans import Interval
    from repro.core.store import ModelStore
    from repro.distributed.sharding import local_mesh_env

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    cfg = LDAConfig()
    d = DOCS_PER_PART
    rng = np.random.default_rng(seed)
    store = ModelStore()
    for i in range(VB_PARTS):
        lam = cfg.eta + rng.gamma(1.0, 1.0, (cfg.n_topics, cfg.vocab_size))
        store.add(Interval(i * d, (i + 1) * d), d, d * DOC_LEN, "vb",
                  {"lam": lam.astype(np.float32)})
    models = store.models("vb")
    single = DeviceBackend()
    sharded = ShardedDeviceBackend(env=local_mesh_env(max_devices=4))
    check(sharded.shards == 4, f"mesh has {sharded.shards} shards, not 4")
    for b in (single, sharded):
        b.bind_store(store)
    one = models[:48]
    batch = [models[:8], models[8:24], models[24:48]]
    calls = (("merge (48 parts)", lambda b: [b.merge(one, "vb", cfg)]),
             ("merge_many (8+16+24 parts)",
              lambda b: b.merge_many(batch, "vb", cfg)))
    for name, call in calls:
        ref = call(single)
        for phase in ("cold", "warm"):
            t = time.perf_counter()
            got = call(sharded)
            ms = (time.perf_counter() - t) * 1e3
            err = max(float(np.abs(a - b).max()) for a, b in zip(ref, got))
            print(f"sharded {name} {phase}: {ms:.1f} ms, max abs err vs "
                  f"single device {err:.3e}")
            check(err <= PARITY_TOL, f"sharded {name} differs by {err}")
    arr = sharded.cache.get(models[0], "lam")
    shard_bytes = sorted({s.data.nbytes for s in arr.addressable_shards})
    print(f"resident per device: {sharded.cache.resident_bytes} B "
          f"(single device {single.cache.resident_bytes} B); one model "
          f"{arr.nbytes} B global, shards {shard_bytes} B on "
          f"{len(arr.addressable_shards)} devices")
    check(sharded.cache.resident_bytes * 4 == single.cache.resident_bytes,
          "per-device resident bytes are not global/4")
    check(len(arr.addressable_shards) == 4
          and shard_bytes == [arr.nbytes // 4],
          "a cached model is not split in four along the vocabulary")
    check(sharded.stats.host_fallbacks == 0, "a merge fell back to the host")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the corpus and the store")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the vocab-sharded merge path")
    args = parser.parse_args()

    import jax
    from repro.compile_cache import use_compile_cache
    from repro.configs.lda_default import LDAConfig
    from repro.kernels.common import (INTERPRET_ENV, default_interpret,
                                      interpret_forced)
    from repro.kernels.gibbs_sweep.ops import default_use_kernel

    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 2
    if interpret_forced():
        print(f"chip_smoke: {INTERPRET_ENV} is set; the kernels would "
              f"run interpreted on the chip", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache_dir}")
    try:
        check(not default_interpret() and default_use_kernel(),
              "the kernels would not run compiled on this device")
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            cfg = LDAConfig()
            run_one_chip(cfg, args.seed)
            calls = kernel_custom_calls(cfg)
            print(f"kernels compiled for this chip (tpu_custom_call "
                  f"count): {calls}")
            check(all(n > 0 for n in calls.values()),
                  f"a kernel did not compile to Mosaic: {calls}")
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
