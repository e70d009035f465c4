"""Pluggable execution backends for the query hot path.

The Fig. 2 pipeline bottoms out in two data-plane operations: merging
a plan's materialized models (Alg. 1/2 — pure bandwidth) and training
scratch gaps (the VB E-step — pure MXU).  ``HostBackend`` runs both on
host NumPy exactly as the seed repo did and is the parity reference.
``DeviceBackend`` keeps hot model parameters device-resident in an
LRU cache keyed by store model id (count- **and** byte-bounded,
invalidated through the store's change notifications), executes merges
through the fused Pallas ``merge_topics`` kernel — one ``(n, K, V)``
launch per query, and a single *ragged segmented* launch for a
``submit_many`` batch (every query's part rows concatenated CSR-style;
zero pad rows on any batch shape — this retired the power-of-two
bucketed launcher) — and routes scratch-gap training through the
kernel paths: VB through the fused E-step kernel
(``vb_fit(..., use_kernel=True)``), Gibbs through the doc-blocked
CGS sweep (``cgs_fit_blocked`` / ``kernels/gibbs_sweep``).  A freshly
trained persisted gap model is warm-inserted into the LRU
(``note_trained``) so the merge that follows reads it back as a hit.

``ShardedDeviceBackend`` ("device_sharded") lifts the one-device HBM
ceiling: every cached model is resident as a vocab-sharded ``(K, Vp)``
array (each device owns a ``V/ndev`` slice), merges run as
shard_map-launched Pallas kernels on the local slice, and the only
cross-device traffic is the per-topic row normalizer psum — so a model
stack whose total bytes exceed one device's ``max_bytes`` still merges
without host round-trips.  Cache byte accounting is *per device*
(global bytes / shard count), which is the unit the calibrated cost
model prices fetches in.

On CPU hosts the merge/E-step kernels execute in Pallas interpret
mode (the CI correctness path); on TPU they compile to Mosaic.  The
Gibbs route runs its blocked math as vmapped XLA off-TPU (see
``kernels/gibbs_sweep/ops.py``).  Selection flows through
``QuerySpec.backend`` / ``MLegoSession(backend=...)``.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, fields, replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from contextlib import contextmanager

from repro.api.trainers import (
    TrainerFn,
    get_merge,
    get_trainer,
    merge_family_name,
)
from repro.configs.lda_default import LDAConfig
from repro.core.errors import (DeviceLostError, ExecutionError,
                               PermanentExecutionError)
from repro.core.lda import MaterializedModel
from repro.core.merge import (
    device_merge_params,
    device_norm_offset,
    device_stat_key,
)
from repro.core.store import ModelStore
from repro.data.corpus import Corpus, doc_term_matrix
from repro.distributed.merge_collective import (
    merge_topics_ragged_sharded,
    merge_topics_sharded,
    padded_vocab,
)
from repro.distributed.sharding import MeshEnv, local_mesh_env
from repro.kernels.common import default_interpret
from repro.kernels.merge_topics.ops import (
    merge_topics,
    merge_topics_ragged,
    segment_ids,
)
from repro.obs import trace as obs
from repro.testing.faults import maybe_fail

BACKEND_NAMES = ("host", "device", "device_sharded")

# Status-message markers of a program the chip's compiler refused: a
# Mosaic kernel that does not compile, a kernel over its scoped VMEM
# (only known at compile time), an XLA:TPU compile failure.
_COMPILE_FAILURE_MARKERS = ("Mosaic failed to compile",
                            "memory space vmem",
                            "compile permanent error")


def _is_compile_failure(exc: jax.errors.JaxRuntimeError) -> bool:
    msg = str(exc)
    return any(m in msg for m in _COMPILE_FAILURE_MARKERS)


@dataclass(frozen=True)
class BackendStats:
    """Monotonic counters; diff two snapshots for per-query attribution.

    ``cache_resident_bytes`` is a *gauge* (current device-cache
    residency), not a counter — ``delta`` carries the newer snapshot's
    value through instead of differencing it.
    """

    cache_hits: int = 0
    cache_misses: int = 0
    cache_hit_bytes: int = 0          # bytes read from the device cache
    cache_miss_bytes: int = 0         # bytes transferred host->device
    cache_evictions: int = 0
    cache_invalidations: int = 0
    merges: int = 0
    device_launches: int = 0
    host_fallbacks: int = 0
    merge_device_ms: float = 0.0
    pad_rows: int = 0                 # zero-weight rows in batched launches
    pad_bytes: int = 0                # bytes those zero-weight rows carry
    train_device_ms: float = 0.0      # kernel-route gap-training wall time
    gap_device_trains: int = 0        # gaps trained through a kernel route
    train_uploads: int = 0            # fresh gap models warmed into the LRU
    cache_resident_bytes: int = 0     # gauge: bytes resident right now

    _GAUGES = ("cache_resident_bytes",)

    def delta(self, since: "BackendStats") -> "BackendStats":
        return BackendStats(**{
            f.name: getattr(self, f.name) - (
                0 if f.name in self._GAUGES else getattr(since, f.name))
            for f in fields(self)})

    @property
    def hit_rate(self) -> float:
        seen = self.cache_hits + self.cache_misses
        return self.cache_hits / seen if seen else 0.0


class ExecutionBackend:
    """Interface the session/executor program against."""

    name: str = "?"
    shards: int = 1   # devices each cached model is sliced across

    def __init__(self):
        self.stats = BackendStats()
        self._stats_lock = threading.Lock()
        # Sessions attribute per-query work by diffing two stats
        # snapshots; on a *shared* backend a concurrent session's
        # launch landing inside that window would be mis-attributed
        # (and fed to the calibrated cost model as this query's
        # bytes).  Callers hold this around snapshot -> launch -> diff
        # sections — coarse, but the device serializes launches anyway.
        self.measure_lock = threading.RLock()
        # health: a quarantined backend is suspected of device loss;
        # sessions route around it until a breaker probe re-admits it
        self.quarantined = False

    # -- health ----------------------------------------------------------
    def quarantine(self) -> None:
        """Mark unhealthy (device lost).  Idempotent."""
        self.quarantined = True

    def unquarantine(self) -> None:
        """Re-admit after a successful health probe."""
        self.quarantined = False

    @contextmanager
    def _device_guard(self):
        """Type what a device launch raises.

        A runtime crash (halted device, failed transfer, device OOM)
        becomes ``DeviceLostError``: the *backend* is suspect, not the
        query, so the session quarantines it and replays on the
        fallback chain.  A kernel that fails to trace, lower or compile
        is deterministic: it becomes ``PermanentExecutionError``, which
        is neither retried nor replayed on another backend — a host
        answer would hide that the device path is broken.  Typed errors
        and I/O errors (injected faults, store reads) pass through."""
        try:
            yield
        except (ExecutionError, OSError):
            raise
        except jax.errors.JaxRuntimeError as exc:
            if _is_compile_failure(exc):
                raise PermanentExecutionError(
                    f"{self.name} backend: kernel failed to compile: "
                    f"{exc}") from exc
            raise DeviceLostError(
                f"{self.name} backend lost its device: {exc}",
                backend=self.name) from exc
        except Exception as exc:
            raise PermanentExecutionError(
                f"{self.name} backend: kernel failed to lower: "
                f"{type(exc).__name__}: {exc}") from exc

    # -- lifecycle -------------------------------------------------------
    def bind_store(self, store: ModelStore) -> None:
        """Attach to the session's store (cache invalidation hookup)."""

    @property
    def bound_store(self) -> Optional[ModelStore]:
        """The store this backend caches against; None if stateless.

        Any number of sessions may share one backend **over the same
        store** (the multi-tenant serving layer does exactly that);
        sessions refuse to adopt a backend whose ``bound_store`` is a
        *different* live store — the cache is keyed by model id alone,
        and ids from two stores collide silently."""
        return None

    # -- data plane ------------------------------------------------------
    def merge(self, parts: Sequence[MaterializedModel], kind: str,
              cfg: LDAConfig) -> np.ndarray:
        raise NotImplementedError

    def merge_many(self, part_lists: Sequence[Sequence[MaterializedModel]],
                   kind: str, cfg: LDAConfig) -> List[np.ndarray]:
        return [self.merge(p, kind, cfg) for p in part_lists]

    def trainer(self, kind: str) -> TrainerFn:
        return get_trainer(kind)

    def kernel_route(self, kind: str) -> bool:
        """True when ``trainer(kind)`` runs through a device kernel.

        The executor uses this to attribute a trained gap's wall time
        to ``train_device_ms`` *per query* — replacing the shared
        stats-snapshot diff whose window picked up concurrent
        sessions' launches on a shared backend."""
        return False

    def note_trained(self, model: MaterializedModel) -> None:
        """Hook: a fresh gap model was persisted after training on this
        backend (device backends warm their LRU with it)."""

    # -- bookkeeping -----------------------------------------------------
    def _count(self, **kw) -> None:
        # read-modify-write on the immutable snapshot; locked so two
        # sessions sharing the backend can't lose each other's counts
        with self._stats_lock:
            self.stats = replace(
                self.stats, **{k: getattr(self.stats, k) + v
                               for k, v in kw.items()})


class HostBackend(ExecutionBackend):
    """Today's NumPy semantics — the parity reference for DeviceBackend."""

    name = "host"

    def merge(self, parts, kind, cfg):
        maybe_fail("backend.merge.host")
        for _ in parts:
            maybe_fail("backend.fetch.host")
        self._count(merges=1)
        return get_merge(kind)(list(parts), cfg)


class _DeviceModelCache:
    """LRU of device-resident merge statistics, keyed by store model id.

    Bounded two ways: ``capacity`` caps the entry count and
    ``max_bytes`` (optional) caps the resident parameter bytes — LRU
    entries are evicted until both bounds hold, so one giant model
    can't silently pin the whole HBM budget the way a count bound
    allows.  Volatile models (id −1, never in the store) pass through
    without being cached — there is no id under which an invalidation
    for them could ever arrive.

    Mutation is lock-serialized: one device cache may be shared by
    every session of a multi-tenant service over the same store.

    ``prepare`` maps a host statistic array to its device-resident form
    (default: plain f32 upload); the sharded backend substitutes a
    pad-and-shard upload.  ``bytes_divisor`` converts a resident
    array's *global* byte count into the unit the bounds and counters
    are kept in — per-device bytes for a vocab-sharded cache, so
    ``max_bytes`` bounds what any one device actually holds.
    """

    def __init__(self, capacity: int, max_bytes: Optional[int] = None,
                 *, prepare=None, bytes_divisor: int = 1):
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        if max_bytes is not None and max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.capacity = capacity
        self.max_bytes = max_bytes
        self._prepare = prepare or (lambda a: jnp.asarray(a, jnp.float32))
        self.bytes_divisor = max(1, int(bytes_divisor))
        self._entries: "OrderedDict[int, jax.Array]" = OrderedDict()
        self._lock = threading.RLock()
        self.resident_bytes = 0
        self.hits = self.misses = self.evictions = self.invalidations = 0
        self.hit_bytes = self.miss_bytes = 0
        # residency epoch: bumps whenever the resident *set* changes
        # (insert/evict/invalidate/clear) — the session plan cache keys
        # on it for providers that price fetches by cache state
        self.epoch = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, model_id: int) -> bool:
        return model_id in self._entries

    def _over_budget(self) -> bool:
        return (len(self._entries) > self.capacity
                or (self.max_bytes is not None
                    and self.resident_bytes > self.max_bytes))

    def _nb(self, arr: jax.Array) -> int:
        """Accounting bytes for one entry: per-device, not global."""
        return int(arr.nbytes) // self.bytes_divisor

    def _evict_lru(self) -> None:
        mid, arr = self._entries.popitem(last=False)
        self.resident_bytes -= self._nb(arr)
        self.evictions += 1
        self.epoch += 1
        obs.instant("cache.evict", model_id=mid, bytes=self._nb(arr))

    def _fits_alone(self, arr: jax.Array) -> bool:
        """A model bigger than the whole byte budget must pass through
        uncached — inserting it would evict every resident entry
        before LRU order finally evicted the newcomer itself."""
        return self.max_bytes is None or self._nb(arr) <= self.max_bytes

    def get(self, model: MaterializedModel, stat_key: str) -> jax.Array:
        mid = model.model_id
        with self._lock:
            if mid >= 0 and mid in self._entries:
                self.hits += 1
                self.hit_bytes += self._nb(self._entries[mid])
                self._entries.move_to_end(mid)
                return self._entries[mid]
            self.misses += 1
            with obs.span("device.upload", "backend", model_id=mid):
                arr = self._prepare(model.theta[stat_key])
                obs.set_attrs(bytes=self._nb(arr))
            self.miss_bytes += self._nb(arr)
            if mid >= 0 and self._fits_alone(arr):
                self._entries[mid] = arr
                self.resident_bytes += self._nb(arr)
                self.epoch += 1
                while self._entries and self._over_budget():
                    self._evict_lru()
            return arr

    def put(self, model: MaterializedModel, stat_key: str) -> bool:
        """Warm-insert a model (no hit/miss accounting) — the gap-
        training upload path.  Returns True if it ended up resident
        (an over-budget model passes through uncached)."""
        mid = model.model_id
        with self._lock:
            if mid < 0 or mid in self._entries:
                return mid in self._entries
            with obs.span("device.upload", "backend", model_id=mid,
                          warm=True):
                arr = self._prepare(model.theta[stat_key])
                obs.set_attrs(bytes=self._nb(arr))
            if not self._fits_alone(arr):
                return False
            self._entries[mid] = arr
            self.resident_bytes += self._nb(arr)
            self.epoch += 1
            while self._entries and self._over_budget():
                self._evict_lru()
            return mid in self._entries

    def invalidate(self, model_id: int) -> None:
        with self._lock:
            arr = self._entries.pop(model_id, None)
            if arr is not None:
                self.resident_bytes -= self._nb(arr)
                self.invalidations += 1
                self.epoch += 1

    def clear(self) -> None:
        with self._lock:
            if self._entries:
                self.epoch += 1
            self._entries.clear()
            self.resident_bytes = 0


class DeviceBackend(ExecutionBackend):
    """Device-resident merges + kernel gap training (VB E-step and the
    doc-blocked Gibbs sweep).

    capacity   : max cached models (LRU-evicted beyond it)
    max_bytes  : optional cap on resident parameter bytes (evicts LRU
                 until under; a model larger than the cap passes
                 through uncached)
    interpret  : Pallas interpret override (None = auto: interpret off
                 TPU or when MLEGO_KERNEL_INTERPRET=1)
    kernel_estep : route "vb" gap training through the fused E-step
                 kernel (True by default)
    kernel_gibbs : route "gs" gap training through the doc-blocked CGS
                 sweep (``core.gibbs.cgs_fit_blocked``; True by
                 default).  The blocked sampler is statistically — not
                 bit — equivalent to the host exact scan; HostBackend
                 keeps the exact ``cgs_fit``.
    gibbs_block_docs : documents per sampler block on the gs route
                 (more blocks = shorter sequential chain, slightly
                 staler topic-word counts within a sweep)
    profile    : accepted so one profiling flag can be passed to every
                 layer; the mirror of spans onto the profiler belongs
                 to the tracer of the owning session or service

    Every other kind falls back to the host trainer registry.  Fresh
    gap models are *warm-inserted* into the LRU (``note_trained``) so
    the merge that follows training hits the cache instead of
    re-uploading Θ — tracked in ``stats.train_uploads``.
    """

    name = "device"

    def __init__(self, capacity: int = 64, *,
                 max_bytes: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 kernel_estep: bool = True,
                 kernel_gibbs: bool = True,
                 gibbs_block_docs: int = 64,
                 profile: bool = False):
        super().__init__()
        self.cache = self._make_cache(capacity, max_bytes)
        self.interpret = interpret
        self.kernel_estep = kernel_estep
        self.kernel_gibbs = kernel_gibbs
        self.gibbs_block_docs = gibbs_block_docs
        self._store: Optional[ModelStore] = None

    def _make_cache(self, capacity: int,
                    max_bytes: Optional[int]) -> _DeviceModelCache:
        return _DeviceModelCache(capacity, max_bytes)

    # -- lifecycle -------------------------------------------------------
    def bind_store(self, store: ModelStore) -> None:
        if store is self._store:
            return
        if self._store is not None:
            self._store.unsubscribe(self._on_store_event)
        self._store = store
        self.cache.clear()
        store.subscribe(self._on_store_event)

    @property
    def bound_store(self) -> Optional[ModelStore]:
        return self._store

    def _on_store_event(self, event: str, model_id: int) -> None:
        # "remove" drops stale device copies; "add" defends against id
        # collisions from a store that was swapped or reloaded in place.
        self.cache.invalidate(model_id)
        self._sync_cache_counters()

    def quarantine(self) -> None:
        # resident copies on a lost device are garbage; drop them so a
        # re-admitted backend re-uploads from the store
        super().quarantine()
        self.cache.clear()
        self._sync_cache_counters()

    def _fetch(self, model, stat_key: str) -> jax.Array:
        maybe_fail(f"backend.fetch.{self.name}")
        return self.cache.get(model, stat_key)

    # -- merge -----------------------------------------------------------
    def merge(self, parts, kind, cfg):
        maybe_fail(f"backend.merge.{self.name}")
        fam = merge_family_name(kind)
        if fam is None:                  # custom merge callable: host only
            self._count(merges=1, host_fallbacks=1)
            return get_merge(kind)(list(parts), cfg)
        stat_key, bias, base, finish = device_merge_params(fam, cfg)
        t0 = time.perf_counter()
        with self._device_guard(), \
                obs.span("kernel.launch", "backend", op="merge_topics",
                         n_parts=len(parts), backend=self.name):
            with obs.span("merge.stack", "backend"):
                stats = jnp.stack([self._fetch(m, stat_key)
                                   for m in parts])
            with obs.span("merge.kernel", "backend"):
                w = jnp.ones((len(parts),), jnp.float32)
                merged = merge_topics(stats, w, bias=bias, base=base,
                                      interpret=self.interpret)
                merged.block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3
            obs.set_attrs(merge_device_ms=ms)
        self._sync_cache_counters()
        self._count(merges=1, device_launches=1, merge_device_ms=ms)
        with obs.span("merge.finish", "backend",
                      bytes_out=int(merged.nbytes)):
            return finish(np.asarray(merged))

    def merge_many(self, part_lists, kind, cfg):
        """§V.C batch merge stage: one ragged segmented launch.

        Every query's part rows concatenate into a single CSR-style
        ``(R, K, V)`` stack merged by the segmented kernel — zero pad
        rows on any batch shape (``stats.pad_rows`` stays 0 by
        construction; the bucketed launcher this replaced padded within
        each power-of-two bucket)."""
        fam = merge_family_name(kind)
        if fam is None:
            # per-list self.merge counts the merges and fallbacks
            return super().merge_many(part_lists, kind, cfg)
        if len(part_lists) == 1:
            return [self.merge(part_lists[0], kind, cfg)]
        maybe_fail(f"backend.merge.{self.name}")
        stat_key, bias, base, finish = device_merge_params(fam, cfg)
        t0 = time.perf_counter()
        with self._device_guard(), \
                obs.span("kernel.launch", "backend",
                         op="merge_topics_ragged",
                         n_plans=len(part_lists), backend=self.name):
            stats_list, weights_list = [], []
            with obs.span("merge.stack", "backend"):
                for parts in part_lists:
                    stats_list.append(jnp.stack(
                        [self._fetch(m, stat_key) for m in parts]))
                    weights_list.append(
                        jnp.ones((len(parts),), jnp.float32))
            with obs.span("merge.kernel", "backend"):
                merged, pad_rows, launches = merge_topics_ragged(
                    stats_list, weights_list, bias=bias, base=base,
                    interpret=self.interpret)
                for row in merged:
                    row.block_until_ready()
            obs.set_attrs(merge_device_ms=(time.perf_counter() - t0) * 1e3,
                          pad_rows=pad_rows)
        ms = (time.perf_counter() - t0) * 1e3
        # a padding row carries one part's worth of (K, V) f32 bytes —
        # the per-byte cost calibration prices it from this
        row_nbytes = int(stats_list[0][0].nbytes)
        self._sync_cache_counters()
        self._count(merges=len(part_lists), device_launches=launches,
                    merge_device_ms=ms, pad_rows=pad_rows,
                    pad_bytes=pad_rows * row_nbytes)
        with obs.span("merge.finish", "backend",
                      bytes_out=sum(int(row.nbytes) for row in merged)):
            return [finish(np.asarray(row)) for row in merged]

    def _sync_cache_counters(self) -> None:
        c = self.cache
        with self._stats_lock:
            self.stats = replace(self.stats, cache_hits=c.hits,
                                 cache_misses=c.misses,
                                 cache_hit_bytes=c.hit_bytes,
                                 cache_miss_bytes=c.miss_bytes,
                                 cache_evictions=c.evictions,
                                 cache_invalidations=c.invalidations,
                                 cache_resident_bytes=c.resident_bytes)

    # -- training --------------------------------------------------------
    def trainer(self, kind: str) -> TrainerFn:
        if kind == "vb" and self.kernel_estep:
            return self._train_vb_kernel
        if kind == "gs" and self.kernel_gibbs:
            return self._train_gs_kernel
        return get_trainer(kind)

    def kernel_route(self, kind: str) -> bool:
        return ((kind == "vb" and self.kernel_estep)
                or (kind == "gs" and self.kernel_gibbs))

    def note_trained(self, model: MaterializedModel) -> None:
        fam = merge_family_name(model.kind)
        if fam is None:                  # custom merge: no device form
            return
        if self.cache.put(model, device_stat_key(fam)):
            self._count(train_uploads=1)
        self._sync_cache_counters()

    def _train_vb_kernel(self, corpus: Corpus, cfg: LDAConfig,
                         key) -> Dict[str, np.ndarray]:
        from repro.core.vb import vb_fit
        t0 = time.perf_counter()
        with obs.span("train.densify", "backend", d=corpus.n_docs):
            x = doc_term_matrix(corpus)
        with self._device_guard():
            # the wait for λ sits in train.fit; the copy back is all
            # that is left for train.fetch
            with obs.span("train.fit", "backend", bytes_in=int(x.nbytes)):
                lam = vb_fit(jax.device_put(x), key, cfg, use_kernel=True)
                lam.block_until_ready()
            with obs.span("train.fetch", "backend",
                          bytes_out=int(lam.nbytes)):
                lam = np.asarray(lam)
        ms = (time.perf_counter() - t0) * 1e3
        obs.set_attrs(train_device_ms=ms, route="vb_estep")
        self._count(gap_device_trains=1, train_device_ms=ms)
        return {"lam": lam}

    def _train_gs_kernel(self, corpus: Corpus, cfg: LDAConfig, key,
                         global_nkv: Optional[np.ndarray] = None
                         ) -> Dict[str, np.ndarray]:
        from repro.core.gibbs import cgs_fit_blocked
        t0 = time.perf_counter()
        # an explicit interpret override must reach the Pallas body
        # like it does on the merge/E-step routes — use_kernel=None
        # alone would route off-TPU hosts to the jnp reference
        with self._device_guard():
            nkv = cgs_fit_blocked(corpus.tokens, corpus.doc_ids, cfg, key,
                                  global_nkv=global_nkv,
                                  block_docs=self.gibbs_block_docs,
                                  use_kernel=(None if self.interpret is None
                                              else True),
                                  interpret=self.interpret)
        ms = (time.perf_counter() - t0) * 1e3
        obs.set_attrs(train_device_ms=ms, route="gibbs_blocked")
        self._count(gap_device_trains=1, train_device_ms=ms)
        return {"delta_nkv": nkv}


class ShardedDeviceBackend(DeviceBackend):
    """Vocab-sharded merges: each device owns a ``V/ndev`` slice.

    The cache uploads every model statistic as a ``(K, Vp)`` array
    sharded over the mesh's "model" axis (``Vp`` rounds V up so every
    slice is lane-aligned; pad columns are masked out of the row
    normalizer, so their value never matters).  Merges run through the
    shard_map-launched Pallas collectives in
    ``distributed/merge_collective.py``: every device merges its local
    slice (ragged-segmented for batches — zero pad rows), applies the
    family's finisher numerator offset, and joins a per-topic row-
    normalizer psum — the *only* cross-device collective, (K,) per
    query regardless of V.  Normalization therefore happens on device;
    the host-side finisher is bypassed.

    ``max_bytes`` bounds **per-device** residency (global bytes /
    shards), which is the point: a model stack whose total f32 bytes
    exceed one device's budget still merges, because no device ever
    holds more than its slice.  ``env`` defaults to a (1, ndev) mesh
    over every local device and degrades to the unsharded semantics at
    one device.  Gap training is inherited unchanged (single-device
    kernels); trained models are warm-inserted in sharded form.
    """

    name = "device_sharded"

    def __init__(self, capacity: int = 64, *,
                 max_bytes: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 kernel_estep: bool = True,
                 kernel_gibbs: bool = True,
                 gibbs_block_docs: int = 64,
                 env: Optional[MeshEnv] = None,
                 profile: bool = False):
        self.env = env if env is not None else local_mesh_env()
        self.shards = max(1, self.env.tp_size)
        super().__init__(capacity, max_bytes=max_bytes,
                         interpret=interpret, kernel_estep=kernel_estep,
                         kernel_gibbs=kernel_gibbs,
                         gibbs_block_docs=gibbs_block_docs,
                         profile=profile)

    def _make_cache(self, capacity, max_bytes):
        return _DeviceModelCache(capacity, max_bytes,
                                 prepare=self._prepare_stat,
                                 bytes_divisor=self.shards)

    def _prepare_stat(self, arr) -> jax.Array:
        """Pad V for lane-aligned slices and shard over the vocab axis."""
        x = jnp.asarray(arr, jnp.float32)
        v = x.shape[-1]
        vp = padded_vocab(v, self.shards)
        if vp != v:
            x = jnp.pad(x, ((0, 0), (0, vp - v)))
        return jax.device_put(x, self.env.sharding(P(None, "model")))

    # -- merge -----------------------------------------------------------
    def merge(self, parts, kind, cfg):
        maybe_fail(f"backend.merge.{self.name}")
        fam = merge_family_name(kind)
        if fam is None:                  # custom merge callable: host only
            self._count(merges=1, host_fallbacks=1)
            return get_merge(kind)(list(parts), cfg)
        stat_key, bias, base, _ = device_merge_params(fam, cfg)
        v_true = int(parts[0].theta[stat_key].shape[-1])
        t0 = time.perf_counter()
        with self._device_guard(), \
                obs.span("kernel.launch", "backend",
                         op="merge_topics_sharded", n_parts=len(parts),
                         backend=self.name, shards=self.shards):
            with obs.span("merge.stack", "backend"):
                stats = jnp.stack([self._fetch(m, stat_key)
                                   for m in parts])
            with obs.span("merge.kernel", "backend"):
                w = jnp.ones((len(parts),), jnp.float32)
                beta = merge_topics_sharded(
                    stats, w, self.env, bias=bias, base=base,
                    num_offset=device_norm_offset(fam, cfg), v_true=v_true,
                    interpret=default_interpret(self.interpret))
                beta.block_until_ready()
            obs.set_attrs(merge_device_ms=(time.perf_counter() - t0) * 1e3)
        ms = (time.perf_counter() - t0) * 1e3
        self._sync_cache_counters()
        self._count(merges=1, device_launches=1, merge_device_ms=ms)
        with obs.span("allgather", "backend", backend=self.name,
                      bytes_out=int(beta.nbytes), shards=self.shards):
            host = np.asarray(beta)
        return host[:, :v_true]

    def merge_many(self, part_lists, kind, cfg):
        fam = merge_family_name(kind)
        if fam is None:
            return ExecutionBackend.merge_many(self, part_lists, kind, cfg)
        if len(part_lists) == 1:
            return [self.merge(part_lists[0], kind, cfg)]
        maybe_fail(f"backend.merge.{self.name}")
        stat_key, bias, base, _ = device_merge_params(fam, cfg)
        v_true = int(part_lists[0][0].theta[stat_key].shape[-1])
        counts = [len(parts) for parts in part_lists]
        t0 = time.perf_counter()
        with self._device_guard(), \
                obs.span("kernel.launch", "backend",
                         op="merge_topics_ragged_sharded",
                         n_plans=len(part_lists), backend=self.name,
                         shards=self.shards):
            with obs.span("merge.stack", "backend"):
                rows = [self._fetch(m, stat_key)
                        for parts in part_lists for m in parts]
                stats = jnp.stack(rows)
            with obs.span("merge.kernel", "backend"):
                w = jnp.ones((len(rows),), jnp.float32)
                beta = merge_topics_ragged_sharded(
                    stats, w, segment_ids(counts), len(counts), self.env,
                    bias=bias, base=base,
                    num_offset=device_norm_offset(fam, cfg), v_true=v_true,
                    interpret=default_interpret(self.interpret))
                beta.block_until_ready()
            obs.set_attrs(merge_device_ms=(time.perf_counter() - t0) * 1e3)
        ms = (time.perf_counter() - t0) * 1e3
        self._sync_cache_counters()
        self._count(merges=len(part_lists), device_launches=1,
                    merge_device_ms=ms)
        with obs.span("allgather", "backend", backend=self.name,
                      bytes_out=int(beta.nbytes), shards=self.shards):
            host = np.asarray(beta)[:, :, :v_true]
        return [host[i] for i in range(len(counts))]


_FACTORIES = {"host": HostBackend, "device": DeviceBackend,
              "device_sharded": ShardedDeviceBackend}


def make_backend(name: str, **kwargs) -> ExecutionBackend:
    """Construct a backend by name; ``kwargs`` pass to its constructor
    (host ignores ``profile=``)."""
    try:
        factory = _FACTORIES[name]
    except KeyError:
        raise ValueError(f"unknown execution backend {name!r}; one of "
                         f"{BACKEND_NAMES}") from None
    if factory is HostBackend:
        kwargs.pop("profile", None)
    return factory(**kwargs)
