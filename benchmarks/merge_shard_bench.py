"""Vocab-sharded merge bench: ragged segmented launch, 8-way V slices.

Forks one subprocess pinned to the CPU (``JAX_PLATFORMS=cpu``, so it
never contends for an accelerator the parent holds) with
``--xla_force_host_platform_device_count=8`` and
``MLEGO_KERNEL_INTERPRET=1``, merges one ragged batch through the
single-device ``DeviceBackend`` and the vocab-sharded
``ShardedDeviceBackend``, and reports launches, pad rows, per-device
resident bytes and wall time for each.  On CPU the walls measure the
interpret-mode overhead, not TPU speed — the structural columns
(launches == 1, ``pad_rows == 0``, per-device bytes == global/ndev)
are the regression surface CI watches.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BODY = """
import json, time
import numpy as np
from repro.api.backend import DeviceBackend, ShardedDeviceBackend
from repro.configs.lda_default import LDAConfig
from repro.core.lda import MaterializedModel
from repro.core.plans import Interval

K, V, COUNTS = {k}, {v}, {counts}
CFG = LDAConfig(n_topics=K, vocab_size=V, eta=0.05)
rng = np.random.default_rng(0)
ms, mid = [], 0
batches = []
for n in COUNTS:
    parts = []
    for _ in range(n):
        parts.append(MaterializedModel(
            mid, Interval(float(mid), float(mid) + 1.0), 10, 100, "vb",
            {{"lam": rng.gamma(1.0, 1.0, (K, V)).astype(np.float32)}}))
        mid += 1
    batches.append(parts)

def bench(backend):
    backend.merge_many(batches, "vb", CFG)      # warm: uploads + compile
    s0 = backend.stats
    t0 = time.perf_counter()
    out = backend.merge_many(batches, "vb", CFG)
    wall = time.perf_counter() - t0
    s = backend.stats.delta(s0)
    return out, dict(wall_s=wall, launches=s.device_launches,
                     pad_rows=s.pad_rows,
                     per_device_bytes=backend.cache.resident_bytes,
                     shards=backend.shards)

single, single_m = bench(DeviceBackend())
sharded, sharded_m = bench(ShardedDeviceBackend())
err = max(float(np.abs(a - b).max()) for a, b in zip(single, sharded))
print(json.dumps(dict(k=K, v=V, counts=COUNTS, rows=sum(COUNTS),
                      single=single_m, sharded=sharded_m,
                      max_abs_err=err)))
"""


def run(quick: bool = False) -> dict:
    k, v = (8, 512) if quick else (16, 2048)
    counts = [1, 1, 4, 1] if quick else [1, 3, 1, 8, 2, 1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["MLEGO_KERNEL_INTERPRET"] = "1"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    body = textwrap.dedent(_BODY).format(k=k, v=v, counts=counts)
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"merge_shard subprocess failed:\n"
                           f"{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])
