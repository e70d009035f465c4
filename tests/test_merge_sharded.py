"""Vocab-sharded merge parity: ShardedDeviceBackend vs single device.

The trivial one-device mesh runs in-process; real multi-device runs
fork a subprocess with ``--xla_force_host_platform_device_count=8``
(the main pytest process must keep the single real CPU device) and
``MLEGO_KERNEL_INTERPRET=1`` so the shard_map-launched Pallas bodies
execute on CPU.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.api.backend import HostBackend, ShardedDeviceBackend
from repro.configs.lda_default import LDAConfig
from repro.core.lda import MaterializedModel
from repro.core.plans import Interval
from repro.distributed.sharding import local_mesh_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = LDAConfig(n_topics=6, vocab_size=150, alpha=0.5, eta=0.05,
                max_iters=6, e_step_iters=5, gibbs_sweeps=6)
RNG = np.random.default_rng(23)


def run_sub(body: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    # virtual CPU devices: the child must never contend for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["MLEGO_KERNEL_INTERPRET"] = "1"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def _models(n, kind, k=CFG.n_topics, v=CFG.vocab_size, seed=0):
    rng = np.random.default_rng(seed)
    key = "lam" if kind == "vb" else "delta_nkv"
    return [MaterializedModel(
        i, Interval(float(i), float(i) + 1.0), 10, 100, kind,
        {key: rng.gamma(1.0, 1.0, (k, v)).astype(np.float32)})
        for i in range(n)]


# ---------------------------------------------------------------------------
# trivial one-device mesh (in-process): sharded semantics degrade cleanly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["vb", "gs"])
def test_single_device_mesh_matches_host(kind):
    env = local_mesh_env(max_devices=1)
    sharded = ShardedDeviceBackend(interpret=True, env=env)
    host = HostBackend()
    ms = _models(4, kind)
    np.testing.assert_allclose(
        sharded.merge(ms, kind, CFG), host.merge(ms, kind, CFG),
        rtol=1e-5, atol=1e-5)
    assert sharded.shards == 1


@pytest.mark.parametrize("kind", ["vb", "gs"])
def test_single_device_mesh_merge_many_matches_host(kind):
    env = local_mesh_env(max_devices=1)
    sharded = ShardedDeviceBackend(interpret=True, env=env)
    host = HostBackend()
    ms = _models(6, kind)
    batches = [ms[:1], ms[1:4], ms[4:]]       # ragged widths 1/3/2
    got = sharded.merge_many(batches, kind, CFG)
    want = host.merge_many(batches, kind, CFG)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    assert sharded.stats.pad_rows == 0
    assert sharded.stats.device_launches == 1


# ---------------------------------------------------------------------------
# 8-device mesh (subprocess): parity + over-budget model stacks
# ---------------------------------------------------------------------------

SUB_COMMON = """
import numpy as np
from repro.api.backend import DeviceBackend, HostBackend, ShardedDeviceBackend
from repro.configs.lda_default import LDAConfig
from repro.core.lda import MaterializedModel
from repro.core.plans import Interval

CFG = LDAConfig(n_topics=6, vocab_size=150, alpha=0.5, eta=0.05)

def models(n, kind, k=6, v=150, seed=0):
    rng = np.random.default_rng(seed)
    key = "lam" if kind == "vb" else "delta_nkv"
    return [MaterializedModel(
        i, Interval(float(i), float(i) + 1.0), 10, 100, kind,
        {key: rng.gamma(1.0, 1.0, (k, v)).astype(np.float32)})
        for i in range(n)]
"""


def test_sharded_merge_matches_single_device_8dev():
    run_sub(SUB_COMMON + """
for kind in ("vb", "gs"):
    sharded = ShardedDeviceBackend()
    assert sharded.shards == 8, sharded.shards
    host = HostBackend()
    ms = models(5, kind)
    np.testing.assert_allclose(
        sharded.merge(ms, kind, CFG), host.merge(ms, kind, CFG),
        rtol=1e-5, atol=1e-5)
print("sharded merge OK")
""")


def test_sharded_ragged_batch_matches_single_device_8dev():
    run_sub(SUB_COMMON + """
for kind in ("vb", "gs"):
    sharded = ShardedDeviceBackend()
    host = HostBackend()
    ms = models(8, kind)
    batches = [ms[:1], ms[1:2], ms[2:7], ms[7:]]   # widths 1/1/5/1
    got = sharded.merge_many(batches, kind, CFG)
    want = host.merge_many(batches, kind, CFG)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    assert sharded.stats.pad_rows == 0
    assert sharded.stats.device_launches == 1
print("sharded ragged OK")
""")


def test_sharded_cache_holds_stack_over_single_device_budget():
    run_sub(SUB_COMMON + """
# Budget sized so ONE model already busts it unsharded (6 x 1000 f32
# = 24000 B > 20000) but each device's 1/8 vocab slice set fits
# (6 x 3072 B = 18432): the sharded cache keeps the whole stack
# resident while the single-device cache can't hold even one model.
kind, n, max_bytes = "vb", 6, 20_000
ms = models(n, kind, v=1000)
host = HostBackend()
want = host.merge(ms, kind, CFG)

sharded = ShardedDeviceBackend(max_bytes=max_bytes)
got = sharded.merge(ms, kind, CFG)
np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
assert sum(m.theta["lam"].nbytes for m in ms) > max_bytes
assert len(sharded.cache) == n, (len(sharded.cache), n)
assert sharded.cache.evictions == 0
assert sharded.cache.resident_bytes <= max_bytes

single = DeviceBackend(max_bytes=max_bytes)
got1 = single.merge(ms, kind, CFG)
np.testing.assert_allclose(got1, want, rtol=1e-5, atol=1e-5)
assert single.cache.evictions > 0 or len(single.cache) < n
print("budget OK")
""")
