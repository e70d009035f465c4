"""Compile the main-path kernels for a TPU v5e at the default LDA width.

Nothing runs: each case lowers and compiles with the TPU compiler for a
described (not attached) v5e:2x2, so a kernel the chip would refuse —
a block that breaks the (8, 128) tiling rule, more scoped VMEM than a
kernel may use, an op Mosaic cannot lower — fails here, on a CPU host.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.lda_default import LDAConfig
from repro.distributed.merge_collective import (merge_topics_ragged_sharded,
                                                merge_topics_sharded)
from repro.distributed.sharding import MeshEnv
from repro.kernels.gibbs_sweep.ops import gibbs_sweep
from repro.kernels.merge_topics.ops import (_merge_topics_ragged_impl,
                                            merge_topics)
from repro.kernels.vb_estep.ops import vb_estep

CFG = LDAConfig()
K, V = CFG.n_topics, CFG.vocab_size


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot
    # be read back without one; keep these compiles out of it (JAX
    # decides once whether the cache is used: reset that decision)
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel not compiled"
    return compiled


def test_merge_topics_compiles_at_64_parts(one_chip):
    s = jax.ShapeDtypeStruct
    _compile(lambda st, w: merge_topics(st, w, bias=CFG.eta, base=CFG.eta,
                                        interpret=False),
             s((64, K, V), jnp.float32, sharding=one_chip),
             s((64,), jnp.float32, sharding=one_chip))


def test_ragged_merge_compiles_for_three_queries(one_chip):
    s = jax.ShapeDtypeStruct
    _compile(lambda st, w, seg: _merge_topics_ragged_impl(
                 st, w, seg, 3, CFG.eta, CFG.eta, interpret=False),
             s((48, K, V), jnp.float32, sharding=one_chip),
             s((48,), jnp.float32, sharding=one_chip),
             s((48,), jnp.int32, sharding=one_chip))


def test_vb_estep_compiles_at_default_vocab(one_chip):
    s = jax.ShapeDtypeStruct
    d = 2048
    _compile(lambda x, e, g: vb_estep(x, e, g, CFG.alpha, CFG.e_step_iters,
                                      interpret=False),
             s((d, V), jnp.float32, sharding=one_chip),
             s((K, V), jnp.float32, sharding=one_chip),
             s((d, K), jnp.float32, sharding=one_chip))


def test_gibbs_sweep_compiles_at_default_vocab(one_chip):
    s = jax.ShapeDtypeStruct
    b, t, bd, k = 4, 6500, 64, 128
    i32 = dict(dtype=jnp.int32, sharding=one_chip)
    f32 = dict(dtype=jnp.float32, sharding=one_chip)
    _compile(lambda *a: gibbs_sweep(*a, CFG.alpha, use_kernel=True,
                                    interpret=False),
             s((b, t), **i32), s((b, t), **i32), s((b, t), **f32),
             s((b, t), **f32), s((b, t), **i32), s((b, bd, k), **f32),
             s((k, V), **f32), s((k,), **f32))


@pytest.mark.parametrize("ragged", [False, True])
def test_sharded_merge_compiles_on_four_devices(topo, ragged):
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    env = MeshEnv(mesh=mesh, profile="serve")
    vocab = NamedSharding(mesh, P(None, None, "model"))
    rep = NamedSharding(mesh, P())
    s = jax.ShapeDtypeStruct
    stats = s((48, K, V), jnp.float32, sharding=vocab)
    w = s((48,), jnp.float32, sharding=rep)
    kw = dict(bias=CFG.eta, base=CFG.eta, num_offset=0.0, v_true=V,
              interpret=False)
    if ragged:
        compiled = _compile(
            lambda st, w, seg: merge_topics_ragged_sharded(
                st, w, seg, 3, env, **kw),
            stats, w, s((48,), jnp.int32, sharding=rep))
    else:
        compiled = _compile(
            lambda st, w: merge_topics_sharded(st, w, env, **kw), stats, w)
    # each device's kernel sees a quarter of the vocabulary
    kernel = [ln for ln in compiled.as_text().splitlines()
              if "tpu_custom_call" in ln][0]
    assert f",{V // 4}]" in kernel.split("custom-call")[0]
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args < 48 * K * V * 4 // 3
