"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# vb_estep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,v,k", [(32, 128, 16), (65, 200, 100),
                                   (128, 384, 128), (8, 64, 10),
                                   # D > block_d and not a block multiple:
                                   # regression for the ragged boundary
                                   # block reading garbage into sstats
                                   (135, 150, 6), (300, 192, 12),
                                   # V over several kernel V chunks and
                                   # not a chunk multiple
                                   (40, 1100, 20)])
def test_vb_estep_kernel(d, v, k):
    from repro.kernels.vb_estep.ops import vb_estep
    from repro.kernels.vb_estep.ref import vb_estep_ref
    x = jnp.asarray(RNG.poisson(0.5, (d, v)), jnp.float32)
    eeb = jnp.asarray(RNG.gamma(1.0, 1.0, (k, v)), jnp.float32)
    eeb = eeb / eeb.sum(1, keepdims=True)
    g0 = jnp.ones((d, k), jnp.float32)
    g1, s1 = vb_estep(x, eeb, g0, 0.5, 8, interpret=True)
    g2, s2 = vb_estep_ref(x, eeb, g0, 0.5, 8)
    np.testing.assert_allclose(g1, g2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# merge_topics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,v", [(1, 16, 64), (5, 100, 300), (12, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_merge_topics_kernel(n, k, v, dtype):
    from repro.kernels.merge_topics.ops import merge_topics
    from repro.kernels.merge_topics.ref import merge_topics_ref
    st = jnp.asarray(RNG.normal(size=(n, k, v)), dtype)
    w = jnp.asarray(RNG.uniform(0.2, 2.0, n), jnp.float32)
    out = merge_topics(st, w, bias=0.05, base=0.05, interpret=True)
    ref = merge_topics_ref(st, w, 0.05, 0.05)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,n,k,v", [(1, 3, 16, 64), (4, 5, 100, 300),
                                     (3, 1, 24, 128)])
def test_merge_topics_batched_kernel(b, n, k, v):
    """One launch merging b independent plans, incl. zero-weight pad
    rows (how ragged submit_many batches share a launch)."""
    from repro.kernels.merge_topics.ops import merge_topics_batch
    from repro.kernels.merge_topics.ref import merge_topics_batched_ref
    st = jnp.asarray(RNG.normal(size=(b, n, k, v)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.2, 2.0, (b, n)), jnp.float32)
    if n > 1:
        w = w.at[0, -1:].set(0.0)        # simulate a ragged batch pad
    out = merge_topics_batch(st, w, bias=0.05, base=0.05, interpret=True)
    ref = merge_topics_batched_ref(st, w, 0.05, 0.05)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kvh,hd", [
    (1, 128, 4, 4, 32),    # MHA
    (2, 128, 8, 2, 64),    # GQA 4:1
    (1, 256, 5, 1, 64),    # MQA, odd heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, s, h, kvh, hd, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, kvh, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, kvh, hd)), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_windowed():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = jnp.asarray(RNG.normal(size=(1, 192, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 192, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 192, 2, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=50, block_q=64,
                          block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=50)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kvh,hd,pos", [
    (2, 256, 4, 2, 64, 0),       # first token
    (2, 256, 4, 2, 64, 255),     # full cache
    (1, 384, 6, 1, 32, 100),     # MQA mid-stream
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel(b, s, h, kvh, hd, pos, dtype):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = jnp.asarray(RNG.normal(size=(b, 1, h, hd)), dtype)
    kc = jnp.asarray(RNG.normal(size=(b, s, kvh, hd)), dtype)
    vc = jnp.asarray(RNG.normal(size=(b, s, kvh, hd)), dtype)
    out = decode_attention(q, kc, vc, pos, block_k=128, interpret=True)
    ref = decode_attention_ref(q, kc, vc, pos)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_windowed():
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = jnp.asarray(RNG.normal(size=(1, 1, 4, 32)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(1, 512, 2, 32)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(1, 512, 2, 32)), jnp.float32)
    out = decode_attention(q, kc, vc, 300, window=64, block_k=128,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, 300, window=64)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_kernel_split_k_matches_device_split():
    """Core-level split-K (kernel) == device-level split (attention.py
    decode path run unsharded) — the two splits compose."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.models.attention import flash_attention_local
    q = jnp.asarray(RNG.normal(size=(2, 1, 4, 32)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(2, 128, 2, 32)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(2, 128, 2, 32)), jnp.float32)
    pos = 90
    a = decode_attention(q, kc, vc, pos, block_k=32, interpret=True)
    qpos = jnp.full((1,), pos, jnp.int32)
    kpos = jnp.arange(128)
    b = flash_attention_local(q, kc, vc, qpos, kpos, causal=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# sLSTM scan (VMEM-resident recurrence)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,b,h,hd,chunk", [
    (32, 2, 2, 16, 8),     # multi-chunk
    (64, 4, 4, 32, 64),    # single chunk
    (48, 1, 3, 8, 16),     # odd head count, B=1
])
def test_slstm_scan_kernel(s, b, h, hd, chunk):
    from repro.kernels.slstm_scan.ops import slstm_scan
    from repro.kernels.slstm_scan.ref import slstm_scan_ref
    xpre = jnp.asarray(RNG.normal(size=(s, b, 4, h, hd)), jnp.float32) * 0.5
    r = jnp.asarray(RNG.normal(size=(h, hd, 4 * hd)), jnp.float32) * (hd ** -0.5)
    out = slstm_scan(xpre, r, chunk=chunk, interpret=True)
    z = jnp.zeros((b, h, hd), jnp.float32)
    ref, _ = slstm_scan_ref(xpre, r, z, z, z,
                            jnp.full((b, h, hd), -1e30, jnp.float32))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_slstm_scan_matches_model_layer():
    """Kernel == the recurrent.py sLSTM scan used by the xlstm arch."""
    from repro.kernels.slstm_scan.ops import slstm_scan
    from repro.models.recurrent import _slstm_local_scan
    s, b, h, hd = 24, 2, 2, 8
    xpre_bshd = jnp.asarray(RNG.normal(size=(b, s, 4, h, hd)),
                            jnp.float32) * 0.5
    r = jnp.asarray(RNG.normal(size=(h, hd, 4 * hd)), jnp.float32) * 0.3
    z = jnp.zeros((b, h, hd), jnp.float32)
    ref, _ = _slstm_local_scan(xpre_bshd, r,
                               (z, z, z, jnp.full((b, h, hd), -1e30)))
    out = slstm_scan(xpre_bshd.transpose(1, 0, 2, 3, 4), r, chunk=8,
                     interpret=True)
    np.testing.assert_allclose(out.transpose(1, 0, 2, 3), ref,
                               rtol=1e-5, atol=1e-5)
