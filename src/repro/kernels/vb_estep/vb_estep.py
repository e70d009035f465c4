"""Pallas TPU kernel: fused LDA VB E-step.

One grid step owns a block of documents and runs the whole
coordinate-ascent fixed point in VMEM:

    repeat n_iters:
        eeθ     = exp(ψ(γ) − ψ(Σγ))          (VPU, fused digamma)
        phinorm = eeθ @ eeβ                   (MXU,  BD×K @ K×V)
        γ       = α + eeθ * ((x/phinorm) @ eeβᵀ)   (MXU, BD×V @ V×K)

and finally accumulates this block's sufficient statistics
    sstats += eeθᵀ @ (x/phinorm) * eeβ        (MXU, K×BD @ BD×V)
into a revisited output block (grid is sequential on TPU, so the
accumulation is race-free).

Tiling: BD documents × full V in VMEM, with every V reduction run as
a loop over 512-wide V chunks so no (BD, V) intermediate is ever
whole; the scoped-VMEM limit is set from the block shapes.  K is
padded to 128 (MXU lane), V to a whole number of chunks.  The digamma
is an 8-term shift + asymptotic series — pure VPU ops, no
transcendental table lookups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _digamma(x):
    """ψ(x) for x > 0 — recurrence shift to x >= 8, then asymptotic."""
    shift = jnp.zeros_like(x)
    for _ in range(8):
        small = x < 8.0
        shift = shift - jnp.where(small, 1.0 / x, 0.0)
        x = jnp.where(small, x + 1.0, x)
    inv = 1.0 / x
    inv2 = inv * inv
    # ψ(x) ≈ ln x − 1/(2x) − 1/(12x²) + 1/(120x⁴) − 1/(252x⁶)
    series = (jnp.log(x) - 0.5 * inv
              - inv2 * (1.0 / 12.0 - inv2 * (1.0 / 120.0 - inv2 / 252.0)))
    return series + shift


def _exp_dirichlet(g):
    return jnp.exp(_digamma(g) - _digamma(g.sum(-1, keepdims=True)))


def _contract_v(a, b):
    """(M, V) x (N, V) -> (M, N): contract the shared vocab axis."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, eeb_ref, g0_ref, gamma_out, sstats_out, *, alpha: float,
            n_iters: int, block_v: int):
    i = pl.program_id(0)
    bd, v = x_ref.shape
    k = eeb_ref.shape[0]
    n_chunks = v // block_v

    def chunk(j):
        sl = pl.ds(pl.multiple_of(j * block_v, block_v), block_v)
        return x_ref[:, sl], eeb_ref[:, sl], sl

    def ratio_dot(eet):
        # Σ_v (x / phinorm)[d, v] · eeβ[k, v], one V chunk at a time so
        # the (BD, V) intermediates never materialize whole
        def body(j, acc):
            x, eeb, _ = chunk(j)
            phinorm = jnp.dot(eet, eeb,
                              preferred_element_type=jnp.float32) + 1e-30
            return acc + _contract_v(x / phinorm, eeb)
        return jax.lax.fori_loop(0, n_chunks, body,
                                 jnp.zeros((bd, k), jnp.float32))

    def body(_, gamma):
        eet = _exp_dirichlet(gamma)
        return alpha + eet * ratio_dot(eet)

    gamma = jax.lax.fori_loop(0, n_iters, body, g0_ref[...])
    gamma_out[...] = gamma
    eet = _exp_dirichlet(gamma)
    eet_t = eet.T                                    # (K, BD)

    @pl.when(i == 0)
    def _init():
        sstats_out[...] = jnp.zeros_like(sstats_out)

    def acc_sstats(j, carry):
        x, eeb, sl = chunk(j)
        phinorm = jnp.dot(eet, eeb,
                          preferred_element_type=jnp.float32) + 1e-30
        sstats_out[:, sl] += jnp.dot(
            eet_t, x / phinorm, preferred_element_type=jnp.float32) * eeb
        return carry

    jax.lax.fori_loop(0, n_chunks, acc_sstats, 0)


def _vmem_bytes(bd: int, k: int, v: int, block_v: int) -> int:
    """VMEM the kernel needs: double-buffered (BD, V), (K, V) in/out
    blocks and (BD, K) blocks, plus the per-chunk intermediates."""
    f32 = 4
    blocks = 2 * f32 * (bd * v + 2 * k * v + 2 * bd * k)
    chunk = f32 * (3 * bd * block_v + 2 * k * block_v + 4 * bd * k)
    return blocks + chunk


def vb_estep_pallas(x, exp_elog_beta, gamma0, alpha: float, n_iters: int,
                    *, block_d: int = 128, block_v: int = 512,
                    interpret: bool = False):
    """x: (D, V) f32; exp_elog_beta: (K, V) f32; gamma0: (D, K) f32.

    D must be a multiple of ``block_d`` (or smaller than it) and V of
    ``block_v``; ops.vb_estep pads to both.
    """
    d, v = x.shape
    k = exp_elog_beta.shape[0]
    bd = min(block_d, d)
    bv = min(block_v, v)
    n_blocks = pl.cdiv(d, bd)
    # the whole-V blocks exceed the default scoped VMEM at realistic
    # vocabularies; ask for what the shapes need (plus headroom for
    # Mosaic's own temporaries)
    vmem = _vmem_bytes(bd, k, v, bv) + (8 << 20)

    kernel = functools.partial(_kernel, alpha=alpha, n_iters=n_iters,
                               block_v=bv)
    gamma, sstats = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bd, v), lambda i: (i, 0)),
            pl.BlockSpec((k, v), lambda i: (0, 0)),
            pl.BlockSpec((bd, k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bd, k), lambda i: (i, 0)),
            pl.BlockSpec((k, v), lambda i: (0, 0)),   # revisited: accumulate
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, k), jnp.float32),
            jax.ShapeDtypeStruct((k, v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(x, exp_elog_beta, gamma0)
    return gamma, sstats
