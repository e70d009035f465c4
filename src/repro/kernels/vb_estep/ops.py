"""jit'd public wrapper for the fused VB E-step kernel.

On this CPU host the kernel runs in interpret mode (correctness path);
on TPU it compiles to Mosaic.  The wrapper pads K to 128 and V to a
128-multiple (MXU alignment) and strips the padding on the way out —
pad topics receive exp(ψ(0-ish)) ≈ 0 mass and contribute nothing.
A caller that runs the E-step many times on one x (``core.vb.vb_fit``)
pads once itself, to ``padded_dims``, and calls ``vb_estep_padded``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.kernels.vb_estep.vb_estep import vb_estep_pallas

BLOCK_V = 512


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def padded_dims(d: int, v: int, k: int,
                block_d: int = 128) -> Tuple[int, int, int]:
    """(Dp, Vp, Kp), the kernel's layout of a (D, V) x and (K, V) E[β].

    K pads to 128.  V pads to a whole number of the kernel's V chunks
    (pad columns carry x = 0, so they add nothing to any reduction).  D
    must pad to a whole number of doc blocks: a ragged boundary block
    would stream out-of-bounds rows into the sstats reduction (x pads
    are zero, so whole pad blocks contribute nothing)."""
    bv = min(BLOCK_V, _round_up(v, 128))
    bd = min(block_d, _round_up(d, 8))
    return _round_up(d, bd), _round_up(v, bv), _round_up(k, 128)


@functools.partial(jax.jit, static_argnames=("alpha", "n_iters", "block_d",
                                             "interpret"))
def vb_estep_padded(x, exp_elog_beta, gamma0, alpha: float, n_iters: int,
                    *, block_d: int = 128, interpret: bool = None):
    """The kernel on inputs already in its layout: x (Dp, Vp) with zero
    pads, E[β] (Kp, Vp) with pad entries 1e-30 (tiny positive keeps
    phinorm finite), γ₀ (Dp, Kp) with pad entries α, all to
    ``padded_dims``.  Returns the padded (γ, sstats)."""
    interpret = default_interpret(interpret)
    # named scope: HLO metadata + jax.profiler timelines attribute the
    # launch to the MLego op by name
    with jax.named_scope("mlego.vb_estep"):
        return vb_estep_pallas(x, exp_elog_beta, gamma0, alpha, n_iters,
                               block_d=block_d, block_v=BLOCK_V,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("alpha", "n_iters", "block_d",
                                             "interpret"))
def vb_estep(x, exp_elog_beta, gamma0, alpha: float, n_iters: int,
             *, block_d: int = 128, interpret: bool = None):
    """Drop-in fused replacement for core.vb.vb_estep's inner loop."""
    d, v = x.shape
    k = exp_elog_beta.shape[0]
    dp, vp, kp = padded_dims(d, v, k, block_d)
    with jax.named_scope("mlego.vb_estep"):
        x = jnp.pad(x, ((0, dp - d), (0, vp - v)))
        exp_elog_beta = jnp.pad(exp_elog_beta, ((0, kp - k), (0, vp - v)),
                                constant_values=1e-30)
        gamma0 = jnp.pad(gamma0, ((0, dp - d), (0, kp - k)),
                         constant_values=alpha)
    gamma, sstats = vb_estep_padded(x, exp_elog_beta, gamma0, alpha,
                                    n_iters, block_d=block_d,
                                    interpret=interpret)
    return gamma[:d, :k], sstats[:k, :v]
