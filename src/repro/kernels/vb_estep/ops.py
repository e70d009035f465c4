"""jit'd public wrapper for the fused VB E-step kernel.

On this CPU host the kernel runs in interpret mode (correctness path);
on TPU it compiles to Mosaic.  The wrapper pads K to 128 and V to a
128-multiple (MXU alignment) and strips the padding on the way out —
pad topics receive exp(ψ(0-ish)) ≈ 0 mass and contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.kernels.vb_estep.vb_estep import vb_estep_pallas


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("alpha", "n_iters", "block_d",
                                             "interpret"))
def vb_estep(x, exp_elog_beta, gamma0, alpha: float, n_iters: int,
             *, block_d: int = 128, interpret: bool = None):
    """Drop-in fused replacement for core.vb.vb_estep's inner loop."""
    interpret = default_interpret(interpret)
    d, v = x.shape
    k = exp_elog_beta.shape[0]
    kp = _round_up(k, 128)
    # V pads to a whole number of the kernel's V chunks (pad columns
    # carry x = 0, so they add nothing to any reduction)
    bv = min(512, _round_up(v, 128))
    vp = _round_up(v, bv)
    # D must pad to a whole number of doc blocks: a ragged boundary
    # block would stream out-of-bounds rows into the sstats reduction
    # (x pads are zero, so whole pad blocks contribute nothing).
    bd = min(block_d, _round_up(d, 8))
    dp = _round_up(d, bd)
    block_d = bd
    # named scope: HLO metadata + jax.profiler timelines attribute the
    # launch to the MLego op by name
    with jax.named_scope("mlego.vb_estep"):
        if (kp, vp, dp) != (k, v, d):
            x = jnp.pad(x, ((0, dp - d), (0, vp - v)))
            # pad eeβ with ~0 (tiny positive keeps phinorm finite)
            exp_elog_beta = jnp.pad(exp_elog_beta,
                                    ((0, kp - k), (0, vp - v)),
                                    constant_values=1e-30)
            gamma0 = jnp.pad(gamma0, ((0, dp - d), (0, kp - k)),
                             constant_values=alpha)
        gamma, sstats = vb_estep_pallas(x, exp_elog_beta, gamma0, alpha,
                                        n_iters, block_d=block_d,
                                        block_v=bv, interpret=interpret)
        return gamma[:d, :k], sstats[:k, :v]
