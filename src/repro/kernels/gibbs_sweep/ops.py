"""jit'd public wrapper for the doc-blocked CGS sweep kernel.

``gibbs_sweep`` runs ONE blocked sweep.  Route selection mirrors the
other kernel packages but adds a host route: on TPU (or when
``MLEGO_KERNEL_INTERPRET=1`` forces the CI correctness leg) the Pallas
kernel body executes; everywhere else the vmapped jnp reference runs —
it is the same math, and XLA's batched lowering of the vmap IS the
blocked algorithm's speedup on hosts (sequential chain length drops
from Σ tokens to max tokens-per-block).  Interpret-mode Pallas would
serialize the grid and forfeit exactly that win, so it is reserved for
the kernel-exercising CI leg.

The kernel path pads K to 128 lanes, BD to 8 sublanes and T to whole
SMEM token chunks, and strips the padding on the way out; pad topics
are masked out of the conditional (``k_real``), pad tokens carry zero
mask, and the snapshot is fed to the kernel transposed as (V, K) so
the per-token topic gather is a lane-aligned row read.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret, interpret_forced, on_tpu
from repro.kernels.gibbs_sweep.gibbs_sweep import gibbs_sweep_pallas
from repro.kernels.gibbs_sweep.ref import gibbs_sweep_ref, token_counts


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def default_use_kernel(use_kernel: Optional[bool] = None) -> bool:
    """Resolve the kernel-vs-host-route default (see module docstring)."""
    if use_kernel is not None:
        return use_kernel
    return interpret_forced() or on_tpu()


@functools.partial(jax.jit, static_argnames=("alpha", "use_kernel",
                                             "interpret"))
def gibbs_sweep(words, ldoc, mask, u, z, nkd, prior, prior_k,
                alpha: float, *, use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None):
    """One blocked CGS sweep.

    words/ldoc/mask/u/z: (B, T); nkd: (B, BD, K); prior: (K, V)
    snapshot + global + β; prior_k: (K,) row sums (with Vβ).
    Returns (z', nkd', nkv (K, V)) with nkv the new assignments' count
    matrix (the next snapshot / final ΔN_kv source).
    """
    use_kernel = default_use_kernel(use_kernel)
    k, v = prior.shape
    if not use_kernel:
        return gibbs_sweep_ref(words, ldoc, mask, u, z, nkd, prior,
                               prior_k, alpha)
    interpret = default_interpret(interpret)
    b, t = words.shape
    bd = nkd.shape[1]
    kp, bdp = _round_up(k, 128), _round_up(bd, 8)
    # tokens run in SMEM chunks of 1024 (XLA's tile for a 1-D SMEM
    # operand); pad tokens carry mask 0
    block_t = 1024
    tp = _round_up(t, block_t)
    # named scope: HLO metadata + jax.profiler timelines attribute the
    # launch to the MLego op by name
    with jax.named_scope("mlego.gibbs_sweep"):
        pad_row = ((0, 0), (0, tp - t))
        # pad topics carry 1.0 so den stays finite; they are masked out
        # of the conditional via k_real and never sampled
        z_new, nkd_new = gibbs_sweep_pallas(
            jnp.pad(words, pad_row), jnp.pad(ldoc, pad_row),
            jnp.pad(mask, pad_row), jnp.pad(u, pad_row), jnp.pad(z, pad_row),
            jnp.pad(nkd, ((0, 0), (0, bdp - bd), (0, kp - k))),
            jnp.pad(prior, ((0, kp - k), (0, 0)), constant_values=1.0).T,
            jnp.pad(prior_k, (0, kp - k), constant_values=1.0).reshape(1, kp),
            alpha, k, block_t=block_t, interpret=interpret)
        z_new = z_new[:, :t]
        return (z_new, nkd_new[:, :bd, :k],
                token_counts(z_new, words, mask, k, v))
