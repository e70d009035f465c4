"""Programs that JAX compiled, or loaded from its persistent cache,
inside the measured window (``jax.monitoring`` backend-compile events).
Warm-up meets every shape, so a reading above 0 is a shape it missed."""


def read(ctx):
    return float(ctx.compiles_in_window)
