"""Persistent-compilation-cache misses during set-up and the window, as
``jax.monitoring`` counts them: 0 on every run after a cell's first in a
checkout."""


def read(ctx):
    return float(
        ctx.setup_compile["cache_misses"] + ctx.window_compile["cache_misses"]
    )
