"""Backend-compile seconds inside set-up (XLA's compile, or the read of a
cached executable), as ``jax.monitoring`` reports them."""


def read(ctx):
    return ctx.setup_compile["compile_s"]
