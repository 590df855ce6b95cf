"""Backend compiles inside the measured window, from JAX's own monitoring
events (a persistent-cache hit counts too).  Warm-up should leave none."""


def read(ctx):
    return ctx.window_compiles
