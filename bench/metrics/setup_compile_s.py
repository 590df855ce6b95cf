"""Seconds of backend compiling during set-up, from JAX's own monitoring
events (with a warm persistent cache, the time to load the cached
programs)."""


def read(ctx):
    return ctx.setup_compiles["compile_s"]
