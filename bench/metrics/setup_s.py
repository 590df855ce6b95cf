"""Seconds from the start of the process to the start of the window:
imports, the device, the inputs and the warm-up sweeps, compiling
included.  Host clock."""


def read(ctx):
    return ctx.setup_s
