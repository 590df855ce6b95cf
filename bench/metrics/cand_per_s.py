"""Candidates that got a definitive outcome (ranked, infeasible or pruned),
over every sweep of the window, per second of the window: from the start
of its first sweep to the end of its last.  Host clock."""

DEFINITIVE = ("ok", "infeasible", "pruned")


def read(ctx):
    done = sum(1 for s in ctx.sweeps for st in s.statuses.values()
               if st in DEFINITIVE)
    return done / ctx.window_s
