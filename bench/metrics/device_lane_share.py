"""Share of the simulated (feasible) candidates of the window's sweeps
whose result came out of the device's lockstep scan, in percent; the rest
ran on the host's exact serial path.  The program's own lane counters
(``BatchStats.lockstep_lanes``), exact for a seed."""


def read(ctx):
    simulated = sum(s.simulated for s in ctx.sweeps)
    if not simulated:
        return None
    return 100.0 * sum(s.lockstep_lanes for s in ctx.sweeps) / simulated
