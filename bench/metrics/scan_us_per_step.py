"""Device time of the lockstep scan per step, in microseconds: the device
durations of the scan's XLA module in the traced window, over the steps
that its launches ran.  A launch's steps are the trip count of the scan's
``while`` loop, read from the loop's operand shapes in the trace (a scan
over ``T`` rows stacks its inputs as ``[T, lanes, ...]``), so padding a
launch to more rows shows as more steps.  Profiler trace; nothing when a
launch's loop is missing from the trace.

The scan is ``jax.jit`` of the function ``run`` that
``repro.core.jaxsim._runner`` returns, so its module is ``jit_run``.
"""

MODULE = "jit_run"


def read(ctx):
    if not ctx.trace:
        return None
    mod = ctx.trace["modules"].get(MODULE)
    if not mod or not mod["launches"] or mod["loops"] != mod["launches"]:
        return None
    return 1e6 * mod["seconds"] / mod["steps"]
