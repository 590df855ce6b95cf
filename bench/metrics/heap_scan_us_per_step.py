"""Device time of the heap scan per step, in microseconds: the device
durations of the heap scan's XLA module in the traced window, over the
steps that its launches ran.  A launch's steps are the trip count of the
scan's ``while`` loop, read from the loop's operand shapes in the trace
(the heap scan steps once per padded row and stacks its step input as
``[rows, lanes]``), so padding a launch to more rows shows as more steps.
Profiler trace; nothing when the module, or a launch's loop, is missing
from the trace.

The heap scan is ``jax.jit`` of the function ``run_heap`` that
``repro.core.jaxsim._heap_runner`` returns, so its module is
``jit_run_heap``.
"""

MODULE = "jit_run_heap"


def read(ctx):
    if not ctx.trace:
        return None
    mod = ctx.trace["modules"].get(MODULE)
    if not mod or not mod["launches"] or mod["loops"] != mod["launches"]:
        return None
    return 1e6 * mod["seconds"] / mod["steps"]
