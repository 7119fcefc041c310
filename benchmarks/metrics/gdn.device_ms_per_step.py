"""Device milliseconds an engine step spends in the gated-delta mixers'
operations, decode and prefill: the decode kernel's calls, the blocked
prefill's operations and the conv, norms and layout operations round them,
their device time inside the traced window over the engine steps that started
in it. ``harness/linear.py`` says how the operations are recognised in the
trace, and what of the mixers is not counted (the projections)."""

from harness import linear


def read(ctx):
    seconds = linear.device_seconds(ctx)
    steps = linear.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
