"""Device milliseconds an engine step spends in the Mamba mixers' conv and
scan (state update) operations: their device time inside the traced window
over the engine steps that ran in it. ``harness/hybrid.py`` says how the
operations are recognised in the trace, and what of the mixers is not
counted."""

from harness import hybrid


def read(ctx):
    seconds = hybrid.ssm_device_seconds(ctx)
    steps = hybrid.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
