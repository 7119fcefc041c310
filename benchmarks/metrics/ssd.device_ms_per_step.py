"""Device milliseconds an engine step spends in the Mamba-2 mixers' conv,
state-update and block-evaluation operations: their device time inside the
traced window over the engine steps that started in it.
``harness/moe_hybrid.py`` says how the operations are recognised in the
trace, and what of the mixers is not counted."""

from harness import moe_hybrid


def read(ctx):
    seconds = moe_hybrid.device_seconds(ctx, "ssd")
    steps = moe_hybrid.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
