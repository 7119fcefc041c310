"""Device milliseconds an engine step spends in the expert layers'
operations (routing, sorting and gathering the routed pairs, the grouped
products, the combine): their device time inside the traced window over the
engine steps that started in it. ``harness/moe_hybrid.py`` says how the
operations are recognised in the trace, and what is not counted."""

from harness import moe_hybrid


def read(ctx):
    seconds = moe_hybrid.device_seconds(ctx, "moe")
    steps = moe_hybrid.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
