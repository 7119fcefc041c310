"""Device milliseconds an engine step spends in the top-1 expert sublayers:
the router network and its carry, the choice, sorting and gathering the routed
rows, the grouped products (``ragged-dot-stationary``), the combine; their
device time inside the traced window over the engine steps that started in it.
``harness/cca.py`` says how the operations are recognised in the trace, and
what is not counted (a prefill piece's gathered and weighted rows, which no
shape tells from the residual stream where an expert is as wide as the
model)."""

from harness import cca


def read(ctx):
    seconds = cca.device_seconds(ctx, "moe")
    steps = cca.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
