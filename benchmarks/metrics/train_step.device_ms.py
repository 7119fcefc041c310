"""Device-busy time per training step in the traced window: the union of the
device's op intervals over the steps traced, mean over the chips."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("traced_steps"):
        return None
    return 1e3 * trace.busy_s / ctx["traced_steps"]
