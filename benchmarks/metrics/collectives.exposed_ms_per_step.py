"""Time per step that a chip spent in collective operations (all-reduce and
its kin, start and done halves included) with nothing else running on it.
On a TPU's op line operations run one after another, so a collective
event's own time is time no compute ran on that chip. Mean over the chips."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("traced_steps") or ctx["chips"] < 2:
        return None
    return 1e3 * trace.collective_s / ctx["traced_steps"]
