"""Device-idle time a traced step during which the host's innermost slice was a
``dispatch.key``: the device trace's gaps split over the engine tracer's
slices (``harness/phases.py``)."""

from harness import phases


def read(ctx):
    return phases.idle_ms_per_step(ctx, ("dispatch.key",))
