"""Device-idle time a traced step during which the host was inside
``loader.index`` or ``loader.stack``: the device trace's gaps split over the
process tracer's slices (``harness/phases.py``)."""

from harness import phases


def read(ctx):
    return phases.idle_ms_per_step(ctx, ("loader.index", "loader.stack"))
