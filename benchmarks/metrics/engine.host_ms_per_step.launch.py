"""Host time a step of the window in the engine's ``dispatch.launch`` slice: the
call of the jitted decode program alone: ``engine._dispatch_decode``, the
engine's tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("dispatch.launch",))
