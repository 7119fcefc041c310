"""Host time a step of the window in the engine's ``dispatch.stage`` slice: the
``jnp.array`` copies of the staged rows, from the end of the slot loop to the
launch: ``engine._dispatch_decode``, the engine's tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("dispatch.stage",))
