"""Host time a step of the window in the engine's ``dispatch.key`` slices, one a
decode row (the row's ``fold_in`` program and its ``np.asarray`` round trip),
summed over the rows: ``engine._dispatch_decode``, the engine's tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("dispatch.key",))
