"""Host time a step of the window in the engine's ``readback.wait`` slice:
``np.asarray`` of the sampled tokens, the host blocked on the device:
``engine._resolve_rows``, the engine's tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("readback.wait",))
