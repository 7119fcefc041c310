"""Host time a step, over the window's epochs, in the Trainer's ``put_batch``
slice: the batch's way to the device, as far as the host waits for it:
``Trainer._run_epoch``, the process's tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("put_batch",))
