"""Host time a step, over the window's epochs, in the Trainer's
``step.dispatch`` slice: ``_run_batch``, the call of the jitted step:
``Trainer._run_epoch``, the process's tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("step.dispatch",))
