"""Median time from a request's submission (its span's ``b``) to its ``admit``
event, over the requests submitted and admitted inside the window: the
engine's tracer (``engine._submit_impl``, ``scheduler.schedule``)."""

from harness import phases


def read(ctx):
    return phases.admit_wait_ms_p50(ctx)
