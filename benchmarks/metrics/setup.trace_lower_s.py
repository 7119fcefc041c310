"""Seconds of set-up inside ``compile`` slices but outside their backend part:
JAX tracing and lowering the programs, and its own Python between the parts.
Host work that the persistent cache does not save. ``harness/setup.py`` says
how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "trace_lower_s")
