"""Seconds of set-up in ``backend.open`` slices: JAX opening its backends, the
chip among them. ``harness/setup.py`` says how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "backend_open_s")
