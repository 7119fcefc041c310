"""Seconds of set-up in the backend part of the ``compile`` slices: XLA
compiling a program, or the persistent cache loading it. ``harness/setup.py``
says how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "backend_compile_s")
