"""Seconds of set-up under none of the set-up slices: the interpreter, the
imports, the benchmark's own weights and images, gaps. ``harness/setup.py``
says how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "unattributed_s")
