"""Seconds of set-up from ``engine.init``'s end to the window's opening, less
the compiles in between: the warm-up requests' own run. ``harness/setup.py``
says how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "warm_run_s")
