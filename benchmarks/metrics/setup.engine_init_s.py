"""Seconds of set-up in ``engine.init`` (the engine's constructor: the page
pools, the per-slot state) less the compiles inside it. ``harness/setup.py``
says how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "engine_init_s")
