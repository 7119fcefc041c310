"""How many programs JAX compiled (or loaded from the persistent cache) before
the window opened: the ``compile`` slices of the stretch. ``harness/setup.py``
says how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "programs_compiled")
