"""How many of set-up's ``compile`` slices asked the persistent cache and were
not served. Warm, those are the programs under the cache's threshold of 0.5 s,
which are never written: the same count in every run. ``harness/setup.py`` says
how the stretch is split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "cache_misses")
