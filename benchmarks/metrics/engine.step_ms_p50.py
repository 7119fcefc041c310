"""Median wall time of one ``engine.step()`` call in the window, from the
harness's own span round it."""

from harness import stats


def read(ctx):
    steps = ctx["spans"].durations("engine.step")
    if not steps:
        return None
    return stats.median(steps) * 1e3
