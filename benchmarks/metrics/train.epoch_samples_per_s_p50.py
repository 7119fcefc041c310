"""Median over the window's epochs of global batch x steps / the epoch's
wall time: the steadier twin of ``train_samples_per_s``, which is taken over
all the window's work and time."""

from harness import stats


def read(ctx):
    rates = ctx.get("epoch_rates")
    if not rates:
        return None
    return stats.median(rates)
