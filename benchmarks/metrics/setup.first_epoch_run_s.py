"""Seconds of set-up in ``trainer.init`` and the Trainer's first ``epoch``,
less the compiles inside them. ``harness/setup.py`` says how the stretch is
split."""

from harness import setup


def read(ctx):
    return setup.read(ctx, "first_epoch_run_s")
