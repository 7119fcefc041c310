"""Device milliseconds an engine step spends in the latent attention's
operations: the decode kernel (``attention._latent_decode_step``) and the
prefill-side walk over the rows' pages, the pool's write and page copy; their
device time inside the traced window over the engine steps that started in
it. ``harness/latent.py`` says how the operations are recognised in the
trace, and what is not counted (the projections)."""

from harness import latent


def read(ctx):
    seconds = latent.device_seconds(ctx, "decode", "rest")
    steps = latent.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
