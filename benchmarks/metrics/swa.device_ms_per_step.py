"""Device milliseconds an engine step spends in the sliding layers' latent
attention: the windowed decode kernel
(``attention._window_latent_decode_step``), the prefill pieces' walk over
their windows' blocks and the pools' writes; their device time inside the
traced window over the engine steps that started in it (``harness/dsa.py``)."""

from harness import dsa


def read(ctx):
    seconds = dsa.device_seconds(ctx, "window", "window_rest")
    steps = dsa.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
