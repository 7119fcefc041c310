"""Device milliseconds an engine step spends in the window layers' attention:
the windowed K/V decode kernel (``attention._window_paged_decode_step``, six
calls a decode program at the published depth) and the prefill pieces'
windowed reads (the gather of a window layer's pages through its group's
short table, the scores and the softmax over them); their device time inside
the traced window over the engine steps that started in it.
``harness/window.py`` says how the operations are recognised in the trace, and
what is not counted (the projections, the heads' norms, the rotation, the
pools' writes)."""

from harness import window


def read(ctx):
    seconds = window.device_seconds(ctx, "decode", "rest")
    steps = window.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
