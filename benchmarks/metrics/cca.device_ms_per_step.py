"""Device milliseconds an engine step spends in the CCA sublayers: the
convolutions over ``[q; k]``, the q-k mean, the L2 norms, the rotation, the
value shift, the slot state's update, the pools' writes, a prefill piece's
walk over its blocks (plain XLA operations, recognised by result shape) plus
the K/V decode kernel's calls (``attention._paged_decode_step``, twenty a
decode program at the cut's depth); their device time inside the traced window
over the engine steps that started in it. ``harness/cca.py`` says how the
operations are recognised in the trace, and what is not counted (``W_o``, the
block's norms and residual merges)."""

from harness import cca


def read(ctx):
    seconds = cca.device_seconds(ctx, "cca", "kernel")
    steps = cca.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
