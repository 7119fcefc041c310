"""Device milliseconds an engine step spends in the learned sparse
attention's operations: the index-score kernel
(``attention._index_scores``), the exact top-k and the gather of the selected
latents (XLA's), the sparse decode kernel
(``attention._sparse_latent_decode_step``), and on the prefill side the
pieces' index scores, selection and masked walk; their device time inside the
traced window over the engine steps that started in it. ``harness/dsa.py``
says how the operations are recognised in the trace, and what is not counted
(the projections)."""

from harness import dsa


def read(ctx):
    seconds = dsa.device_seconds(
        ctx, "index", "select", "gather", "sparse", "full_rest")
    steps = dsa.traced_steps(ctx)
    if seconds is None or steps is None:
        return None
    return 1e3 * seconds / len(steps)
