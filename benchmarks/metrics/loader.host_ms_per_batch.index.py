"""Host time a batch, over the window's epochs, in the loader's ``loader.index``
slice: the ``dataset[i]`` calls: ``ShardedLoader.iter_batches``, the process's
tracer."""

from harness import phases


def read(ctx):
    return phases.host_ms_per_step(ctx, ("loader.index",))
