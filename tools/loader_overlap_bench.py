"""Loader-on-the-clock benchmark: the C++ prefetch pool's overlap win.

The pool (``native/prefetch.cpp`` + :class:`NativeShardedLoader`) replaces
``DataLoader(num_workers=..., pin_memory=True)`` (reference
``multigpu.py:72-79``): GIL-free worker threads gather batches into a bounded
ring while the training loop consumes. ``bench.py`` deliberately pre-stages
batches off the clock, so THIS bench supplies the pool's missing number: a CPU-backend
train loop with batch assembly ON the clock, identical batches either way.

    JAX_PLATFORMS=cpu python tools/loader_overlap_bench.py

Prints steps/s for the Python loader vs the native pool, plus the decomposed
assembly-only and compute-only rates so the overlap arithmetic is visible:
python ~ 1/(assembly + compute), native ~ 1/max(assembly', compute) with the
gather itself also moving to C++ memcpy.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(n_steps: int = 100, batch: int = 256, features: int = 8192, hidden: int = 16):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import flax.linen as nn

    jax.config.update("jax_platforms", "cpu")

    from distributed_pytorch_tpu.training.losses import mse_loss
    from distributed_pytorch_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )
    from distributed_pytorch_tpu.utils.data import (
        ArrayDataset,
        NativeShardedLoader,
        ShardedLoader,
    )

    class WideMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(hidden)(x)
            x = nn.relu(x)
            x = nn.Dense(hidden)(x)
            x = nn.relu(x)
            return nn.Dense(1)(x)

    rng = np.random.default_rng(0)
    n_samples = n_steps * batch  # one full epoch, no repeats
    data = ArrayDataset(
        rng.standard_normal((n_samples, features)).astype(np.float32),
        rng.standard_normal((n_samples, 1)).astype(np.float32),
    )

    optimizer = optax.sgd(1e-3)
    model = WideMLP()
    step = make_train_step(model.apply, optimizer, mse_loss)

    def loaders():
        return {
            "python_loader": ShardedLoader(data, batch, shuffle=True),
            "native_pool": NativeShardedLoader(
                data, batch, shuffle=True, num_workers=4, prefetch_depth=4
            ),
        }

    # The train step donates its state buffer; every run needs a fresh one.
    fresh = lambda: create_train_state(model, optimizer, data.inputs[:1])  # noqa: E731

    # Warm the jit cache once.
    xs, ys = next(iter(loaders()["python_loader"]))
    state, loss = step(fresh(), jax.device_put((xs, ys)))
    float(loss)

    results = {}
    for name, loader in loaders().items():
        state = fresh()
        t0 = time.perf_counter()
        for xs, ys in loader:
            state, loss = step(state, jax.device_put((xs, ys)))
        float(loss)
        elapsed = time.perf_counter() - t0
        results[name] = n_steps / elapsed

    # Decomposition: assembly-only (drain each loader, no compute) and
    # compute-only (one resident batch re-fed).
    for name, loader in loaders().items():
        t0 = time.perf_counter()
        for _ in loader:
            pass
        results[f"{name}_assembly_only"] = n_steps / (time.perf_counter() - t0)
    resident = jax.device_put((xs, ys))
    state = fresh()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, loss = step(state, resident)
    float(loss)
    results["compute_only"] = n_steps / (time.perf_counter() - t0)

    results = {k: round(v, 2) for k, v in results.items()}
    results["overlap_speedup"] = round(
        results["native_pool"] / results["python_loader"], 3
    )
    print(json.dumps(results, indent=1))
    return results


if __name__ == "__main__":
    main()
