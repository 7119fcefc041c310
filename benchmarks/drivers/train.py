"""Driver for configurations of kind ``train``: the program's normal
``Trainer`` over a ``ShardedLoader`` and an ``ArrayDataset`` held in host
memory, one epoch at a time.

Set-up makes the images and the initial parameters from ``--seed``, builds ONE
Trainer, and drives it through its first epoch (which compiles, and whose
first three steps are the ones the reference follows). The same Trainer then
runs whole epochs until ``--seconds`` have passed; every epoch ends with the
Trainer fetching its losses, so the window ends synchronised. Loader, host to
device copy and step are all on the clock.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, List

import numpy as np

from harness import stats
from harness.spans import Spans
from harness.trace import Profiler, load_xplane, reduce_trace, whole_steps

SPAN_NAMES = ("loader.next", "train.put_batch", "train.step", "train.epoch")


def make_images(seed: int, n: int, size: int, classes: int):
    """``n`` float32 images uniform in [0, 1) and their labels, by the
    cheapest seeded generator numpy has."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    images = rng.random((n, size, size, 3), dtype=np.float32)
    labels = rng.integers(0, classes, size=n, dtype=np.int32)
    return images, labels


def nest(flat: Dict[str, object]) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def flatten(tree: dict, prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for key, value in tree.items():
        path = f"{prefix}/{key}" if prefix else key
        if isinstance(value, dict):
            out.update(flatten(value, path))
        else:
            out[path] = value
    return out


def build_trainer(cell, dataset, spans: Spans, recorder):
    """The program's Trainer, as ``examples/multichip_profile.py`` builds it,
    with the harness's spans round its loader, its copy and its step."""
    import jax.numpy as jnp
    import optax

    from distributed_pytorch_tpu import ShardedLoader, Trainer, make_mesh
    from distributed_pytorch_tpu.models.resnet import BottleneckBlock, ResNet
    from distributed_pytorch_tpu.training.losses import (
        softmax_cross_entropy_loss,
    )

    cfg, traffic = cell.config, cell.traffic

    class SpanLoader(ShardedLoader):
        def iter_batches(self, start_batch: int = 0):
            batches = super().iter_batches(start_batch)
            while True:
                with spans.span("loader.next"):
                    try:
                        batch = next(batches)
                    except StopIteration:
                        return
                yield batch

    loader = SpanLoader(dataset, traffic["global_batch"], drop_last=True)
    model = ResNet(
        stage_sizes=tuple(cfg["stage_sizes"]), block=BottleneckBlock,
        num_classes=cfg["num_classes"], num_filters=cfg["num_filters"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    opt = cfg["assumed"]["optimizer"]
    mesh = make_mesh(devices=cell.devices) if cell.chips > 1 else None
    trainer = Trainer(
        model, loader, optax.sgd(opt["learning_rate"], momentum=opt["momentum"]),
        save_every=0, mesh=mesh, loss_fn=softmax_cross_entropy_loss,
        metrics=recorder, log_every=0,
    )
    return trainer, mesh


def install_weights(trainer, mesh, flat_params) -> None:
    """Replace the Trainer's own initial parameters with the benchmark's
    (float32, seeded), and start the optimizer from them."""
    import jax

    from distributed_pytorch_tpu.parallel.sharding import replicated_sharding

    params = nest(flat_params)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), trainer.state.params)
    have = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    if want != have:
        raise ValueError("the benchmark's parameters do not fit the program's tree")
    # Copies: the step donates its state, and the reference's arrays stay.
    params = jax.tree_util.tree_map(lambda x: x.copy(), params)
    state = trainer.state.replace(
        params=params, opt_state=trainer.optimizer.init(params))
    if mesh is not None:
        state = jax.device_put(state, replicated_sharding(mesh))
    trainer.state = state


class FirstSteps:
    """Wraps ``trainer.train_step``: a span round every dispatch and, for
    the first ``n`` calls, what the reference is compared with."""

    def __init__(self, trainer, spans: Spans, start_params, n: int = 3):
        import jax
        import jax.numpy as jnp

        self.inner = trainer.train_step
        self.spans, self.n, self.calls = spans, n, 0
        self.start = start_params
        self.losses: List[object] = []
        self.first_grad = None
        self.change = None
        self.batch_rows = None
        self.shapes = None
        norms = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t)
        self._norms = jax.jit(norms)
        self._change = jax.jit(lambda p, p0: norms(
            jax.tree_util.tree_map(lambda a, b: a - b, p, p0)))

    def temporaries_bytes(self) -> int:
        """The compiled step's temporaries on one device, as the compiler
        reports them: the allocator's ``peak_bytes_in_use`` leaves a running
        program's temporaries out (PERF.md, PR 21), and for a training step
        they are most of the memory. A compile-cache hit, after the window."""
        analysis = self.inner.lower(*self.shapes).compile().memory_analysis()
        return int(analysis.temp_size_in_bytes)

    def __call__(self, state, batch):
        if self.shapes is None:
            import jax

            self.shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding), (state, batch))
        with self.spans.span("train.step"):
            new_state, loss = self.inner(state, batch)
        self.calls += 1
        if self.calls <= self.n:
            self.losses.append(loss)
            if self.calls == 1:
                trace = next(s.trace for s in new_state.opt_state
                             if hasattr(s, "trace"))
                self.first_grad = self._norms(trace)
                self.batch_rows = [
                    s.data.shape[0] for s in batch[0].addressable_shards]
            if self.calls == self.n:
                self.change = self._change(new_state.params, self.start)
        return new_state, loss


def run(cell):
    import jax

    from distributed_pytorch_tpu.metrics import MetricLogger
    from distributed_pytorch_tpu.obs.xla import RecompileSentinel

    cfg, traffic = cell.config, cell.traffic
    ref = cell.reference
    spans = Spans()
    batch, n_images = traffic["global_batch"], traffic["images"]
    steps_per_epoch = n_images // batch

    class Recorder(MetricLogger):
        def __init__(self):
            super().__init__()
            self.epoch_losses: List[float] = []

        def log(self, step, **scalars):
            if "epoch_loss" in scalars:
                self.epoch_losses.append(float(scalars["epoch_loss"]))

    from distributed_pytorch_tpu.utils.data import ArrayDataset

    images, labels = make_images(
        cell.seed, n_images, cfg["image_size"], cfg["num_classes"])
    dataset = ArrayDataset(images, labels)
    cell.say(f"dataset of {n_images} images "
             f"({images.nbytes / 2**30:.2f} GiB of host memory) at "
             f"{time.perf_counter() - cell.t_start:.1f}s")
    recorder = Recorder()
    trainer, mesh = build_trainer(cell, dataset, spans, recorder)
    start = ref.make_weights(cfg, cell.seed)
    install_weights(trainer, mesh, start)
    start_tree = nest(start)
    if mesh is not None:
        from distributed_pytorch_tpu.parallel.sharding import (
            replicated_sharding,
        )

        start_tree = jax.device_put(start_tree, replicated_sharding(mesh))
    first = FirstSteps(trainer, spans, start_tree)
    trainer.train_step = cell.hooks.get("wrap_step", lambda f: f)(first)
    put_batch = trainer._put_batch

    def spanned_put(xs, ys):
        with spans.span("train.put_batch"):
            return put_batch(xs, ys)

    trainer._put_batch = spanned_put

    # The first epoch compiles; its first three steps are the reference's.
    trainer.train(1)
    jax.block_until_ready(trainer.state)
    sentinel = RecompileSentinel()
    sentinel.arm()
    t_open = time.perf_counter()
    spans.recording = True
    setup_s = t_open - cell.t_start
    cell.say(f"first epoch done, window opens at {setup_s:.1f}s")

    epoch_s: List[float] = []
    while time.perf_counter() - t_open < cell.seconds:
        t0 = time.perf_counter()
        with spans.span("train.epoch"):
            trainer.train(trainer.epochs_run + 1)
        epoch_s.append(time.perf_counter() - t0)
    jax.block_until_ready(trainer.state)
    window_s = time.perf_counter() - t_open
    window_rows = list(spans.rows)  # the host spans the metrics read

    # A traced run then traces one more epoch, after the window. The host
    # tracer stays off: it writes an event for every block the host transposes
    # on the way to the chip, 690,000 a step of 256 images, which triples a
    # step's host time and makes the trace cost a minute to close and read
    # (my chip runs, PR 23). So the trace holds the device alone, and the
    # traced window is cut from it afterwards, at the starts of the step's
    # program (whole_steps). The CPU rehearsal has no device plane and reads
    # XLA's host threads, so there the host tracer is on, and the annotation
    # round the epoch is the window.
    if cell.trace:
        profiler = Profiler(cell.scratch("trace"),
                            host_tracer_level=1 if cell.allow_cpu else 0)
        t_profiler = time.perf_counter()
        profiler.start()
        profiler.open_window()
        spans.annotate = True
        trainer.train(trainer.epochs_run + 1)
        jax.block_until_ready(trainer.state)
        t_traced = time.perf_counter()
        xplane_path = profiler.stop()
        spans.annotate = False
        spans.rows = window_rows
        cell.say(
            f"after the window: one epoch under the profiler took "
            f"{t_traced - t_profiler:.3f}s (an epoch of the window took "
            f"{stats.median(epoch_s):.3f}s), and the profiler "
            f"{time.perf_counter() - t_traced:.1f}s to close")
    spans.recording = False
    sentinel.disarm()
    # Derived, and labelled so: the allocator's peak leaves a running
    # program's temporaries out, and they are most of a training step's memory.
    device = cell.stamp()
    device["memory_allocator_peak_bytes"] = device["memory_peak_bytes"]
    t_analysis = time.perf_counter()
    device["memory_step_temporaries_bytes"] = first.temporaries_bytes()
    device["memory_peak_bytes"] += device["memory_step_temporaries_bytes"]
    device["memory_peak_source"] = (
        "derived: allocator peak_bytes_in_use + the compiled step's "
        "temp_size_in_bytes")
    cell.say(f"memory (derived): allocator peak "
             f"{device['memory_allocator_peak_bytes']} B + the step's "
             f"temporaries {device['memory_step_temporaries_bytes']} B = "
             f"{device['memory_peak_bytes']} B; the analysis took "
             f"{time.perf_counter() - t_analysis:.1f}s")

    epochs = len(epoch_s)
    samples_per_s = epochs * steps_per_epoch * batch / window_s
    bad = [l for l in recorder.epoch_losses if not math.isfinite(l)]
    cell.say(
        f"window {window_s:.2f}s: {epochs} epochs of {steps_per_epoch} steps "
        f"of {batch}; epoch time p50 {stats.median(epoch_s):.4f}s; "
        f"{len(bad)} non-finite epoch losses; compiles in window "
        f"{sentinel.count}")

    # What the program did in its first steps, read back before its state
    # goes; then the state goes, so the reference fits and the peak above
    # stays the program's.
    program = {
        "losses": [float(l) for l in first.losses],
        "first_grad_norms": {
            k: float(v) for k, v in flatten(first.first_grad).items()},
        "param_change_norms": {
            k: float(v) for k, v in flatten(first.change).items()},
    }
    replicated = True
    if mesh is not None:
        n = len(cell.devices)
        replicated = all(
            len({s.device for s in leaf.addressable_shards}) == n
            and all(s.data.shape == leaf.shape for s in leaf.addressable_shards)
            for leaf in jax.tree_util.tree_leaves(trainer.state.params))
        for leaf in jax.tree_util.tree_leaves(trainer.state.params):
            rows = [np.asarray(s.data) for s in leaf.addressable_shards]
            replicated &= all((r == rows[0]).all() for r in rows[1:])
    split_ok = first.batch_rows == [batch // cell.chips] * cell.chips
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()

    t_ref = time.perf_counter()
    opt = cfg["assumed"]["optimizer"]
    batches = [
        (images[i * batch:(i + 1) * batch], labels[i * batch:(i + 1) * batch])
        for i in range(len(program["losses"]))
    ]
    want = ref.train_steps(
        cfg, start, batches, learning_rate=opt["learning_rate"],
        momentum=opt["momentum"], devices=cell.devices)
    limits = traffic["check"]
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(program["losses"], want["losses"]))
    grad_gap, grad_leaf = stats.worst_leaf_gap(
        program["first_grad_norms"], want["first_grad_norms"])
    change_gap, change_leaf = stats.worst_leaf_gap(
        program["param_change_norms"], want["param_change_norms"])
    checks = [
        ("loss of steps 1-3, widest relative gap", loss_gap, limits["loss_gap_limit"]),
        (f"first gradient, worst leaf ({grad_leaf})", grad_gap, limits["grad_gap_limit"]),
        (f"parameter change after 3 steps, worst leaf ({change_leaf})",
         change_gap, limits["change_gap_limit"]),
    ]
    correct = not bad and replicated and split_ok
    for what, value, limit in checks:
        cell.say(f"correct: {what}: {value:.6f} (limit {limit})")
        correct &= value <= limit
    cell.say(
        f"correct: losses program {program['losses']} reference "
        f"{want['losses']}; state identical on every device: {replicated}; "
        f"batch rows a device {first.batch_rows}; reference took "
        f"{time.perf_counter() - t_ref:.1f}s")

    os.makedirs(cell.scratch(""), exist_ok=True)
    with open(os.path.join(cell.scratch(""), "correct_readings.json"), "w") as f:
        json.dump({"seed": cell.seed, "program": program, "reference": want}, f)
    if "after_check" in cell.hooks:  # control.py and the tests
        cell.hooks["after_check"](cell, start, batches, want)

    values = {"train_samples_per_s": samples_per_s, "setup_s": setup_s}
    context = None
    if cell.trace:
        t_reduce = time.perf_counter()
        xplane = load_xplane(xplane_path)
        window, traced_steps = whole_steps(xplane)
        summary = reduce_trace(
            xplane, SPAN_NAMES, cell.allow_cpu, window=window)
        cell.say(
            f"trace of {os.path.getsize(xplane_path)} B read and reduced in "
            f"{time.perf_counter() - t_reduce:.1f}s: "
            f"{traced_steps or steps_per_epoch} steps in a window of "
            f"{summary.window_s:.3f}s, the device busy {summary.busy_s:.3f}s")
        context = dict(
            trace=summary, spans=spans, cfg=cfg, traffic=traffic,
            window_s=window_s, chips=cell.chips, device_kind=device["kind"],
            compiles=sentinel.count, samples_per_s=samples_per_s,
            epoch_rates=[steps_per_epoch * batch / s for s in epoch_s],
            step_s_p50=stats.median(epoch_s) / steps_per_epoch,
            traced_steps=traced_steps or steps_per_epoch, global_batch=batch,
            train_flops_per_sample=ref.train_flops_per_sample(cfg),
            train_min_bytes_per_step=ref.train_min_bytes_per_step(
                cfg, batch // cell.chips),
        )
    return dict(
        correct=bool(correct), attempted=epochs, failed=len(bad),
        values=values, device=device, context=context,
    )
