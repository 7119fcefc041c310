"""The Trainer: epoch loop -> batch loop -> jitted train step, plus
checkpoint/snapshot I/O.

Capability twin of the reference's ``Trainer`` in all five ladder rungs
(serial ``single_gpu.py:6-45``; DDP ``multigpu.py:22-62``; elastic
``multigpu_torchrun.py:15-68``; multinode ``multinode_torchrun.py:15-69``;
profiled ``multigpu_profile.py:30-91``) — one class covers all rungs because
the SPMD design makes "how many chips / hosts" a property of the mesh, not of
the training code:

* no mesh           -> serial rung (1 chip);
* mesh, 1 process   -> single-host data parallel (DDP twin);
* mesh, N processes -> multi-host pod (multinode twin) — each host feeds its
  local loader shard into a globally sharded batch.

Elasticity contract (identical to ``multigpu_torchrun.py:30-40,57-65``): if a
snapshot exists at construction it is loaded and ``train()`` resumes from
``epochs_run``; snapshots are written every ``save_every`` epochs by process 0
only, with a cross-host barrier after the write.
"""

from __future__ import annotations

import collections
import os
import signal
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from distributed_pytorch_tpu.chaos import on_step as _chaos_on_step
from distributed_pytorch_tpu.checkpoint import (
    load_snapshot_with_fallback,
    save_checkpoint,
    save_snapshot,
)
from distributed_pytorch_tpu.metrics import MetricLogger, ReservoirHistogram
from distributed_pytorch_tpu.obs.tracer import process_tracer
from distributed_pytorch_tpu.parallel.bootstrap import is_main_process
from distributed_pytorch_tpu.parallel.sharding import (
    put_global_batch,
    replicated_sharding,
)
from distributed_pytorch_tpu.training.losses import mse_loss
from distributed_pytorch_tpu.training.train_step import (
    TrainState,
    create_train_state,
    make_eval_step,
    make_train_step,
)
from distributed_pytorch_tpu.utils.data import ShardedLoader


def _put_host_state(state, sharding):
    """Place a host-loaded (numpy) state tree onto a possibly multi-process
    sharding.

    ``jax.device_put(host_array, multi_process_sharding)`` runs a
    cross-process value-equality collective, which the CPU backend does not
    implement (and which is redundant here: every process read the same
    snapshot file). ``make_array_from_callback`` assembles the global array
    from locally-computed shards with no collective, so snapshot resume works
    on any backend. ``sharding`` may be a single Sharding or a state-shaped
    tree of them.
    """
    if jax.process_count() == 1:
        return jax.device_put(state, sharding)

    def put(x, s):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, s, lambda idx: x[idx])

    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(lambda x: put(x, sharding), state)
    return jax.tree_util.tree_map(put, state, sharding)


# Batches whose host arrays a Trainer holds at once: the one the loader is
# stacking, and two lent to steps not known to be done (one being computed on,
# one on its way to the device: that copy goes on after ``device_put`` has
# returned, ~45 ms for 154 MB on a v5e's host, PERF.md §5).
HOST_BATCHES = 3


class Trainer:
    """Drives training of a flax model over a ShardedLoader.

    Parameters mirror the reference ctor
    (``model, train_data, optimizer, save_every[, snapshot_path]``,
    e.g. ``multigpu_torchrun.py:16-23``) with TPU-native additions:
    ``mesh`` (in place of gpu_id / process-group), ``loss_fn``, and
    ``checkpoint_path``.
    """

    def __init__(
        self,
        model,
        train_data: ShardedLoader,
        optimizer: optax.GradientTransformation,
        save_every: int,
        *,
        snapshot_path: Optional[str] = None,
        checkpoint_path: str = "checkpoint.npz",
        mesh: Optional[Mesh] = None,
        loss_fn: Callable = mse_loss,
        rng_seed: int = 0,
        profiler=None,
        metrics: Optional[MetricLogger] = None,
        log_every: int = 0,
        grad_accum: int = 1,
        async_save: bool = False,
        paranoid: bool = False,
        loss_scale=None,
        partition_specs=None,
        keep_checkpoints: int = 0,
        dropout_seed: Optional[int] = None,
        registry=None,
        tracer=None,
    ):
        # ``trainer.init``, a set-up slice, is written at this constructor's
        # end, to the process's tracer whatever ``tracer`` is (made first, so
        # that its ``process.start`` ends before the slice begins).
        setup = process_tracer()
        t_init = time.perf_counter()
        self.model = model
        self.train_data = train_data
        # (inputs, targets, loss) of the steps last dispatched: _hand_back
        self._lent: collections.deque = collections.deque()
        self.optimizer = optimizer
        self.save_every = save_every
        self.snapshot_path = snapshot_path
        self.checkpoint_path = checkpoint_path
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.profiler = profiler
        self.metrics = metrics or MetricLogger()
        # Where the step loop writes its host phases (epoch > step >
        # put_batch, step.dispatch; epoch.loss_fetch): the process's bounded
        # tracer unless the caller hands over a Tracer of its own.
        self.tracer = tracer if tracer is not None else process_tracer()
        # Per-batch wall time (dispatch + any sync the loop already does) in
        # a bounded reservoir, fed from the ``step`` slice's own two clock
        # reads; p50/p95 logged at every epoch boundary. Tail percentiles
        # are where stragglers, recompiles, and host stalls show up — the
        # mean hides them.
        self.step_times = ReservoirHistogram(1024)
        # Optional unified-observability hookup: expose the step-time
        # reservoir and global step through a shared MetricsRegistry
        # alongside the serving/elastic metrics. Pull-based — the registry
        # reads these attributes at snapshot time, nothing is double-booked.
        if registry is not None:
            registry.reservoir(
                "trainer_step_time_seconds", lambda: self.step_times
            )
            registry.counter_fn(
                "trainer_steps_total", lambda: int(self.state.step)
            )
        self.log_every = log_every
        self.grad_accum = grad_accum
        # async_save: overlap snapshot disk writes with the next epoch's
        # compute; paranoid: replica-consistency check before every snapshot
        # (the race detector, SURVEY.md §5).
        self.checkpointer = None
        if async_save:
            from distributed_pytorch_tpu.checkpoint import AsyncCheckpointer

            self.checkpointer = AsyncCheckpointer()
        self.paranoid = paranoid
        # keep_checkpoints > 0: rotate instead of overwriting —
        # checkpoint_path becomes a DIRECTORY managed by CheckpointManager
        # (newest K by write time + the best-by-epoch-loss protected);
        # 0 keeps the reference's single-file overwrite semantics.
        self.manager = None
        if keep_checkpoints > 0:
            if snapshot_path is not None:
                # train() routes periodic saves to the snapshot when
                # snapshot_path is set; a manager built here would silently
                # never run — refuse the combination instead.
                raise ValueError(
                    "keep_checkpoints rotates checkpoint_path saves, but "
                    "snapshot_path is also set (snapshots are single-file "
                    "by design — the elastic resume contract); use one or "
                    "the other"
                )
            from distributed_pytorch_tpu.checkpoint import CheckpointManager

            self.manager = CheckpointManager(
                checkpoint_path, keep=keep_checkpoints, mode="min"
            )
        self.epochs_run = 0
        # Mid-epoch (drain snapshot) resume point, consumed by the first
        # _run_epoch after a resume: skip the first _resume_step batches of
        # epoch _resume_epoch and seed the epoch-loss mean with the partial
        # sums accumulated before the drain (so the logged epoch_loss of a
        # preempted-and-resumed epoch equals the un-preempted one).
        self._resume_epoch = 0
        self._resume_step = 0
        self._resume_loss_sum = 0.0
        self._resume_loss_count = 0

        if mesh is not None:
            data_size = mesh.shape.get("data", 1)
            if train_data.batch_size % data_size != 0:
                raise ValueError(
                    f"batch_size {train_data.batch_size} is not divisible by the "
                    f"mesh's data axis ({data_size}); P('data') cannot place it"
                )
            if not train_data.drop_last and not train_data.pad_final_batch:
                # Static shapes under jit: wrap-pad any ragged final batch
                # (DistributedSampler's pad-by-repeat semantic).
                train_data.pad_final_batch = True
        if grad_accum > 1:
            if train_data.batch_size % grad_accum != 0:
                raise ValueError(
                    f"batch_size {train_data.batch_size} is not divisible by "
                    f"grad_accum {grad_accum}"
                )
            if not train_data.drop_last and not train_data.pad_final_batch:
                # A ragged final batch would break the microbatch split even
                # in the serial (mesh-free) case.
                train_data.pad_final_batch = True

        sample_x, _ = next(iter(train_data))
        # loss_scale: a mixed_precision.{Static,Dynamic}LossScale for fp16
        # compute policies; rides in TrainState (see train_step.TrainState).
        # dropout_seed arms the step's stochastic path (the model must set
        # dropout_rate > 0 for it to have any effect; eval/decode stay
        # deterministic either way — see train_step.TrainState.rng).
        self.state: TrainState = create_train_state(
            model, optimizer, sample_x, rng_seed=rng_seed,
            loss_scale=loss_scale, dropout_rng=dropout_seed,
        )
        # partition_specs opens the sharding zoo through the flagship API:
        # either a params-shaped PartitionSpec tree (TP/FSDP rule output —
        # lifted onto the whole TrainState, Adam moments following their
        # params) or a full TrainState-shaped spec tree (e.g.
        # make_zero1_state_specs). None = plain replicated DP.
        self.state_sharding = None
        if partition_specs is not None:
            if mesh is None:
                raise ValueError("partition_specs requires mesh=")
            from distributed_pytorch_tpu.parallel.partitioning import (
                make_state_specs,
                specs_to_shardings,
            )

            specs = (
                partition_specs
                if isinstance(partition_specs, TrainState)
                else make_state_specs(self.state, partition_specs)
            )
            self.state_sharding = specs_to_shardings(mesh, specs)
            self.state = jax.device_put(self.state, self.state_sharding)
        elif mesh is not None:
            # Replicate state across the mesh (the DDP-construction broadcast,
            # reference multigpu.py:36, minus the network traffic: every
            # process computes identical init from the same seed).
            self.state = jax.device_put(self.state, replicated_sharding(mesh))

        # Snapshot probe-on-init: the elasticity contract
        # (reference multigpu_torchrun.py:30-32). _load_snapshot handles the
        # whole fallback chain, including "only <path>.prev exists" (a crash
        # between rotation and write) and "latest is corrupt".
        if snapshot_path is not None:
            self._load_snapshot(snapshot_path)

        self.train_step = make_train_step(
            model.apply, optimizer, loss_fn, mesh=mesh, grad_accum=grad_accum,
            state_sharding=self.state_sharding,
        )
        self._eval_step = None  # built lazily on first evaluate()
        self._eval_step_fns = None  # metric-fn set the cached step was built for
        # tpurun's hung-worker detector (--worker-heartbeat-timeout): when the
        # agent sets this env var, touch the file every batch so a wedged
        # worker (stuck in a collective whose peer died) is distinguishable
        # from a slow one. None outside tpurun — zero overhead.
        self._heartbeat_file = os.environ.get("TPURUN_HEARTBEAT_FILE")
        # Preemption drain (tpurun's SIGTERM grace path): the agent touches
        # TPURUN_DRAIN_FILE and soft-signals SIGTERM when the node is being
        # reclaimed; either signal sets _drain_flag, and the batch loop then
        # finishes the in-flight step, takes a just-in-time step-granular
        # snapshot, and exits with the distinguished drain exit code so the
        # agent classifies the death as a preemption (restart budget intact).
        # Armed only when there is a snapshot to drain into.
        self._drain_file = os.environ.get("TPURUN_DRAIN_FILE")
        self._drain_exit_code = int(
            os.environ.get("TPURUN_DRAIN_EXIT_CODE", "121")
        )
        self._drain_flag = False
        self._drain_armed = snapshot_path is not None
        if (
            self._drain_armed
            and threading.current_thread() is threading.main_thread()
        ):
            try:
                signal.signal(signal.SIGTERM, self._on_sigterm)
            except (ValueError, OSError):
                pass  # embedded in a host that owns signals: file-poll only
        setup.setup_slice(
            "trainer.init", t_init, time.perf_counter() - t_init,
            resumed_at_epoch=self.epochs_run,
        )

    # ---------------------------------------------------------------- persistence

    def _load_snapshot(self, path: str) -> None:
        loaded = load_snapshot_with_fallback(path, self.state)
        if loaded is None:
            # Nothing loadable: either a first run (silent) or every
            # candidate was corrupt (load_snapshot_with_fallback already
            # warned loudly and quarantined) — train from scratch.
            return
        state, meta, used = loaded
        self.epochs_run = int(meta.get("epochs_run", 0))
        if self.state_sharding is not None:
            state = _put_host_state(state, self.state_sharding)
        elif self.mesh is not None:
            state = _put_host_state(state, replicated_sharding(self.mesh))
        else:
            state = jax.device_put(state)
        self.state = state
        # Mid-epoch (drain) snapshot: step_in_epoch batches of epoch
        # epochs_run are already in the restored state. Resuming at the exact
        # batch is only sound if the loader reproduces the drained run's
        # batch order — otherwise (e.g. num_shards changed after a
        # scale-down) replay the epoch from batch 0: re-applying a batch is
        # safe for coverage, skipping one is not.
        step = int(meta.get("step_in_epoch", 0))
        step_note = ""
        if step > 0:
            if self.train_data.matches_order_state(meta.get("order")):
                self._resume_epoch = self.epochs_run
                self._resume_step = step
                self._resume_loss_sum = float(meta.get("loss_sum", 0.0))
                self._resume_loss_count = int(meta.get("loss_count", 0))
                step_note = f", step {step}"
            elif is_main_process():
                print(
                    f"[drain] snapshot was taken at step {step} of epoch "
                    f"{self.epochs_run} under a different loader geometry; "
                    f"replaying the epoch from step 0",
                    flush=True,
                )
        if is_main_process():
            note = "" if used == path else f" (fell back to {used})"
            print(
                f"Resuming training from snapshot at Epoch {self.epochs_run}"
                f"{step_note}{note}",
                flush=True,
            )

    def _save_snapshot(self, epoch: int) -> None:
        # Long synchronous saves (and the paranoid replica check) run no
        # batches; beat before and after so the hung-worker detector doesn't
        # mistake a big checkpoint for a wedge.
        self._touch_heartbeat()
        if self.paranoid:
            from distributed_pytorch_tpu.parallel.consistency import (
                assert_replicas_consistent,
            )

            assert_replicas_consistent(self.state, name="TrainState")
        if self.checkpointer is not None:
            self.checkpointer.save_snapshot(
                self.snapshot_path, self.state, epochs_run=epoch + 1
            )
            note = "snapshot write started (async)"
        else:
            save_snapshot(self.snapshot_path, self.state, epochs_run=epoch + 1)
            note = "Training snapshot saved"
        if is_main_process():
            print(
                f"Epoch {epoch} | {note} at {self.snapshot_path}",
                flush=True,
            )
        self._touch_heartbeat()

    def _save_checkpoint(self, epoch: int, metric=None) -> None:
        # Params AND non-trainable model state (BatchNorm running stats):
        # the reference's state_dict includes both (multigpu.py:54). Beat
        # around the synchronous save, same as _save_snapshot.
        self._touch_heartbeat()
        tree = {
            "params": self.state.params,
            "model_state": self.state.model_state,
        }
        # ONE metadata schema for both modes: {"epoch": N (0-based, the
        # reference's convention), "epochs_run": N+1}; rotated files add
        # "metric".
        if self.manager is not None:
            where = self.manager.save(
                tree, step=epoch + 1, metric=metric, epochs_run=epoch + 1,
                extra_metadata={"epoch": epoch},
            )
        else:
            where = self.checkpoint_path
            save_checkpoint(
                where, tree,
                metadata={"epoch": epoch, "epochs_run": epoch + 1},
            )
        if is_main_process():
            print(
                f"Epoch {epoch} | Training checkpoint saved at {where}",
                flush=True,
            )
        self._touch_heartbeat()

    # ---------------------------------------------------------------- training

    def _put_batch(self, xs: np.ndarray, ys: np.ndarray):
        """Host numpy -> device, globally sharded along the data axis."""
        if self.mesh is None:
            return jax.device_put((xs, ys))
        return put_global_batch(self.mesh, (xs, ys))

    def _hand_back(self, xs, ys, loss, where: dict) -> None:
        """Lend the step just dispatched its batch's host arrays, and give
        the loader back those of the step ``HOST_BATCHES - 1`` before it,
        once that step is done: the device copy may alias them (CPU
        backend), so the step's output is the fence, not the copy's. The
        wait is also what keeps the host from running further ahead of the
        device than that."""
        self._lent.append((xs, ys, loss))
        if len(self._lent) < HOST_BATCHES:
            return
        xs, ys, loss = self._lent.popleft()
        with self.tracer.phase("recycle.fence", **where):
            jax.block_until_ready(loss)
        self.train_data.recycle(xs, ys)

    def _run_batch(self, batch) -> float:
        """One optimizer step (twin of ``_run_batch``, ``single_gpu.py:21-26``)."""
        # Chaos hook: deterministic "kill/hang worker N at step S" fires here
        # (exact no-op unless TPURUN_FAULT_PLAN is armed).
        _chaos_on_step()
        self.state, loss = self.train_step(self.state, batch)
        self._touch_heartbeat()
        return loss

    def _touch_heartbeat(self) -> None:
        """Liveness beat for tpurun's hung-worker detector. Called at every
        point of progress (each train/eval batch, around snapshot writes) —
        no-op outside tpurun, and never allowed to kill training."""
        if self._heartbeat_file is None:
            return
        try:
            os.close(os.open(self._heartbeat_file, os.O_CREAT | os.O_WRONLY))
            os.utime(self._heartbeat_file)
        except OSError:
            pass

    # ------------------------------------------------------------------ drain

    def _on_sigterm(self, signum, frame) -> None:
        """Preemption notice via direct signal delivery. Under tpurun a BARE
        SIGTERM (drain file absent) is the agent tearing the group down for a
        failure-restart — die immediately, as before this handler existed,
        so restarts stay fast; only a SIGTERM accompanying a touched drain
        file (or any SIGTERM outside tpurun) means "snapshot and go"."""
        if self._drain_file is not None and not os.path.exists(self._drain_file):
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)
            return
        self._drain_flag = True

    def _drain_due(self) -> bool:
        """Poll the drain flag at a step boundary. Multi-process runs under
        the agent agree on the flag COLLECTIVELY every batch: the notice can
        reach ranks at different wall times, and a rank stopping to snapshot
        (a barrier) while another still runs the train step (a collective)
        would deadlock the world — the allgather is the in-band drain
        barrier that makes every rank stop at the identical step."""
        local = self._drain_flag or (
            self._drain_file is not None and os.path.exists(self._drain_file)
        )
        if (
            self._drain_armed
            and self._drain_file is not None
            and jax.process_count() > 1
        ):
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(
                np.asarray(local, dtype=np.int32)
            )
            return bool(np.max(flags))
        return local

    def _drain_exit(self, epoch: int, steps_done: int, loss_sum: float,
                    loss_count: int) -> None:
        """Just-in-time snapshot at the current step, then exit with the
        drain code (the agent classifies it as a preemption, not a failure).
        Always a SYNCHRONOUS save — the process is about to die, so the
        write must be durable before the grace window closes."""
        self._touch_heartbeat()
        if self.checkpointer is not None:
            self.checkpointer.wait()  # order behind any in-flight async write
        extra = {
            "order": self.train_data.order_state(),
            "loss_sum": float(loss_sum),
            "loss_count": int(loss_count),
        }
        save_snapshot(
            self.snapshot_path, self.state, epochs_run=epoch,
            step_in_epoch=steps_done, extra_meta=extra,
        )
        self._touch_heartbeat()
        if is_main_process():
            print(
                f"[drain] just-in-time snapshot at epoch {epoch}, step "
                f"{steps_done} -> {self.snapshot_path}; exiting with code "
                f"{self._drain_exit_code}",
                flush=True,
            )
        # SystemExit (not os._exit) so train()'s finally still stops the
        # profiler and closes metrics before the interpreter exits.
        raise SystemExit(self._drain_exit_code)

    # ---------------------------------------------------------------- epochs

    def _run_epoch(self, epoch: int) -> float:
        """One pass over this process's shard (twin of ``_run_epoch``,
        ``single_gpu.py:28-34``). Returns the mean loss over the epoch."""
        with self.tracer.phase("epoch", epoch=epoch):
            return self._run_epoch_steps(epoch)

    def _run_epoch_steps(self, epoch: int) -> float:
        """The body of :meth:`_run_epoch`, inside its ``epoch`` slice."""
        tr = self.tracer
        self.train_data.set_epoch(epoch)
        n_batches = len(self.train_data)
        start = 0
        carry_sum, carry_count = 0.0, 0
        if self._resume_step and epoch == self._resume_epoch:
            # Resuming from a mid-epoch drain snapshot: the first
            # _resume_step batches are already in the state; their losses are
            # carried so this epoch's logged mean spans the whole epoch.
            start = self._resume_step
            carry_sum = self._resume_loss_sum
            carry_count = self._resume_loss_count
        self._resume_step = 0  # one-shot: later epochs start at batch 0
        if is_main_process():
            resume_note = f" (resuming at step {start})" if start else ""
            print(
                f"[proc{jax.process_index()}] Epoch {epoch} | "
                f"Batchsize: {self.train_data.batch_size} | Steps: {n_batches}"
                f"{resume_note}",
                flush=True,
            )
        losses = []
        last_loss = None
        for i, (xs, ys) in enumerate(
            self.train_data.iter_batches(start), start=start
        ):
            where = dict(step=i, epoch=epoch)
            with tr.phase("step", **where) as step_span:
                with tr.phase(
                    "put_batch", bytes=xs.nbytes + ys.nbytes, **where
                ):
                    batch = self._put_batch(xs, ys)
                with tr.phase("step.dispatch", **where):
                    loss = self._run_batch(batch)
                losses.append(loss)
            self._hand_back(xs, ys, loss, where)
            if tr.enabled:
                self.step_times.record(step_span.seconds)
            if self.profiler is not None:
                # Device sync so the profiled window reflects real step time.
                jax.block_until_ready(loss)
                self.profiler.step()
            if self.log_every and (i + 1) % self.log_every == 0:
                last_loss = float(loss)
                self.metrics.log(int(self.state.step), loss=last_loss, epoch=epoch)
            if self._drain_armed and self._drain_due():
                host_losses = [float(l) for l in losses]
                self._drain_exit(
                    epoch,
                    steps_done=i + 1,
                    loss_sum=carry_sum + float(np.sum(host_losses)),
                    loss_count=carry_count + len(host_losses),
                )
        # The host waits here for the device to finish the epoch's steps.
        with tr.phase("epoch.loss_fetch", epoch=epoch, steps=len(losses)):
            total = carry_sum + (
                float(np.sum([float(l) for l in losses])) if losses else 0.0
            )
        count = carry_count + len(losses)
        epoch_loss = total / count if count else 0.0
        self.metrics.log(
            int(self.state.step),
            epoch_loss=epoch_loss,
            epoch=epoch,
            step_time_s_p50=self.step_times.quantile(0.5),
            step_time_s_p95=self.step_times.quantile(0.95),
        )
        return epoch_loss

    def _eval_apply(self, variables, inputs, **kwargs):
        """Forward in eval mode. Models whose ``__call__`` takes a ``train``
        flag (BatchNorm family) get ``train=False`` so running statistics are
        used; models without one are called plainly. Detected via the call
        signature — a try/except on TypeError would mask real errors and
        silently fall back to train mode."""
        import inspect

        signature = inspect.signature(type(self.model).__call__)
        if "train" in signature.parameters:
            kwargs["train"] = False
        return self.model.apply(variables, inputs, **kwargs)

    def _prepare_eval_loader(self, eval_data: ShardedLoader) -> ShardedLoader:
        """Mesh divisibility checks + pad-final-batch on a COPY (the caller's
        loader must not change behavior)."""
        if self.mesh is not None:
            data_size = self.mesh.shape.get("data", 1)
            if eval_data.batch_size % data_size != 0:
                raise ValueError(
                    f"eval batch_size {eval_data.batch_size} is not divisible "
                    f"by the mesh's data axis ({data_size})"
                )
            if not eval_data.drop_last and not eval_data.pad_final_batch:
                import copy

                eval_data = copy.copy(eval_data)
                eval_data.pad_final_batch = True
        return eval_data

    def evaluate(self, eval_data: ShardedLoader, metric_fns=None):
        """Forward-only evaluation over ``eval_data`` (no gradients, no state
        mutation). No reference analog — the reference never evaluates
        (SURVEY.md §5: loss is computed but not even logged).

        When ``loss_fn`` has a per-sample twin (``losses.PER_SAMPLE_TWINS`` —
        both stock losses do) the mean is EXACT on any dataset size / mesh
        shape: per-sample losses are computed and the loader's wrap-pad
        duplicate rows (shard- and batch-level) are weighted to zero, so no
        padding bias enters. ``metric_fns`` adds further per-sample metrics
        (``{name: (predictions, targets) -> [batch]}``, e.g.
        ``losses.per_sample_accuracy``); passing it returns a dict of means
        instead of the bare loss float.

        Custom opaque ``loss_fn``s (no per-sample twin, no ``metric_fns``)
        fall back to the weighted-batch-mean path, which over-counts wrapped
        duplicates on a non-divisible eval set under a mesh — the
        DistributedSampler semantic the training path deliberately keeps."""
        from distributed_pytorch_tpu.training.losses import PER_SAMPLE_TWINS

        fns = dict(metric_fns or {})
        if "loss" not in fns:
            per_sample_loss = PER_SAMPLE_TWINS.get(self.loss_fn)
            if per_sample_loss is not None:
                fns["loss"] = per_sample_loss

        if not fns:
            return self._evaluate_batch_mean(eval_data)

        # Key the cached step by the metric FUNCTIONS, not just their names:
        # a different fn under the same name must rebuild, or it would
        # silently return the old metric under the new label.
        fns_key = frozenset(fns.items())
        if self._eval_step is None or self._eval_step_fns != fns_key:
            from distributed_pytorch_tpu.training.train_step import (
                make_metrics_eval_step,
            )

            self._eval_step = make_metrics_eval_step(
                self._eval_apply, fns, mesh=self.mesh,
                state_sharding=self.state_sharding,
            )
            self._eval_step_fns = fns_key

        eval_data = self._prepare_eval_loader(eval_data)
        totals = None
        weight_rows = eval_data.batch_weight_table()
        for (xs, ys), w in zip(eval_data, weight_rows):
            if self.mesh is None:
                batch, weights = jax.device_put(((xs, ys), w))
            else:
                batch, weights = put_global_batch(self.mesh, ((xs, ys), w))
            out = self._eval_step(self.state, batch, weights)
            self._touch_heartbeat()
            totals = (
                out
                if totals is None
                else jax.tree_util.tree_map(jnp.add, totals, out)
            )
        if totals is None:
            return 0.0 if metric_fns is None else {}
        # One host fetch for all sums, not one device sync per metric.
        names = sorted(totals)
        host = np.asarray(jnp.stack([totals[k] for k in names]))
        sums = dict(zip(names, host))
        weight = max(float(sums.pop("__weight__")), 1e-9)
        results = {name: float(value) / weight for name, value in sums.items()}
        self.metrics.log(
            int(self.state.step),
            **{f"eval_{k}" if k != "loss" else "eval_loss": v
               for k, v in results.items()},
        )
        return results if metric_fns is not None else results["loss"]

    def _evaluate_batch_mean(self, eval_data: ShardedLoader) -> float:
        """Legacy weighted-batch-mean eval for opaque loss functions (wrapped
        duplicates count toward the mean — see ``evaluate``)."""
        if self._eval_step is None or self._eval_step_fns is not None:
            self._eval_step = make_eval_step(
                self._eval_apply, self.loss_fn, mesh=self.mesh,
                state_sharding=self.state_sharding,
            )
            self._eval_step_fns = None
        eval_data = self._prepare_eval_loader(eval_data)
        losses, weights = [], []
        for xs, ys in eval_data:
            losses.append(self._eval_step(self.state, self._put_batch(xs, ys)))
            self._touch_heartbeat()
            weights.append(xs.shape[0])
        if losses:
            host_losses = np.asarray(jnp.stack(losses))
            eval_loss = float(np.average(host_losses, weights=weights))
        else:
            eval_loss = 0.0
        self.metrics.log(int(self.state.step), eval_loss=eval_loss)
        return eval_loss

    def train(self, max_epochs: int) -> None:
        """Epoch loop with snapshot/checkpoint cadence (twin of ``train``,
        ``multigpu_torchrun.py:64-68``: resumes from ``epochs_run``)."""
        if self.profiler is not None:
            self.profiler.start()
        try:
            for epoch in range(self.epochs_run, max_epochs):
                epoch_loss = self._run_epoch(epoch)
                self.epochs_run = epoch + 1
                if self.save_every and (epoch + 1) % self.save_every == 0:
                    if self.snapshot_path is not None:
                        self._save_snapshot(epoch)
                    else:
                        self._save_checkpoint(epoch, metric=epoch_loss)
        finally:
            try:
                if self.checkpointer is not None:
                    # Snapshot must be durable before returning; a surfaced
                    # write error must not skip profiler/metrics cleanup.
                    self.checkpointer.wait()
            finally:
                if self.profiler is not None:
                    self.profiler.stop()
                self.metrics.close()
