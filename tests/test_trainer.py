"""Trainer integration tests: end-to-end epochs, DP parity, snapshot resume
(the reference's elasticity contract, ``multigpu_torchrun.py:30-40,57-65``)."""

import pytest
import jax
import numpy as np
import optax

from distributed_pytorch_tpu.models.toy import ToyRegressor
from distributed_pytorch_tpu.parallel.mesh import make_mesh
from distributed_pytorch_tpu.training.trainer import Trainer
from distributed_pytorch_tpu.utils.data import MaterializedDataset, ShardedLoader


def _loader(batch=32, n=256, seed=0, **kw):
    return ShardedLoader(MaterializedDataset(n, seed=seed), batch, **kw)


def test_trainer_serial_end_to_end(tmp_path):
    trainer = Trainer(
        ToyRegressor(),
        _loader(),
        optax.sgd(1e-2),
        save_every=2,
        checkpoint_path=str(tmp_path / "ckpt.npz"),
    )
    first = trainer._run_epoch(0)
    trainer.train(4)
    last = trainer._run_epoch(99)
    assert last < first
    assert (tmp_path / "ckpt.npz").exists()


def test_trainer_dp_matches_serial(tmp_path):
    """Same seed + same global batch: 8-way DP Trainer == serial Trainer."""
    mesh = make_mesh()
    serial = Trainer(
        ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
        checkpoint_path=str(tmp_path / "a.npz"),
    )
    dp = Trainer(
        ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
        checkpoint_path=str(tmp_path / "b.npz"), mesh=mesh,
    )
    l1 = serial._run_epoch(0)
    l2 = dp._run_epoch(0)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(serial.state.params),
        jax.tree_util.tree_leaves(dp.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_snapshot_resume_contract(tmp_path):
    """Train 2 epochs with snapshots -> new Trainer resumes at epoch 2 and
    finishes to 4 with state identical to an uninterrupted 4-epoch run."""
    snap = str(tmp_path / "snapshot.npz")

    t1 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    t1.train(2)

    # "Crash" and restart: fresh Trainer probes the snapshot on init.
    t2 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    assert t2.epochs_run == 2
    t2.train(4)

    # Uninterrupted reference run.
    t3 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
                 snapshot_path=None, checkpoint_path=str(tmp_path / "c.npz"))
    t3.train(4)

    for a, b in zip(
        jax.tree_util.tree_leaves(t2.state.params),
        jax.tree_util.tree_leaves(t3.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_snapshot_resume_with_adam_opt_state(tmp_path):
    """Optimizer state survives resume (the gap the reference leaves open)."""
    snap = str(tmp_path / "snap.npz")
    t1 = Trainer(ToyRegressor(), _loader(), optax.adam(1e-3), save_every=1,
                 snapshot_path=snap)
    t1.train(2)
    t2 = Trainer(ToyRegressor(), _loader(), optax.adam(1e-3), save_every=1,
                 snapshot_path=snap)
    t2.train(4)
    t3 = Trainer(ToyRegressor(), _loader(), optax.adam(1e-3), save_every=0,
                 snapshot_path=None, checkpoint_path=str(tmp_path / "c.npz"))
    t3.train(4)
    for a, b in zip(
        jax.tree_util.tree_leaves(t2.state.params),
        jax.tree_util.tree_leaves(t3.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_trainer_mesh_auto_pads_ragged_batches(tmp_path):
    """Non-divisible dataset on a mesh: Trainer wrap-pads the final batch so
    shapes stay static and P('data') placement works."""
    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    mesh = make_mesh()
    loader = _loader(batch=32, n=100)
    trainer = Trainer(ToyRegressor(), loader, optax.sgd(1e-2), save_every=0,
                      checkpoint_path=str(tmp_path / "c.npz"), mesh=mesh)
    assert loader.pad_final_batch
    trainer.train(1)  # would crash on the 4-row final batch without padding


def test_trainer_mesh_rejects_indivisible_batch(tmp_path):
    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    import pytest
    mesh = make_mesh()
    with pytest.raises(ValueError, match="not divisible"):
        Trainer(ToyRegressor(), _loader(batch=12), optax.sgd(1e-2), save_every=0,
                mesh=mesh)


@pytest.mark.slow
def test_checkpoint_includes_model_state(tmp_path):
    """Plain checkpoints carry BatchNorm running stats (reference parity:
    state_dict includes them)."""
    import numpy as np
    from distributed_pytorch_tpu.checkpoint import load_checkpoint
    from distributed_pytorch_tpu.models import ResNet18
    from distributed_pytorch_tpu.training.losses import softmax_cross_entropy_loss
    from distributed_pytorch_tpu.utils.data import RandomDataset

    ds = RandomDataset(16, (16, 16, 3), num_classes=10)
    loader = ShardedLoader(ds, 8)
    path = str(tmp_path / "ckpt.npz")
    trainer = Trainer(ResNet18(num_classes=10), loader, optax.sgd(1e-2),
                      save_every=1, checkpoint_path=path,
                      loss_fn=softmax_cross_entropy_loss)
    trainer.train(1)
    template = {"params": trainer.state.params, "model_state": trainer.state.model_state}
    restored, meta = load_checkpoint(path, template)
    stats = jax.tree_util.tree_leaves(restored["model_state"])
    assert stats and any(not np.allclose(np.asarray(s), 0) for s in stats)


@pytest.mark.slow
def test_trainer_partition_specs_zero1_and_fsdp(tmp_path):
    """The sharding zoo through the flagship API: Trainer(partition_specs=)
    with ZeRO-1 (TrainState-shaped specs) and FSDP (params-shaped specs)
    both match the replicated-DP loss, shard what they claim on device, and
    survive the snapshot-resume contract under sharded placement."""
    import optax as _optax

    from distributed_pytorch_tpu.parallel.partitioning import (
        make_fsdp_specs,
        make_zero1_state_specs,
    )

    def make(partition_specs=None, mesh=None, snap=None):
        return Trainer(
            ToyRegressor(), _loader(), _optax.adam(1e-2), save_every=1,
            mesh=mesh, partition_specs=partition_specs,
            snapshot_path=snap,
            checkpoint_path=str(tmp_path / "unused.npz"),
        )

    mesh8 = make_mesh({"data": 8})
    dp = make(mesh=mesh8)
    base = dp._run_epoch(0)

    # ZeRO-1 on a 4-device mesh (the toy kernel's dim 20 shards 4-way; it
    # has no 8-divisible dim): Adam mu sharded, params not.
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    z1_specs = make_zero1_state_specs(make(mesh=mesh).state, mesh=mesh)
    z1 = make(partition_specs=z1_specs, mesh=mesh)
    np.testing.assert_allclose(z1._run_epoch(0), base, rtol=1e-5)
    assert all(
        leaf.sharding.is_fully_replicated
        for leaf in jax.tree_util.tree_leaves(z1.state.params)
    )
    assert any(
        not a.sharding.is_fully_replicated
        for a in jax.tree_util.tree_leaves(z1.state.opt_state[0].mu)
    )

    # FSDP: params-shaped specs, lifted onto the state internally.
    fsdp_mesh = make_mesh({"data": 2, "fsdp": 4})
    probe = make(mesh=fsdp_mesh)
    fsdp_specs = make_fsdp_specs(probe.state.params, mesh=fsdp_mesh)
    fsdp = make(partition_specs=fsdp_specs, mesh=fsdp_mesh)
    np.testing.assert_allclose(fsdp._run_epoch(0), base, rtol=1e-5)

    # Snapshot round-trip under sharded placement: resume keeps the specs.
    snap = str(tmp_path / "z1.npz")
    t1 = make(partition_specs=z1_specs, mesh=mesh, snap=snap)
    t1.train(2)
    t2 = make(partition_specs=z1_specs, mesh=mesh, snap=snap)
    assert t2.epochs_run == 2
    assert any(
        not a.sharding.is_fully_replicated
        for a in jax.tree_util.tree_leaves(t2.state.opt_state[0].mu)
    )


def test_trainer_partition_specs_requires_mesh():
    import pytest as _pytest

    with _pytest.raises(ValueError, match="mesh"):
        Trainer(
            ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
            partition_specs={"linear": None},
        )


def test_trainer_evaluate_with_partition_specs(tmp_path):
    """Exact eval runs against a ZeRO-1-sharded state and matches the
    replicated-DP eval (the eval steps inherit state_sharding)."""
    import optax as _optax

    from distributed_pytorch_tpu.parallel.partitioning import (
        make_zero1_state_specs,
    )

    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    eval_loader = _loader(batch=32, n=96, seed=7)

    def make(specs=None):
        return Trainer(
            ToyRegressor(), _loader(), _optax.adam(1e-2), save_every=0,
            mesh=mesh, partition_specs=specs,
            checkpoint_path=str(tmp_path / "unused.npz"),
        )

    dp = make()
    dp._run_epoch(0)
    base = dp.evaluate(eval_loader)

    # dp.state already has the TrainState structure the specs need.
    z1 = make(make_zero1_state_specs(dp.state, mesh=mesh))
    z1._run_epoch(0)
    np.testing.assert_allclose(z1.evaluate(eval_loader), base, rtol=1e-5)


def test_trainer_rotating_checkpoints(tmp_path):
    """keep_checkpoints=K: checkpoint_path becomes a rotating directory —
    newest K survive, best-by-epoch-loss protected, contents restorable."""
    import optax

    from distributed_pytorch_tpu.checkpoint import CheckpointManager
    from distributed_pytorch_tpu.models.toy import ToyRegressor
    from distributed_pytorch_tpu.training.losses import mse_loss
    from distributed_pytorch_tpu.training.trainer import Trainer
    from distributed_pytorch_tpu.utils.data import MaterializedDataset, ShardedLoader

    data = MaterializedDataset(64)
    loader = ShardedLoader(data, 16)
    ckpt_dir = str(tmp_path / "rotated")
    trainer = Trainer(
        ToyRegressor(),
        loader,
        optax.sgd(1e-2),
        save_every=1,
        checkpoint_path=ckpt_dir,
        loss_fn=mse_loss,
        keep_checkpoints=2,
    )
    trainer.train(5)
    import os as _os

    files = sorted(_os.listdir(ckpt_dir))
    # 2 newest; best may coincide with a newest file (loss usually falls).
    assert 2 <= len(files) <= 3, files
    mgr = CheckpointManager(ckpt_dir, keep=2)
    template = {
        "params": trainer.state.params,
        "model_state": trainer.state.model_state,
    }
    restored, meta = mgr.restore(template)
    assert meta["epochs_run"] == 5
    assert "metric" in meta


# ------------------------------------------------------------ graceful drain


@pytest.fixture
def _restore_sigterm():
    import signal

    prev = signal.getsignal(signal.SIGTERM)
    yield
    signal.signal(signal.SIGTERM, prev)


def _drain_after(trainer, n_batches):
    """Arm trainer to raise its drain flag after the Nth _run_batch call."""
    orig = trainer._run_batch
    calls = {"n": 0}

    def wrapped(batch):
        loss = orig(batch)
        calls["n"] += 1
        if calls["n"] == n_batches:
            trainer._drain_flag = True
        return loss

    trainer._run_batch = wrapped


def test_drain_mid_epoch_snapshot_and_exact_resume(tmp_path, capsys, _restore_sigterm):
    """The tentpole contract, in-process: a drain request lands mid-epoch,
    the trainer finishes the in-flight batch, snapshots at (epoch, step),
    exits with the drain code — and a fresh Trainer resumes at that exact
    batch, finishing with params identical to an uninterrupted run."""
    from distributed_pytorch_tpu.checkpoint import load_snapshot

    snap = str(tmp_path / "snapshot.npz")
    t1 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    # 8 batches/epoch; drain on the 11th batch = epoch 1, steps_done 3.
    _drain_after(t1, 11)
    with pytest.raises(SystemExit) as exc:
        t1.train(3)
    assert exc.value.code == 121  # default TPURUN_DRAIN_EXIT_CODE
    out = capsys.readouterr().out
    assert "[drain] just-in-time snapshot at epoch 1, step 3" in out

    restored, meta = load_snapshot(snap, t1.state)
    assert meta["epochs_run"] == 1
    assert meta["step_in_epoch"] == 3
    assert meta["order"] == t1.train_data.order_state()
    assert meta["loss_count"] == 3

    t2 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    assert t2.epochs_run == 1
    out = capsys.readouterr().out
    assert "Resuming training from snapshot at Epoch 1, step 3" in out
    t2.train(3)

    t3 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
                 snapshot_path=None, checkpoint_path=str(tmp_path / "c.npz"))
    t3.train(3)
    for a, b in zip(
        jax.tree_util.tree_leaves(t2.state.params),
        jax.tree_util.tree_leaves(t3.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_drain_epoch_loss_parity_across_resume(tmp_path, capsys, _restore_sigterm):
    """The interrupted epoch's reported mean loss (carry + tail) matches the
    uninterrupted run's mean for the same epoch."""
    import re

    snap = str(tmp_path / "snapshot.npz")
    t1 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    _drain_after(t1, 5)  # epoch 0, steps_done 5 of 8
    with pytest.raises(SystemExit):
        t1.train(2)
    capsys.readouterr()

    t2 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    resumed_loss = t2._run_epoch(0)

    t3 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
                 snapshot_path=None, checkpoint_path=str(tmp_path / "c.npz"))
    full_loss = t3._run_epoch(0)
    np.testing.assert_allclose(resumed_loss, full_loss, rtol=1e-6)


def test_drain_file_poll_and_exit_code_override(tmp_path, monkeypatch, capsys, _restore_sigterm):
    """The agent-side signal: touching TPURUN_DRAIN_FILE drains the very next
    batch, and TPURUN_DRAIN_EXIT_CODE overrides the exit status."""
    drain_file = tmp_path / "drain.0"
    monkeypatch.setenv("TPURUN_DRAIN_FILE", str(drain_file))
    monkeypatch.setenv("TPURUN_DRAIN_EXIT_CODE", "77")
    snap = str(tmp_path / "snapshot.npz")
    t = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                snapshot_path=snap)
    drain_file.write_text("drain\n")
    with pytest.raises(SystemExit) as exc:
        t.train(2)
    assert exc.value.code == 77
    assert "[drain] just-in-time snapshot at epoch 0, step 1" in capsys.readouterr().out


def test_sigterm_with_drain_file_present_sets_flag(tmp_path, monkeypatch, _restore_sigterm):
    """Under tpurun (TPURUN_DRAIN_FILE set), SIGTERM with the drain file
    touched means 'snapshot and go' — the handler latches the flag instead
    of killing the process."""
    import os
    import signal

    drain_file = tmp_path / "drain.0"
    drain_file.write_text("drain\n")
    monkeypatch.setenv("TPURUN_DRAIN_FILE", str(drain_file))
    t = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                snapshot_path=str(tmp_path / "s.npz"))
    assert not t._drain_flag
    os.kill(os.getpid(), signal.SIGTERM)
    assert t._drain_flag  # delivered synchronously at the next bytecode


def test_drain_without_snapshot_path_is_inert(tmp_path, _restore_sigterm):
    """No snapshot_path -> nothing to drain to: the flag is ignored and the
    run completes normally (matches a plain, non-elastic launch)."""
    t = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=0,
                checkpoint_path=str(tmp_path / "c.npz"))
    t._drain_flag = True
    t.train(1)  # must not raise SystemExit
    assert t.epochs_run == 1


def test_drain_resume_geometry_mismatch_replays_epoch(tmp_path, capsys, _restore_sigterm):
    """A snapshot taken mid-epoch under a different loader geometry (elastic
    scale-down) cannot be resumed at the saved step: the epoch replays from
    step 0, loudly."""
    snap = str(tmp_path / "snapshot.npz")
    t1 = Trainer(ToyRegressor(), _loader(), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    _drain_after(t1, 3)
    with pytest.raises(SystemExit):
        t1.train(2)
    capsys.readouterr()

    t2 = Trainer(ToyRegressor(), _loader(batch=16), optax.sgd(1e-2), save_every=1,
                 snapshot_path=snap)
    out = capsys.readouterr().out
    assert "different loader geometry" in out
    assert "Resuming training from snapshot at Epoch 0" in out
    assert t2._resume_step == 0
    t2.train(1)  # replays epoch 0 from scratch, completes
    assert t2.epochs_run == 1


# ------------------------------------------------ host phases in the tracer


def _traced_run(tmp_path, tracer, name, mesh=None, epochs=2):
    """Two epochs over a toy ``ArrayDataset`` with loader and Trainer
    recording to ``tracer``; returns the Trainer."""
    from distributed_pytorch_tpu.utils.data import ArrayDataset

    rng = np.random.default_rng(0)
    data = ArrayDataset(
        rng.random((64, 20), dtype=np.float32),
        rng.random((64, 1), dtype=np.float32),
    )
    trainer = Trainer(
        ToyRegressor(), ShardedLoader(data, 16, tracer=tracer),
        optax.sgd(1e-2), save_every=0, mesh=mesh,
        checkpoint_path=str(tmp_path / f"{name}.npz"), tracer=tracer,
    )
    if trainer.tracer.enabled:
        # the Trainer drew one batch to initialise the model from
        trainer.tracer.events.clear()
    trainer.train(epochs)
    return trainer


@pytest.mark.parametrize("mesh", [False, True], ids=["serial", "mesh"])
def test_trainer_writes_its_host_phases_and_trains_the_same(
    tmp_path, mesh, dispatcher_off
):
    """Per step one each of ``loader.index``, ``loader.stack``, ``step`` >
    ``put_batch`` + ``step.dispatch``; per epoch one ``epoch`` holding all
    of them and one ``epoch.loss_fetch``; a ``recycle.fence`` after every
    step from the third on, where the Trainer waits for the step two back
    before it hands that step's arrays to the loader; and the state a Trainer
    reaches does not depend on who records, nor on whether the set-up
    timeline's dispatcher is installed."""
    from distributed_pytorch_tpu.obs.tracer import NULL_TRACER, Tracer
    from distributed_pytorch_tpu.obs.xla import install_dispatcher
    from distributed_pytorch_tpu.training.trainer import HOST_BATCHES

    mesh = make_mesh() if mesh else None
    tr = Tracer()
    assert install_dispatcher()
    traced = _traced_run(tmp_path, tr, "traced", mesh)
    with dispatcher_off():
        silent = _traced_run(tmp_path, NULL_TRACER, "silent", mesh)
    for a, b in zip(jax.tree_util.tree_leaves(traced.state),
                    jax.tree_util.tree_leaves(silent.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    assert {e["ph"] for e in tr.events} == {"X"}
    named = lambda name, **args: [  # noqa: E731
        e for e in tr.events if e["name"] == name
        and all(e["args"][k] == v for k, v in args.items())]
    inside = lambda c, p: (  # noqa: E731
        p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"])
    before = lambda a, b: a["ts"] + a["dur"] <= b["ts"]  # noqa: E731
    for epoch in range(2):
        (whole,) = named("epoch", epoch=epoch)
        (fetch,) = named("epoch.loss_fetch", epoch=epoch)
        assert inside(fetch, whole) and fetch["args"]["steps"] == 4
        for step in range(4):
            at = dict(epoch=epoch, step=step)
            (index,) = named("loader.index", **at)
            (stack,) = named("loader.stack", **at)
            (one,) = named("step", **at)
            (put,) = named("put_batch", **at)
            (dispatch,) = named("step.dispatch", **at)
            for e in (index, stack, one):
                assert inside(e, whole)
            assert inside(put, one) and inside(dispatch, one)
            assert before(index, stack) and before(stack, one)
            assert before(put, dispatch) and before(one, fetch)
            assert index["args"]["rows"] == 16
            assert stack["args"]["bytes"] == 16 * 21 * 4
            assert put["args"]["bytes"] == stack["args"]["bytes"]
            # the Trainer holds HOST_BATCHES arrays: from then on every batch
            # is stacked into the one it handed back after waiting for its step
            nth = 4 * epoch + step
            assert stack["args"]["recycled"] is (nth >= HOST_BATCHES)
            fences = named("recycle.fence", **at)
            assert len(fences) == (nth >= HOST_BATCHES - 1)
            for fence in fences:
                assert inside(fence, whole) and before(one, fence)
    loader = traced.train_data
    # the Trainer's own first draw (to initialise the model) it keeps
    assert loader.batches_allocated == 1 + HOST_BATCHES
    assert loader.batches_recycled == 8 - HOST_BATCHES
    assert len(tr.events) == 2 * (2 + 4 * 5) + 8 - (HOST_BATCHES - 1)
    # the step's own slice is what the step-time reservoir was fed from
    assert traced.step_times.count == 8 and silent.step_times.count == 0
    assert traced.step_times.quantile(1.0) == pytest.approx(
        max(e["dur"] for e in named("step")) / 1e6)


def test_trainer_and_loader_record_to_the_process_tracer_by_default(tmp_path):
    from distributed_pytorch_tpu.obs.tracer import process_tracer

    tr = process_tracer()
    trainer = _traced_run(tmp_path, None, "default", epochs=1)
    assert trainer.tracer is tr and trainer.train_data.tracer is tr
    names = [e["name"] for e in tr.events]
    assert names.count("epoch") == 1 and names.count("step.dispatch") == 4
    assert names.count("loader.stack") == 4


# ------------------------------------------- recycled batch buffers (PR 27)


def _watch_losses(trainer):
    """Every step's loss, as the device array the step returned (fetched
    after the run, so watching adds no sync between steps)."""
    losses = []
    run = trainer._run_batch

    def watched(batch):
        losses.append(run(batch))
        return losses[-1]

    trainer._run_batch = watched
    return losses


@pytest.mark.parametrize("mesh", [False, True], ids=["serial", "mesh"])
def test_recycled_buffers_train_the_same_as_copies(tmp_path, mesh):
    """On this backend ``device_put`` may alias the host arrays, so a buffer
    refilled before its step was done would change that step's batch. The
    loss sequence with the loader's arrays recycled equals the sequence with
    recycling out of play (the step reads a private copy of every batch)."""
    from distributed_pytorch_tpu.training.trainer import HOST_BATCHES

    def run(name, private_copies):
        trainer = Trainer(
            ToyRegressor(), _loader(batch=16, n=128, shuffle=True),
            optax.sgd(1e-2), save_every=0,
            mesh=make_mesh() if mesh else None,
            checkpoint_path=str(tmp_path / f"{name}.npz"),
        )
        if private_copies:
            put = trainer._put_batch
            trainer._put_batch = lambda xs, ys: put(xs.copy(), ys.copy())
        losses = _watch_losses(trainer)
        trainer.train(2)
        return [float(l) for l in losses], trainer.train_data

    recycled, loader = run("recycled", False)
    copied, _ = run("copied", True)
    assert len(recycled) == 16 >= 3 * HOST_BATCHES
    assert recycled == copied
    # recycling was in play: all but the first few went into handed-back arrays
    assert loader.batches_recycled == 16 - HOST_BATCHES
    assert loader.batches_allocated == 1 + HOST_BATCHES


def test_arrays_go_back_only_after_their_own_step_was_waited_for(
    tmp_path, monkeypatch
):
    """The race above shows by luck on a small model; the order that rules
    it out does not: every pair of arrays reaches ``loader.recycle`` after
    ``block_until_ready`` of the loss of the step that read them, oldest
    first, and the newest ``HOST_BATCHES - 1`` stay lent."""
    from distributed_pytorch_tpu.training.trainer import HOST_BATCHES

    trainer = Trainer(
        ToyRegressor(), _loader(batch=16, n=128, shuffle=True),
        optax.sgd(1e-2), save_every=0,
        checkpoint_path=str(tmp_path / "c.npz"),
    )
    losses = _watch_losses(trainer)
    put_order, waited, handed = [], [], []
    put, recycle = trainer._put_batch, trainer.train_data.recycle
    ready = jax.block_until_ready

    def watched_put(xs, ys):
        put_order.append(xs)
        return put(xs, ys)

    def watched_ready(x):
        waited.append(x)
        return ready(x)

    def watched_recycle(xs, ys):
        step = max(n for n, seen in enumerate(put_order) if seen is xs)
        assert any(w is losses[step] for w in waited)
        handed.append(step)
        recycle(xs, ys)

    trainer._put_batch = watched_put
    trainer.train_data.recycle = watched_recycle
    monkeypatch.setattr(jax, "block_until_ready", watched_ready)
    trainer.train(2)
    assert handed == list(range(16 - (HOST_BATCHES - 1)))


def test_mid_epoch_resume_with_buffers_in_circulation(
    tmp_path, _restore_sigterm
):
    """A drain leaves with batches lent to steps and one handed back; the
    resumed Trainer's loader starts at the drained step with none, and from
    there on the losses are the uninterrupted run's."""
    from distributed_pytorch_tpu.obs.tracer import Tracer
    from distributed_pytorch_tpu.training.trainer import HOST_BATCHES

    def build(snap, tracer=None):
        return Trainer(
            ToyRegressor(), _loader(shuffle=True, seed=3, tracer=tracer),
            optax.sgd(1e-2), save_every=1, snapshot_path=snap, tracer=tracer,
        )

    whole = build(str(tmp_path / "whole.npz"))
    want = _watch_losses(whole)
    whole.train(2)

    snap = str(tmp_path / "snapshot.npz")
    t1 = build(snap)
    head = _watch_losses(t1)
    _drain_after(t1, 11)  # 8 batches an epoch: epoch 1, 3 steps done
    with pytest.raises(SystemExit):
        t1.train(2)
    assert t1.train_data.batches_recycled >= 5

    tr = Tracer()
    t2 = build(snap, tracer=tr)
    tr.events.clear()  # the Trainer's own first draw
    tail = _watch_losses(t2)
    t2.train(2)
    stacked = [e["args"] for e in tr.events if e["name"] == "loader.stack"]
    assert [a["step"] for a in stacked] == [3, 4, 5, 6, 7]
    assert [a["recycled"] for a in stacked] == (
        [False] * HOST_BATCHES + [True] * (5 - HOST_BATCHES)
    )
    got = [float(l) for l in head + tail]
    assert len(head) == 11 and got == [float(l) for l in want]
