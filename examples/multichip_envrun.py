"""Rung 3 — env-driven bootstrap + elastic snapshot resume.
Twin of ``multigpu_torchrun.py``.

The torchrun contract (env rendezvous + restart-and-resume,
``multigpu_torchrun.py:12-13,30-40,57-65``) maps to:

* rendezvous: ``setup_distributed()`` reads ``COORDINATOR_ADDRESS`` /
  ``NUM_PROCESSES`` / ``PROCESS_ID`` (the MASTER_ADDR / WORLD_SIZE / RANK
  analogs) and calls ``jax.distributed.initialize``; unset -> single process.
* elasticity: if ``snapshot.npz`` exists the Trainer loads it on init and
  ``train()`` resumes from ``epochs_run``. Kill any process mid-run, relaunch
  the same command, and training continues from the last snapshot — including
  optimizer state, which the reference forgets.

Run (single host):    python examples/multichip_envrun.py 10 2
Run (N processes):    COORDINATOR_ADDRESS=host0:1234 NUM_PROCESSES=N PROCESS_ID=i \
                          python examples/multichip_envrun.py 10 2
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


import jax
import optax

from distributed_pytorch_tpu import (
    MaterializedDataset,
    ShardedLoader,
    Trainer,
    make_mesh,
    setup_distributed,
    shutdown_distributed,
)
from distributed_pytorch_tpu.models import ToyRegressor


def load_train_objs():
    """Factory twin of ``multigpu_torchrun.py:71-75``."""
    dataset = MaterializedDataset(2048)
    model = ToyRegressor()
    optimizer = optax.sgd(1e-3)
    return dataset, model, optimizer


def main(total_epochs: int, save_every: int, batch_size: int, snapshot_path: str):
    setup_distributed()  # env-driven; no-op when single-process
    mesh = make_mesh()
    dataset, model, optimizer = load_train_objs()
    # Each process loads only the shard its chips will consume.
    per_process_batch = batch_size * jax.local_device_count()
    loader = ShardedLoader(
        dataset,
        per_process_batch,
        shuffle=True,
        num_shards=jax.process_count(),
        shard_index=jax.process_index(),
    )
    trainer = Trainer(
        model, loader, optimizer, save_every, snapshot_path=snapshot_path, mesh=mesh
    )
    trainer.train(total_epochs)
    shutdown_distributed()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="env-bootstrapped elastic training job (rung 3)"
    )
    parser.add_argument("total_epochs", type=int, help="Total epochs to train the model")
    parser.add_argument("save_every", type=int, help="How often to save a snapshot")
    parser.add_argument("--batch_size", default=32, type=int,
                        help="Input batch size per chip (default: 32)")
    parser.add_argument("--snapshot_path", default="snapshot.npz", type=str)
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices instead of real chips")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args.total_epochs, args.save_every, args.batch_size, args.snapshot_path)
