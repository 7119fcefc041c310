"""Rung 4 — multi-host TPU pod-slice training. Twin of ``multinode_torchrun.py``
plus the ``slurm/`` launcher directory.

Differences from rung 3 are exactly the reference's rung-3 -> rung-4 diff,
restated for TPU:

* local vs global rank (``multinode_torchrun.py:24-25``): JAX owns the split —
  ``jax.process_index()`` is the global identity, local device binding is
  automatic. Logging uses the global process index, like the reference's
  ``global_rank`` banner (``:52``).
* the launcher: ``launch/tpu_pod_run.sh`` (gcloud ``--worker=all``) replaces
  ``slurm/sbatch_run.sh``; on a real pod slice ``jax.distributed.initialize``
  autodetects topology so no env is needed at all.
* the global batch spans hosts: each process feeds only its addressable shard
  (``put_global_batch`` inside the Trainer assembles the global array) — and
  the snapshot is written by *global* process 0 only, fixing the reference's
  per-node multi-writer race (``multinode_torchrun.py:68``).

Run on a pod slice (from launch/tpu_pod_run.sh):
    gcloud compute tpus tpu-vm ssh $TPU_NAME --worker=all \
        --command="cd /path/to/repo && python examples/multihost_pod.py 50 5"
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
from distributed_pytorch_tpu.training.losses import mse_loss


def load_train_objs():
    """Factory twin of ``multinode_torchrun.py:72-76`` (MSE loss here — the one
    rung where the reference's loss matches its regression head)."""
    dataset = MaterializedDataset(2048)
    model = ToyRegressor()
    optimizer = optax.sgd(1e-3)
    return dataset, model, optimizer


def main(total_epochs: int, save_every: int, batch_size: int, snapshot_path: str):
    setup_distributed()  # pod metadata / env / single-process, in that order
    print(
        f"[proc {jax.process_index()}/{jax.process_count()}] "
        f"{jax.local_device_count()} local / {jax.device_count()} global chips",
        flush=True,
    )
    mesh = make_mesh()
    dataset, model, optimizer = load_train_objs()
    loader = ShardedLoader(
        dataset,
        batch_size * jax.local_device_count(),
        shuffle=True,
        num_shards=jax.process_count(),
        shard_index=jax.process_index(),
    )
    trainer = Trainer(
        model,
        loader,
        optimizer,
        save_every,
        snapshot_path=snapshot_path,
        mesh=mesh,
        loss_fn=mse_loss,
    )
    trainer.train(total_epochs)
    shutdown_distributed()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="multi-host pod training job (rung 4)")
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
