"""Rung 2 — single-host data parallelism over all local chips.
Twin of ``multigpu.py``.

What the reference needed a process per GPU for (``mp.spawn``,
``init_process_group``, DDP wrapping, DistributedSampler — ``multigpu.py:12-36``)
is here ONE process and ONE jitted step over a ``data`` mesh: JAX addresses all
local chips from a single Python process, the global batch is sharded along the
mesh's ``data`` axis, and XLA inserts the gradient all-reduce onto ICI.

``batch_size`` is per-chip (matching the reference's per-rank semantics); the
global batch is ``batch_size * n_chips``.

Run:  python examples/multichip.py 10 2 [--batch_size 32]
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


import jax
import optax

from distributed_pytorch_tpu import MaterializedDataset, ShardedLoader, Trainer, make_mesh
from distributed_pytorch_tpu.models import ToyRegressor


def load_train_objs():
    """Factory twin of ``multigpu.py:65-69``."""
    dataset = MaterializedDataset(2048)
    model = ToyRegressor()
    optimizer = optax.sgd(1e-3)
    return dataset, model, optimizer


def main(total_epochs: int, save_every: int, batch_size: int):
    mesh = make_mesh()  # 1-D {"data": all local chips}
    n_chips = jax.device_count()
    dataset, model, optimizer = load_train_objs()
    # One process feeds the full global batch; the mesh shards it across chips.
    # (Per-process sharding appears at rung 4 when hosts multiply.)
    loader = ShardedLoader(dataset, batch_size * n_chips, shuffle=True)
    trainer = Trainer(model, loader, optimizer, save_every, mesh=mesh)
    trainer.train(total_epochs)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="single-host data-parallel job (rung 2)")
    parser.add_argument("total_epochs", type=int, help="Total epochs to train the model")
    parser.add_argument("save_every", type=int, help="How often to save a checkpoint")
    parser.add_argument("--batch_size", default=32, type=int,
                        help="Input batch size per chip (default: 32)")
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices instead of real chips")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args.total_epochs, args.save_every, args.batch_size)
