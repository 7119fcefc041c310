"""Rung 1 — serial training on one chip. Twin of ``single_gpu.py``.

The whole reference hot loop (``single_gpu.py:21-26``) is one jitted
``train_step``; there is no device id to pass around — JAX places arrays on the
default device.

Run:  python examples/single_chip.py 10 2 [--batch_size 32] [--policy bf16]
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


import optax

from distributed_pytorch_tpu import MaterializedDataset, ShardedLoader, Trainer
from distributed_pytorch_tpu.models import ToyRegressor

# Compute-dtype policies (training/mixed_precision.py): params stay float32
# master weights in every case. fp16 has a 5-bit exponent, so it trains under
# a dynamic loss scale; bf16/f32 need none. The reference trains fp32 only.
POLICIES = ("f32", "bf16", "fp16")


def load_train_objs(policy: str = "f32"):
    """Factory twin of ``load_train_objs`` (``single_gpu.py:48-52``):
    2048-sample toy dataset, Linear(20,1) model, SGD(lr=1e-3)."""
    from distributed_pytorch_tpu.training import (
        BF16_POLICY,
        F32_POLICY,
        FP16_POLICY,
    )

    dtype = {
        "f32": F32_POLICY,
        "bf16": BF16_POLICY,
        "fp16": FP16_POLICY,
    }[policy].compute_dtype
    dataset = MaterializedDataset(2048)
    model = ToyRegressor(dtype=dtype)
    optimizer = optax.sgd(1e-3)
    return dataset, model, optimizer


def main(total_epochs: int, save_every: int, batch_size: int, policy: str):
    dataset, model, optimizer = load_train_objs(policy)
    loader = ShardedLoader(dataset, batch_size, shuffle=True)
    loss_scale = None
    if policy == "fp16":
        from distributed_pytorch_tpu.training import DynamicLossScale

        loss_scale = DynamicLossScale.create()
    trainer = Trainer(
        model, loader, optimizer, save_every, loss_scale=loss_scale
    )
    trainer.train(total_epochs)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="serial training job (rung 1)")
    parser.add_argument("total_epochs", type=int, help="Total epochs to train the model")
    parser.add_argument("save_every", type=int, help="How often to save a checkpoint")
    parser.add_argument("--batch_size", default=32, type=int,
                        help="Input batch size on each device (default: 32)")
    parser.add_argument("--policy", default="f32", choices=POLICIES,
                        help="compute dtype policy (fp16 adds dynamic loss scaling)")
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices instead of real chips")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args.total_epochs, args.save_every, args.batch_size, args.policy)
