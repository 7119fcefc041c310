"""Rung 5 — profiled real-model training: ResNet-50 on synthetic images with
step-scheduled TensorBoard traces. Twin of ``multigpu_profile.py``.

* torchvision ``resnet50()`` (``multigpu_profile.py:23``) -> our flax ResNet-50
  (NHWC, optional bfloat16 compute for the MXU); the reference's commented-out
  ``vit_l_32`` alternative (``multigpu_profile.py:24``) is a first-class flag
  here: ``--model vit`` swaps in ``ViT_L32`` (305M params), no code edits;
* ``torch.profiler`` with schedule(wait=1, warmup=1, active=5) and
  ``tensorboard_trace_handler`` (``:80-91``) -> ``StepProfiler`` over
  ``jax.profiler.start_trace/stop_trace`` with the same step schedule;
* lazy ``MyRandomDataset(2048, (3,224,224))`` (``:16``) -> ``RandomDataset``
  with NHWC ``(224,224,3)`` and integer class targets.

View traces:  tensorboard --logdir log/resnet50

Run:  python examples/multichip_profile.py [--epochs 3] [--batch_size 32] [--bf16]
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


import jax
import jax.numpy as jnp
import optax

from distributed_pytorch_tpu import RandomDataset, ShardedLoader, StepProfiler, Trainer, make_mesh
from distributed_pytorch_tpu.models import ResNet50, ViT_L32
from distributed_pytorch_tpu.training.losses import softmax_cross_entropy_loss


def load_train_objs(model_name: str, bf16: bool):
    """Factory twin of ``multigpu_profile.py:13-27`` (the torchvision
    resnet50/vit_l_32 swap-in, ``:23-24``, as a flag instead of a comment)."""
    dataset = RandomDataset(2048, (224, 224, 3), num_classes=1000)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    if model_name == "vit":
        model = ViT_L32(num_classes=1000, dtype=dtype)
    else:
        model = ResNet50(dtype=dtype)
    optimizer = optax.sgd(1e-3, momentum=0.9)
    return dataset, model, optimizer


def main(epochs: int, batch_size: int, model_name: str, bf16: bool,
         profile: bool, logdir: str):
    mesh = make_mesh() if jax.device_count() > 1 else None
    dataset, model, optimizer = load_train_objs(model_name, bf16)
    loader = ShardedLoader(dataset, batch_size * jax.device_count(), drop_last=True)
    profiler = StepProfiler(logdir, wait=1, warmup=1, active=5) if profile else None
    trainer = Trainer(
        model,
        loader,
        optimizer,
        save_every=epochs,  # checkpoint at the end (reference saves once, :107-108)
        checkpoint_path=f"{model_name}_checkpoint.npz",
        mesh=mesh,
        loss_fn=softmax_cross_entropy_loss,
        profiler=profiler,
        log_every=10,
    )
    trainer.train(epochs)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="profiled ResNet-50 job (rung 5)")
    parser.add_argument("--epochs", default=3, type=int)
    parser.add_argument("--batch_size", default=32, type=int, help="per-chip batch size")
    parser.add_argument("--model", default="resnet50", choices=["resnet50", "vit"],
                        help="real model to train (reference multigpu_profile.py:23-24)")
    parser.add_argument("--bf16", action="store_true", help="bfloat16 compute (MXU-native)")
    parser.add_argument("--no_profile", action="store_true")
    parser.add_argument("--logdir", default="", type=str,
                        help="trace directory (default: log/<model>)")
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices instead of real chips")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args.epochs, args.batch_size, args.model, args.bf16,
         not args.no_profile, args.logdir or f"log/{args.model}")
