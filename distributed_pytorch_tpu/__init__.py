"""distributed_pytorch_tpu — a TPU-native (JAX/XLA/pjit) distributed training framework.

Re-implements, TPU-first, the full capability surface of the reference
``subramen/distributed-pytorch`` tutorial ladder (see SURVEY.md):

1. A reusable :class:`Trainer` (epoch loop -> batch loop -> fused jitted train step).
2. Data-parallel gradient synchronization — XLA-inserted all-reduce over a named
   device mesh replaces DDP/NCCL (reference: ``multigpu.py:36,42``).
3. Per-replica disjoint input sharding — :class:`ShardedLoader` replaces
   ``DistributedSampler`` (reference: ``multigpu.py:72-79``).
4. Process bootstrap + rendezvous, explicit and env-driven —
   :func:`setup_distributed` replaces ``init_process_group`` / torchrun env vars
   (reference: ``multigpu.py:12-20``, ``multigpu_torchrun.py:12-13``).
5. Checkpointing and snapshot-based elastic resume (reference:
   ``multigpu_torchrun.py:30-40,57-62``), extended to include optimizer state.
6. Multi-host pod launch (reference: ``slurm/sbatch_run.sh``) via
   ``launch/tpu_pod_run.sh``.
7. Step-level profiling with TensorBoard trace export (reference:
   ``multigpu_profile.py:80-91``) via :class:`StepProfiler`.
8. Toy synthetic datasets and a real-model (ResNet-50 / ViT) swap-in path.

The design stance is SPMD-first: one pure jitted ``train_step`` over a
``jax.sharding.Mesh``; the compiler owns communication (ICI/DCN collectives),
there is no user-space NCCL analog.
"""

from distributed_pytorch_tpu.checkpoint import (
    AsyncCheckpointer,
    export_orbax,
    import_orbax,
    load_checkpoint,
    load_snapshot,
    save_checkpoint,
    save_snapshot,
)
from distributed_pytorch_tpu.generation import (
    beam_search,
    generate,
    top_p_filter,
)
from distributed_pytorch_tpu.speculative import speculative_generate
from distributed_pytorch_tpu.parallel.bootstrap import (
    is_main_process,
    setup_distributed,
    shutdown_distributed,
)
from distributed_pytorch_tpu.parallel.mesh import make_mesh
from distributed_pytorch_tpu.profiling import StepProfiler
from distributed_pytorch_tpu.training.losses import (
    mse_loss,
    smoothed_cross_entropy_loss,
    softmax_cross_entropy_loss,
)
from distributed_pytorch_tpu.training.lora import LoraModel, merge_lora
from distributed_pytorch_tpu.training.train_step import TrainState, make_train_step
from distributed_pytorch_tpu.training.trainer import Trainer
from distributed_pytorch_tpu.utils.data import (
    ArrayDataset,
    MaterializedDataset,
    RandomDataset,
    ShardedLoader,
)

__version__ = "0.4.0"

__all__ = [
    "AsyncCheckpointer",
    "ArrayDataset",
    "MaterializedDataset",
    "beam_search",
    "generate",
    "speculative_generate",
    "top_p_filter",
    "RandomDataset",
    "ShardedLoader",
    "StepProfiler",
    "TrainState",
    "Trainer",
    "LoraModel",
    "merge_lora",
    "export_orbax",
    "import_orbax",
    "is_main_process",
    "load_checkpoint",
    "load_snapshot",
    "make_mesh",
    "make_train_step",
    "mse_loss",
    "save_checkpoint",
    "save_snapshot",
    "setup_distributed",
    "shutdown_distributed",
    "smoothed_cross_entropy_loss",
    "softmax_cross_entropy_loss",
]
