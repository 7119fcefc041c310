from distributed_pytorch_tpu.utils.data import (
    ArrayDataset,
    MaterializedDataset,
    RandomDataset,
    ShardedLoader,
)
from distributed_pytorch_tpu.utils.datasets import (
    AugmentedDataset,
    cifar10_or_synthetic,
    load_cifar10,
    normalize_images,
    synthetic_cifar10,
    synthetic_oracle_accuracy,
)
from distributed_pytorch_tpu.utils.platform import use_fake_cpu_devices

__all__ = [
    "ArrayDataset",
    "AugmentedDataset",
    "MaterializedDataset",
    "RandomDataset",
    "ShardedLoader",
    "cifar10_or_synthetic",
    "load_cifar10",
    "normalize_images",
    "synthetic_cifar10",
    "synthetic_oracle_accuracy",
    "use_fake_cpu_devices",
]
