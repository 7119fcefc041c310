"""Host-side synthetic datasets and the sharded batch loader.

TPU-native replacement for the reference's data sublayer:

* :class:`MaterializedDataset` — eagerly materialized ``(input, target)`` pairs,
  capability twin of ``MyTrainDataset`` (reference ``utils.py:4-13``).
* :class:`RandomDataset` — lazy per-index random samples, capability twin of
  ``MyRandomDataset`` (reference ``utils.py:16-26``).
* :class:`ShardedLoader` — batching + shuffling + per-process disjoint sharding,
  replacing ``DataLoader(..., sampler=DistributedSampler(...))`` (reference
  ``multigpu.py:72-79``). Shard semantics mirror ``DistributedSampler``: the
  index list is padded *by wrapping around* so every shard sees the same number
  of samples, and shards are strided (``indices[shard_index::num_shards]``) so
  they are pairwise disjoint before padding. A consumer that hands a batch's
  arrays back gets a later batch stacked into them (:meth:`ShardedLoader.recycle`).

Data stays in numpy on the host; device placement (with sharding) happens in the
Trainer so that the loader is backend-agnostic and cheap to test.
"""

from __future__ import annotations

import math
from typing import Iterator, Sequence, Tuple

import numpy as np

from distributed_pytorch_tpu.obs.tracer import process_tracer

Batch = Tuple[np.ndarray, np.ndarray]


class MaterializedDataset:
    """Eagerly materialized random regression dataset.

    Twin of ``MyTrainDataset`` (reference ``utils.py:4-13``): ``size`` pairs of
    ``(input_dim,)`` inputs and ``(target_dim,)`` targets, generated once at
    construction. Deterministic given ``seed``.
    """

    def __init__(self, size: int, input_dim: int = 20, target_dim: int = 1, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.inputs = rng.standard_normal((size, input_dim)).astype(np.float32)
        self.targets = rng.standard_normal((size, target_dim)).astype(np.float32)

    def __len__(self) -> int:
        return self.inputs.shape[0]

    def __getitem__(self, index: int) -> Batch:
        return self.inputs[index], self.targets[index]


class ArrayDataset:
    """Materialized dataset over caller-provided arrays.

    The general form of :class:`MaterializedDataset` (any shapes/dtypes):
    exposes C-contiguous ``inputs``/``targets``. Used for real data (e.g.
    CIFAR-10) and materialized benchmark workloads.
    """

    def __init__(self, inputs: np.ndarray, targets: np.ndarray):
        if len(inputs) != len(targets):
            raise ValueError(
                f"inputs ({len(inputs)}) and targets ({len(targets)}) disagree"
            )
        self.inputs = np.ascontiguousarray(inputs)
        self.targets = np.ascontiguousarray(targets)

    def __len__(self) -> int:
        return self.inputs.shape[0]

    def __getitem__(self, index: int) -> Batch:
        return self.inputs[index], self.targets[index]


class RandomDataset:
    """Lazy random dataset: every ``__getitem__`` generates its sample on demand.

    Twin of ``MyRandomDataset`` (reference ``utils.py:16-26``). Unlike the
    reference (fresh ``torch.rand`` every access), samples here are
    *deterministic per index* so that loss curves are reproducible and the
    serial-vs-data-parallel parity tests are meaningful.

    ``num_classes`` > 0 yields integer class targets (for classification models
    like ResNet-50); otherwise targets are dense random vectors of
    ``target_shape`` (the reference default ``(1000,)``).
    """

    def __init__(
        self,
        size: int,
        input_shape: Sequence[int],
        target_shape: Sequence[int] = (1000,),
        seed: int = 0,
        num_classes: int = 0,
    ):
        self.size = size
        self.input_shape = tuple(input_shape)
        self.target_shape = tuple(target_shape)
        self.seed = seed
        self.num_classes = num_classes

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, index: int) -> Batch:
        if not 0 <= index < self.size:
            raise IndexError(index)
        rng = np.random.default_rng([self.seed, index])
        x = rng.standard_normal(self.input_shape).astype(np.float32)
        if self.num_classes:
            y = rng.integers(0, self.num_classes, size=(), dtype=np.int32)
            return x, np.asarray(y)
        y = rng.standard_normal(self.target_shape).astype(np.float32)
        return x, y


class ShardedLoader:
    """Batched, optionally shuffled, per-process-sharded iterator over a dataset.

    Replaces the reference's ``DataLoader`` + ``DistributedSampler`` pair
    (``multigpu.py:72-79``) with ``DistributedSampler``-compatible semantics:

    * total indices are padded by wrapping (repeat from the start) up to a
      multiple of ``num_shards`` so all shards are equal length;
    * shard ``i`` takes ``indices[i::num_shards]`` — disjoint before padding;
    * ``set_epoch(e)`` reseeds the shuffle so every epoch (and every shard)
      agrees on one global permutation, mirroring ``sampler.set_epoch``.

    Ragged final batches and XLA: a batch whose leading dim changes forces a
    recompile, and one that is not divisible by the mesh's data axis cannot be
    placed with ``P("data")`` at all. Two remedies:

    * ``drop_last=True`` drops the ragged final batch;
    * ``pad_final_batch=True`` wraps the final batch around to full
      ``batch_size`` (the same pad-by-repeat semantic DistributedSampler
      applies across ranks). The Trainer auto-enables this when running on a
      mesh.

    With the reference's divisible defaults (2048 samples / batch 32) neither
    changes anything.

    **Buffers are recycled only when handed back.** Stacking into a NEW array
    costs ten times the copy itself at image sizes (the pages of a fresh 154 MB
    array are faulted in one by one: PERF.md §6, PR 27). A consumer that is
    done with a batch may give its arrays back with :meth:`recycle`, and a
    later batch of as many rows is stacked into them. A consumer that never
    hands back (``list(loader)``, an eval loop) owns fresh arrays for as long
    as it likes.

    Every batch leaves two slices in ``tracer`` (the process's own unless
    another is handed over): ``loader.index`` round the ``dataset[i]`` calls
    and ``loader.stack`` round the two ``np.stack``, noting ``bytes`` and
    ``recycled``. ``batches_recycled`` / ``batches_allocated`` count the same.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        *,
        shuffle: bool = False,
        num_shards: int = 1,
        shard_index: int = 0,
        seed: int = 0,
        drop_last: bool = False,
        pad_final_batch: bool = False,
        tracer=None,
    ):
        if not 0 <= shard_index < num_shards:
            raise ValueError(f"shard_index {shard_index} not in [0, {num_shards})")
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.num_shards = num_shards
        self.shard_index = shard_index
        self.seed = seed
        self.drop_last = drop_last
        self.pad_final_batch = pad_final_batch
        self.tracer = tracer if tracer is not None else process_tracer()
        self._epoch = 0
        self._free: list = []  # (inputs, targets) handed back, oldest first
        self.batches_recycled = self.batches_allocated = 0

    def recycle(self, xs: np.ndarray, ys: np.ndarray) -> None:
        """Hand back a batch this loader yielded, to be stacked into again:
        from now on the arrays are the loader's to write. Only for the
        consumer the batch was yielded to, once NOTHING it started reads them
        any more. For a copy to a device that is the end of the step that
        consumed the copy, not of the copy: on the CPU backend
        ``jax.device_put`` may alias these arrays for the device array's
        life."""
        self._free.append((xs, ys))

    def _handed_back(self, rows: int):
        """The oldest handed-back pair of ``rows`` rows (a ragged final
        batch's waits for the next ragged one); ``(None, None)``, for
        ``np.stack`` to allocate, where there is none."""
        for n, (xs, ys) in enumerate(self._free):
            if len(xs) == rows:
                del self._free[n]
                return xs, ys
        return None, None

    def set_epoch(self, epoch: int) -> None:
        """Reseed shuffling for ``epoch`` (twin of ``DistributedSampler.set_epoch``);
        forwarded to the dataset when it is epoch-aware (e.g.
        ``AugmentedDataset``: fresh deterministic crops/flips per epoch)."""
        self._epoch = epoch
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def shard_indices(self) -> np.ndarray:
        """The (padded, strided) global indices owned by this shard, this epoch."""
        n = len(self.dataset)
        if self.shuffle:
            order = np.random.default_rng([self.seed, self._epoch]).permutation(n)
        else:
            order = np.arange(n)
        padded_total = math.ceil(n / self.num_shards) * self.num_shards
        if padded_total > n:
            order = np.concatenate([order, order[: padded_total - n]])
        return order[self.shard_index :: self.num_shards]

    def __len__(self) -> int:
        per_shard = math.ceil(len(self.dataset) / self.num_shards)
        if self.drop_last:
            return per_shard // self.batch_size
        return math.ceil(per_shard / self.batch_size)

    def batch_index_table(self) -> "list[np.ndarray]":
        """This epoch's batches as a list of per-batch index rows (the final
        batch is wrap-padded to full size when ``pad_final_batch`` is set,
        otherwise it may be short)."""
        indices = self.shard_indices()
        rows = []
        for b in range(len(self)):
            chunk = indices[b * self.batch_size : (b + 1) * self.batch_size]
            if self.pad_final_batch and len(chunk) < self.batch_size:
                # np.resize repeats cyclically, so this wraps even when the
                # whole shard is smaller than one batch.
                chunk = np.concatenate(
                    [chunk, np.resize(indices, self.batch_size - len(chunk))]
                )
            rows.append(chunk)
        return rows

    def batch_weight_table(self) -> "list[np.ndarray]":
        """Per-batch sample weights (1.0 real / 0.0 wrap-pad duplicate),
        aligned row-for-row with :meth:`batch_index_table`.

        Two padding layers can duplicate samples: shard-level (the global
        index list is wrapped so every shard has equal length — this shard's
        row ``j`` came from padded-order position ``shard_index +
        j * num_shards``, a duplicate iff that position >= dataset size) and
        batch-level (``pad_final_batch`` wraps the final batch to full size).
        Weighting both kinds to zero makes weighted eval sums EXACT
        distinct-sample statistics on any dataset/mesh shape (the training
        path deliberately keeps DistributedSampler's pad-by-repeat mean)."""
        n = len(self.dataset)
        per_shard = math.ceil(n / self.num_shards)
        positions = self.shard_index + np.arange(per_shard) * self.num_shards
        real = (positions < n).astype(np.float32)
        rows = []
        for b in range(len(self)):
            chunk = real[b * self.batch_size : (b + 1) * self.batch_size]
            if self.pad_final_batch and len(chunk) < self.batch_size:
                chunk = np.concatenate(
                    [chunk, np.zeros(self.batch_size - len(chunk), np.float32)]
                )
            rows.append(chunk)
        return rows

    def order_state(self) -> dict:
        """The parameters that determine this epoch's batch order — the
        resumable-iteration contract behind mid-epoch (drain) snapshots. A
        resumed process whose loader :meth:`matches_order_state` will, after
        ``set_epoch(epoch)``, yield the IDENTICAL batch sequence, so
        ``iter_batches(start_batch=k)`` continues exactly where a drained
        run stopped."""
        return {
            "seed": int(self.seed),
            "shuffle": bool(self.shuffle),
            "num_shards": int(self.num_shards),
            "batch_size": int(self.batch_size),
            "dataset_size": int(len(self.dataset)),
        }

    def matches_order_state(self, state) -> bool:
        """True iff a saved :meth:`order_state` describes this loader's batch
        order (same sharding geometry, seed, and dataset) — i.e. a mid-epoch
        ``start_batch`` recorded under that state is still meaningful here.
        False after e.g. an elastic scale-down changed ``num_shards``: the
        caller must replay the epoch from batch 0 instead."""
        return isinstance(state, dict) and state == self.order_state()

    def iter_batches(self, start_batch: int = 0) -> Iterator[Batch]:
        """Iterate this epoch's batches, optionally skipping the first
        ``start_batch`` of them (mid-epoch resume: batches already applied to
        the restored state before a drain snapshot must not be replayed)."""
        tr = self.tracer
        for step, chunk in enumerate(
            self.batch_index_table()[start_batch:], start=start_batch
        ):
            where = dict(step=step, epoch=self._epoch, rows=len(chunk))
            out_x, out_y = self._handed_back(len(chunk))
            with tr.phase("loader.index", **where):
                samples = [self.dataset[int(i)] for i in chunk]
            with tr.phase("loader.stack", **where) as span:
                xs = np.stack([s[0] for s in samples], out=out_x)
                ys = np.stack([s[1] for s in samples], out=out_y)
                span.note(bytes=xs.nbytes + ys.nbytes, recycled=out_x is not None)
            if out_x is None:
                self.batches_allocated += 1
            else:
                self.batches_recycled += 1
            yield xs, ys

    def __iter__(self) -> Iterator[Batch]:
        return self.iter_batches()
