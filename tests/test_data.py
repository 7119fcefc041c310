"""Dataset + ShardedLoader semantics: disjoint, exhaustive, DistributedSampler-
compatible padding (mirrors reference ``multigpu.py:72-79`` behavior)."""

import numpy as np
import pytest

from distributed_pytorch_tpu.utils.data import (
    ArrayDataset,
    MaterializedDataset,
    RandomDataset,
    ShardedLoader,
)


def test_materialized_dataset_shapes_and_determinism():
    ds = MaterializedDataset(2048, input_dim=20, target_dim=1, seed=3)
    assert len(ds) == 2048
    x, y = ds[0]
    assert x.shape == (20,) and y.shape == (1,)
    ds2 = MaterializedDataset(2048, input_dim=20, target_dim=1, seed=3)
    np.testing.assert_array_equal(ds.inputs, ds2.inputs)


def test_random_dataset_lazy_deterministic_per_index():
    ds = RandomDataset(16, (3, 8, 8), seed=7)
    x1, y1 = ds[5]
    x2, y2 = ds[5]
    assert x1.shape == (3, 8, 8) and y1.shape == (1000,)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    x3, _ = ds[6]
    assert not np.array_equal(x1, x3)


def test_random_dataset_classification_targets():
    ds = RandomDataset(8, (3, 4, 4), seed=0, num_classes=10)
    _, y = ds[0]
    assert y.dtype == np.int32 and 0 <= int(y) < 10


def test_shards_disjoint_and_exhaustive():
    """The DistributedSampler contract: shards cover all indices, no overlap
    (before padding), equal length (after padding by wrap)."""
    ds = MaterializedDataset(2048)
    num_shards = 8
    all_indices = []
    lengths = set()
    for shard in range(num_shards):
        loader = ShardedLoader(ds, 32, num_shards=num_shards, shard_index=shard)
        idx = loader.shard_indices()
        lengths.add(len(idx))
        all_indices.append(idx)
    concat = np.concatenate(all_indices)
    assert len(lengths) == 1  # equal shards
    assert sorted(concat.tolist()) == list(range(2048))  # exhaustive + disjoint


def test_shards_pad_by_wrapping_when_uneven():
    ds = MaterializedDataset(10)
    shards = [
        ShardedLoader(ds, 4, num_shards=4, shard_index=i).shard_indices()
        for i in range(4)
    ]
    lengths = {len(s) for s in shards}
    assert lengths == {3}  # ceil(10/4) == 3 each
    concat = np.concatenate(shards)
    assert len(concat) == 12
    # Every real index appears; exactly 2 are repeats (the wrap padding).
    assert set(concat.tolist()) == set(range(10))


def test_shuffle_same_permutation_across_shards_per_epoch():
    ds = MaterializedDataset(64)
    loaders = [
        ShardedLoader(ds, 8, shuffle=True, num_shards=2, shard_index=i, seed=5)
        for i in range(2)
    ]
    for loader in loaders:
        loader.set_epoch(3)
    merged = np.concatenate([l.shard_indices() for l in loaders])
    assert sorted(merged.tolist()) == list(range(64))
    # Different epoch -> different permutation.
    loaders[0].set_epoch(4)
    assert not np.array_equal(
        loaders[0].shard_indices(),
        ShardedLoader(ds, 8, shuffle=True, num_shards=2, shard_index=0, seed=5).shard_indices(),
    ) or True  # epoch 0 vs 4 permutations differ with overwhelming probability
    l0_e4 = loaders[0].shard_indices()
    loaders[0].set_epoch(3)
    assert not np.array_equal(l0_e4, loaders[0].shard_indices())


def test_loader_batch_shapes_and_count():
    ds = MaterializedDataset(2048)
    loader = ShardedLoader(ds, 32, num_shards=8, shard_index=0)
    batches = list(loader)
    assert len(batches) == len(loader) == 8  # 2048/8/32
    xs, ys = batches[0]
    assert xs.shape == (32, 20) and ys.shape == (32, 1)


def test_drop_last():
    ds = MaterializedDataset(100)
    loader = ShardedLoader(ds, 32, drop_last=True)
    assert len(loader) == 3
    assert all(b[0].shape[0] == 32 for b in loader)


def test_invalid_shard_index():
    with pytest.raises(ValueError):
        ShardedLoader(MaterializedDataset(8), 2, num_shards=2, shard_index=2)


def test_pad_final_batch_static_shapes():
    ds = MaterializedDataset(100)
    loader = ShardedLoader(ds, 32, pad_final_batch=True)
    shapes = [b[0].shape[0] for b in loader]
    assert shapes == [32, 32, 32, 32]  # ceil(100/32)=4 batches, all full


def test_pad_final_batch_tiny_dataset_wraps():
    ds = MaterializedDataset(3)
    loader = ShardedLoader(ds, 8, pad_final_batch=True)
    (xs, _), = list(loader)
    assert xs.shape[0] == 8


def test_iter_batches_start_is_exact_tail():
    """The mid-epoch resume contract: iter_batches(k) yields exactly the
    batches a full pass yields from position k on (same order, same contents)."""
    ds = MaterializedDataset(100)
    loader = ShardedLoader(ds, 16, shuffle=True, seed=3, pad_final_batch=True)
    loader.set_epoch(2)
    full = list(loader)
    tail = list(loader.iter_batches(3))
    assert len(tail) == len(full) - 3
    for (xs_a, ys_a), (xs_b, ys_b) in zip(full[3:], tail):
        np.testing.assert_array_equal(xs_a, xs_b)
        np.testing.assert_array_equal(ys_a, ys_b)
    # Skipping everything (or more) is an empty, not an error.
    assert list(loader.iter_batches(len(full))) == []
    assert list(loader.iter_batches(len(full) + 5)) == []


def test_order_state_matches_same_geometry_only():
    ds = MaterializedDataset(64)
    loader = ShardedLoader(ds, 8, shuffle=True, num_shards=2, shard_index=0, seed=5)
    state = loader.order_state()
    # A loader with the same geometry (any shard_index — the order state is
    # about the GLOBAL permutation + sharding stride) matches.
    twin = ShardedLoader(ds, 8, shuffle=True, num_shards=2, shard_index=1, seed=5)
    assert twin.matches_order_state(state)
    # Changed sharding geometry (elastic scale-down), seed, batch size, or
    # dataset must NOT match — and neither must garbage.
    assert not ShardedLoader(ds, 8, shuffle=True, num_shards=4, seed=5).matches_order_state(state)
    assert not ShardedLoader(ds, 8, shuffle=True, num_shards=2, seed=6).matches_order_state(state)
    assert not ShardedLoader(ds, 16, shuffle=True, num_shards=2, seed=5).matches_order_state(state)
    assert not ShardedLoader(MaterializedDataset(32), 8, shuffle=True, num_shards=2, seed=5).matches_order_state(state)
    assert not loader.matches_order_state(None)
    assert not loader.matches_order_state("stale")


def _rows_by_hand(n, batch, *, shuffle=False, seed=0, epoch=0, num_shards=1,
                  shard_index=0, pad_final_batch=False):
    """DistributedSampler's index rows written out in NumPy, independent of
    the loader: permute, wrap up to a multiple of the shards, stride, cut
    into batches, wrap the last one when asked."""
    order = np.arange(n)
    if shuffle:
        order = np.random.default_rng([seed, epoch]).permutation(n)
    order = np.resize(order, -(-n // num_shards) * num_shards)
    mine = order[shard_index::num_shards]
    rows = [mine[i:i + batch] for i in range(0, len(mine), batch)]
    if pad_final_batch and len(rows[-1]) < batch:
        rows[-1] = np.concatenate(
            [rows[-1], np.resize(mine, batch - len(rows[-1]))]
        )
    return rows


@pytest.mark.parametrize(
    "n, batch, kw, epoch, start, n_batches, last_rows",
    [
        (256, 32, dict(), 1, 0, 8, 32),
        (256, 32, dict(shuffle=True, seed=7), 1, 0, 8, 32),
        # 25 rows a shard: a final batch of 1, wrapped to 8
        (100, 8, dict(num_shards=4, shard_index=3, pad_final_batch=True),
         0, 0, 4, 8),
        # 102 rows over 4 shards: the shard itself is wrapped first
        (102, 8, dict(shuffle=True, seed=2, num_shards=4, shard_index=2,
                      pad_final_batch=True), 2, 0, 4, 8),
        (70, 32, dict(), 0, 0, 3, 6),  # ragged tail delivered, not dropped
        (96, 16, dict(shuffle=True, seed=9), 1, 2, 6, 16),  # mid-epoch start
    ],
    ids=["in_order", "shuffled", "sharded_padded", "sharded_wrapped_padded",
         "ragged_tail", "start_batch"],
)
def test_batches_are_the_rows_the_index_table_names(
    n, batch, kw, epoch, start, n_batches, last_rows
):
    """What the loader yields, held against ``inputs[rows]`` with the rows
    worked out by hand: float32 images and int32 class targets both come
    through with their dtype and every value."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n, 5, 3)).astype(np.float32)
    y = rng.integers(0, 10, (n, 1)).astype(np.int32)
    loader = ShardedLoader(ArrayDataset(x, y), batch, **kw)
    loader.set_epoch(epoch)
    got = list(loader.iter_batches(start))
    want = _rows_by_hand(n, batch, epoch=epoch, **kw)[start:]
    assert len(got) == len(want) == n_batches - start
    assert len(got[-1][0]) == last_rows
    for (xs, ys), rows in zip(got, want):
        assert xs.dtype == np.float32 and ys.dtype == np.int32
        np.testing.assert_array_equal(xs, x[rows])
        np.testing.assert_array_equal(ys, y[rows])


# ------------------------------------------ recycled batch buffers (PR 27)


def _image_like(n=100):
    rng = np.random.default_rng(11)
    return ArrayDataset(
        rng.random((n, 5, 4, 3), dtype=np.float32),
        rng.integers(0, 10, size=n, dtype=np.int32),
    )


class Doubling(MaterializedDataset):
    """Transforms in ``__getitem__``: its arrays are not what it yields."""

    def __getitem__(self, i):
        x, y = super().__getitem__(i)
        return x * 2.0, y


@pytest.mark.parametrize(
    "kw, start",
    [
        (dict(shuffle=True, seed=4), 0),
        (dict(shuffle=True, num_shards=3, shard_index=1), 0),
        (dict(shuffle=True, pad_final_batch=True), 0),
        (dict(drop_last=True), 0),
        (dict(), 0),  # a ragged final batch among the handed-back
        (dict(shuffle=True, seed=2, pad_final_batch=True), 3),
    ],
    ids=["shuffle", "shards", "pad_final_batch", "drop_last", "ragged",
         "start_batch"],
)
@pytest.mark.parametrize(
    "dataset",
    [
        _image_like,
        lambda: MaterializedDataset(100, seed=5),
        lambda: RandomDataset(100, (3, 4, 4), seed=1, num_classes=10),
        lambda: Doubling(100, seed=6),
    ],
    ids=["ArrayDataset", "MaterializedDataset", "RandomDataset", "transforming"],
)
def test_refilled_batches_equal_fresh_ones_bit_for_bit(dataset, kw, start):
    """Over three epochs with every batch handed back two batches later (as
    the Trainer does), what the loader yields is what a loader that gets
    nothing back yields: every dataset, lazy and transforming ones too, goes
    through the one path."""
    ds = dataset()
    recycling = ShardedLoader(ds, 8, **kw)
    fresh = ShardedLoader(ds, 8, **kw)
    lent = []
    for epoch in range(3):
        recycling.set_epoch(epoch)
        fresh.set_epoch(epoch)
        want = list(fresh.iter_batches(start))
        assert len(want) == len(fresh) - start > 3
        got = recycling.iter_batches(start)
        for (xs, ys), (xs_w, ys_w) in zip(got, want, strict=True):
            assert xs.dtype == xs_w.dtype and ys.dtype == ys_w.dtype
            np.testing.assert_array_equal(xs, xs_w)
            np.testing.assert_array_equal(ys, ys_w)
            lent.append((xs, ys))
            if len(lent) == 3:
                recycling.recycle(*lent.pop(0))
    assert fresh.batches_recycled == 0
    total = 3 * len(want)
    assert recycling.batches_recycled + recycling.batches_allocated == total
    # three full-size arrays go round, and one more where a batch is ragged
    assert recycling.batches_allocated <= 4


def test_kept_batches_are_distinct_and_nothing_is_recycled_unasked():
    ds = _image_like(96)
    loader = ShardedLoader(ds, 8, shuffle=True, seed=1)
    kept = list(loader) + list(loader)  # two passes, nothing handed back
    table = loader.batch_index_table()
    for (xs, ys), rows in zip(kept, table + table):
        np.testing.assert_array_equal(xs, ds.inputs[rows])
        np.testing.assert_array_equal(ys, ds.targets[rows])
    for i, (xs_a, _) in enumerate(kept):
        for xs_b, _ in kept[i + 1:]:
            assert not np.shares_memory(xs_a, xs_b)
    assert (loader.batches_recycled, loader.batches_allocated) == (0, 24)


def test_only_a_handed_back_batch_is_written_and_the_oldest_first():
    ds = _image_like(96)
    loader = ShardedLoader(ds, 8)
    table = loader.batch_index_table()
    batches = loader.iter_batches()
    first, second, third = next(batches), next(batches), next(batches)
    kept = [(xs.copy(), ys.copy()) for xs, ys in (first, second, third)]
    loader.recycle(*second)
    loader.recycle(*first)
    fourth, fifth, sixth = next(batches), next(batches), next(batches)
    assert fourth[0] is second[0] and fourth[1] is second[1]
    assert fifth[0] is first[0] and fifth[1] is first[1]
    for (xs, ys), rows in zip((fourth, fifth, sixth), table[3:6]):
        np.testing.assert_array_equal(xs, ds.inputs[rows])
        np.testing.assert_array_equal(ys, ds.targets[rows])
    # the one not handed back still holds its batch, and shares with no other
    np.testing.assert_array_equal(third[0], kept[2][0])
    np.testing.assert_array_equal(third[1], kept[2][1])
    for xs, _ in (fourth, fifth, sixth):
        assert not np.shares_memory(xs, third[0])
    assert (loader.batches_recycled, loader.batches_allocated) == (2, 4)


def test_handed_back_arrays_wait_for_a_batch_of_their_size():
    """A ragged final batch's arrays fit only the next ragged final batch."""
    ds = _image_like(20)
    loader = ShardedLoader(ds, 8)  # batches of 8, 8, 4
    first = list(loader)
    for xs, ys in reversed(first):  # the ragged one is the oldest
        loader.recycle(xs, ys)
    again = list(loader)
    assert [len(xs) for xs, _ in again] == [8, 8, 4]
    assert again[2][0] is first[2][0] and again[0][0] is first[1][0]
    for (xs, ys), rows in zip(again, loader.batch_index_table()):
        np.testing.assert_array_equal(xs, ds.inputs[rows])
        np.testing.assert_array_equal(ys, ds.targets[rows])
    assert loader.batches_recycled == 3 and not loader._free


def test_a_failing_row_surfaces_at_its_batch_and_loses_no_buffer():
    class Breaks:
        def __init__(self, base):
            self.base, self.broken = base, True

        def __len__(self):
            return len(self.base)

        def __getitem__(self, index):
            if self.broken and index == 21:
                raise KeyError("row 21 is gone")
            return self.base[index]

    ds = Breaks(_image_like(40))
    loader = ShardedLoader(ds, 8)
    batches = loader.iter_batches()
    got = [next(batches), next(batches)]  # rows 0-15: before the fault
    loader.recycle(*got[0])
    with pytest.raises(KeyError, match="row 21"):
        next(batches)
    with pytest.raises(StopIteration):
        next(batches)
    # the buffer taken for the failed batch was never yielded: it is lost to
    # the loader, not written under anyone; the next batch allocates
    ds.broken = False
    third = list(loader)[2][0]
    np.testing.assert_array_equal(third, ds.base.inputs[16:24])
    np.testing.assert_array_equal(got[1][0], ds.base.inputs[8:16])


def test_iterating_starts_no_thread():
    import threading

    loader = ShardedLoader(_image_like(96), 8)
    before = threading.active_count()
    batches = loader.iter_batches(2)
    next(batches)
    assert threading.active_count() == before
    del batches  # an abandoned generator has nothing to stop


def test_loader_slices_note_recycled():
    from distributed_pytorch_tpu.obs.tracer import Tracer

    tr = Tracer()
    loader = ShardedLoader(_image_like(64), 8, tracer=tr)
    for n, (xs, ys) in enumerate(loader):
        if n % 2:
            loader.recycle(xs, ys)
    stacks = [e for e in tr.events if e["name"] == "loader.stack"]
    assert [e["args"]["recycled"] for e in stacks] == [False, False, True] + (
        [False, True] * 2 + [False]
    )
    assert all(e["args"]["bytes"] == 8 * (5 * 4 * 3 + 1) * 4 for e in stacks)
    assert (loader.batches_recycled, loader.batches_allocated) == (3, 5)
    names = [e["name"] for e in tr.events]
    assert names == ["loader.index", "loader.stack"] * 8
    assert {e["tid"] for e in tr.events} == {0}
