"""Block tables a GROUP of layers (``serving/kv_cache.py`` ``WindowTable`` /
``WindowGroup``, the scheduler's second group) and the windowed K/V call
(``ops/paged_attention.py`` ``paged_window_attention``).

The scheduler's part is host-only: random lengths through a scheduler with
both groups, its plans "executed" by the notes the engine would send, under
pools small enough to preempt. Held after every step: both allocators'
invariants, a sequence's window-group pages never above the bound, and the
short tables the engine would stage naming a held page for every position a
window can still meet. The kernel's part runs it through the Pallas
interpreter against the gather path over the same pools and short tables."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.ops import paged_attention as pa
from distributed_pytorch_tpu.serving.kv_cache import (
    NULL_PAGE,
    PagedBlockAllocator,
    WindowGroup,
    WindowTable,
)
from distributed_pytorch_tpu.serving.scheduler import (
    Request,
    RequestState,
    SamplingParams,
    Scheduler,
)

PAGE = 4


# ------------------------------------------------------------ the table alone


def test_a_window_table_grows_at_its_end_and_gives_back_its_start():
    allocator = PagedBlockAllocator(32)
    table = WindowTable()
    assert table.ensure(10, PAGE, allocator) == 3 and table.first == 0
    assert table.trim(7, PAGE, allocator) == 1  # page 0 is wholly before 7
    assert (table.first, len(table.pages)) == (1, 2)
    assert table.trim(7, PAGE, allocator) == 0
    assert table.ensure(21, PAGE, allocator) == 3  # logical pages 3, 4, 5
    assert table.as_row(4, 1).tolist() == table.pages[:4]
    assert table.as_row(4, 3).tolist() == table.pages[2:] + [NULL_PAGE]
    assert table.as_row(3, 0).tolist() == [NULL_PAGE] + table.pages[:2]
    assert allocator.num_allocated == 5
    assert table.release(allocator) == 5 and table.first == 0
    allocator.assert_quiescent()


@pytest.mark.parametrize("window, chunk, decode, piece", [
    (128, 512, 9, 41), (8, 8, 3, 5), (1, 4, 1, 2), (16, 1, 5, 5),
])
def test_the_bounds_are_the_kernels_own_arithmetic(window, chunk, decode, piece):
    group = WindowGroup(
        PagedBlockAllocator(64), window=window,
        page_size=16 if window == 128 else PAGE, chunk=chunk)
    page = group.page_size
    assert group.decode_pages == decode == pa.window_pages(window, page)
    assert group.piece_pages == piece == pa.window_group_pages(
        window, page, chunk)
    for pos in (0, 3, window - 1, window, 5 * window + 3):
        assert group.first_page(pos) == int(
            pa.window_first_page(np.asarray(pos), window, page))


# ----------------------------------------------- the scheduler's second group


def drive(seed, *, window, chunk, num_pages, window_pages, slots=4,
          requests=14, max_prompt=70):
    """Random requests through a scheduler with both groups until all have
    finished; returns the scheduler and what was seen on the way."""
    rng = np.random.default_rng(seed)
    allocator = PagedBlockAllocator(num_pages)
    group = WindowGroup(
        PagedBlockAllocator(window_pages), window=window, page_size=PAGE,
        chunk=chunk)
    sched = Scheduler(
        allocator, max_slots=slots, page_size=PAGE, pages_per_seq=32,
        token_budget=chunk + slots, max_prefill_chunk=chunk,
        window_group=group, debug=True)
    reqs = []
    for i in range(requests):
        prompt = rng.integers(1, 50, size=int(rng.integers(2, max_prompt)))
        reqs.append(Request(i, prompt.tolist(), SamplingParams(
            max_new_tokens=int(rng.integers(1, 24)))))
        sched.add(reqs[-1])
    seen = {"held": 0, "steps": 0}

    def check_row(req, width, start):
        """The short table the engine would stage for a program whose first
        new token is at ``start``: a held page for every position from the
        window's first key to the table's end."""
        first = group.first_page(start)
        row = req.window_table.as_row(width, first)
        live = [p for p in range(first, first + width)
                if req.window_table.first <= p
                < req.window_table.first + len(req.window_table.pages)]
        assert live and live[0] == first, (first, req.window_table.first)
        assert all(row[p - first] != NULL_PAGE for p in live)
        return row

    while sched.has_work and seen["steps"] < 5000:
        seen["steps"] += 1
        plan = sched.schedule()
        for slot, tokens in plan.prefill:
            req = sched.slots[slot]
            seen["held"] = max(seen["held"], len(req.window_table.pages))
            assert len(req.window_table.pages) <= group.piece_pages
            check_row(req, group.piece_pages, req.len_cached)
            # the piece's own pages are there too
            last = (req.len_cached + tokens - 1) // PAGE
            assert last < req.window_table.first + len(req.window_table.pages)
            sched.note_prefilled(slot, tokens)
        for slot in plan.decode_slots:
            req = sched.slots[slot]
            assert len(req.window_table.pages) <= group.decode_pages
            row = check_row(req, group.decode_pages, req.len_cached)
            assert row[req.len_cached // PAGE
                       - group.first_page(req.len_cached)] != NULL_PAGE
            done = sched.note_decoded(slot, int(rng.integers(1, 50)))
            if done is not None:
                sched.retire(done)
        allocator.check_invariants()
        group.allocator.check_invariants()
        held = sum(len(r.window_table.pages) for r in sched.running)
        assert held == group.allocator.num_allocated
        assert all(not r.window_table.pages and not r.table.pages
                   for r in sched.waiting)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    return sched, group, seen


@pytest.mark.parametrize("seed", range(6))
def test_random_lengths_keep_both_groups_whole(seed):
    """Roomy pools: nobody is preempted, both allocators end quiescent, and
    the pages behind every window came back as the sequences ran."""
    sched, group, seen = drive(
        seed, window=8, chunk=8, num_pages=200, window_pages=4 * 5 + 1)
    assert sched.preemptions == 0
    sched.allocator.assert_quiescent()
    group.allocator.assert_quiescent()
    assert group.pages_freed > 0
    assert seen["held"] <= group.pages_held_peak <= group.piece_pages


@pytest.mark.parametrize("seed, num_pages, window_pages", [
    (0, 40, 21), (1, 40, 21), (2, 200, 9), (3, 200, 9), (4, 36, 9),
])
def test_a_shortage_in_either_group_preempts_and_both_are_given_back(
        seed, num_pages, window_pages):
    """A full group or a window group too small for four sequences at once:
    the lowest priority yields by the one rule, gives back its pages in BOTH
    groups, is prefilled again from position 0, and everything finishes."""
    sched, group, _ = drive(
        seed, window=8, chunk=8, num_pages=num_pages,
        window_pages=window_pages, max_prompt=60)
    assert sched.preemptions > 0
    sched.allocator.assert_quiescent()
    group.allocator.assert_quiescent()


@pytest.mark.parametrize("window, chunk", [(5, 4), (8, 16), (13, 8), (1, 8)])
def test_pieces_that_cross_page_and_window_boundaries(window, chunk):
    """Windows that are no whole pages, pieces wider and narrower than a
    window: the bound holds and the short tables stay whole."""
    _, group, seen = drive(
        7, window=window, chunk=chunk, num_pages=200,
        window_pages=4 * (1 + -(-(window + chunk - 2) // PAGE)) + 1)
    assert seen["held"] <= group.piece_pages
    group.allocator.assert_quiescent()


def test_a_window_group_is_planned_one_piece_a_step():
    sched = Scheduler(
        PagedBlockAllocator(100), max_slots=2, page_size=PAGE,
        pages_per_seq=32, token_budget=64, max_prefill_chunk=8,
        window_group=WindowGroup(
            PagedBlockAllocator(20), window=8, page_size=PAGE, chunk=8))
    sched.add(Request(0, list(range(1, 41)), SamplingParams(max_new_tokens=2)))
    plan = sched.schedule()
    assert plan.prefill == [(0, 8)]  # the budget would hold five
    plain = Scheduler(
        PagedBlockAllocator(100), max_slots=2, page_size=PAGE,
        pages_per_seq=32, token_budget=64, max_prefill_chunk=8)
    plain.add(Request(0, list(range(1, 41)), SamplingParams(max_new_tokens=2)))
    assert len(plain.schedule().prefill) == 5


def test_a_window_group_takes_neither_a_trie_nor_speculative_rounds():
    from distributed_pytorch_tpu.serving.kv_cache import PrefixCache

    allocator = PagedBlockAllocator(20)
    group = WindowGroup(
        PagedBlockAllocator(20), window=8, page_size=PAGE, chunk=8)
    common = dict(max_slots=2, page_size=PAGE, pages_per_seq=8,
                  max_prefill_chunk=8, window_group=group)
    with pytest.raises(ValueError, match="window group"):
        Scheduler(allocator, prefix_cache=PrefixCache(allocator, PAGE), **common)
    with pytest.raises(ValueError, match="window group"):
        Scheduler(allocator, gamma=2, **common)


# ------------------------------------------------------- the windowed K/V call


def pools_and_tables(positions, window, *, heads=8, kv_heads=2, d=16,
                     seed=0, absent=()):
    """Random pools, and for rows at ``positions`` the short tables a window
    group would stage: distinct physical pages, the null page past a row's
    last live one and everywhere in an ``absent`` row."""
    rng = np.random.default_rng(seed)
    width = pa.window_pages(window, PAGE)
    rows = len(positions)
    num_pages = rows * width + 1
    k_pool = jnp.asarray(rng.standard_normal((num_pages, PAGE, kv_heads, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((num_pages, PAGE, kv_heads, d)),
                         jnp.float32)
    q = jnp.asarray(rng.standard_normal((rows, 1, heads, d)), jnp.float32)
    tables = np.zeros((rows, width), np.int32)
    pages = rng.permutation(np.arange(1, num_pages))
    for r, pos in enumerate(positions):
        if r in absent:
            continue
        first = max(pos - (window - 1), 0) // PAGE
        live = pos // PAGE - first + 1
        tables[r, :live] = pages[r * width: r * width + live]
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(
        np.asarray(positions, np.int32))


@pytest.mark.parametrize("heads, kv_heads", [(8, 2), (6, 3)])
@pytest.mark.parametrize("window", [8, 6, 13])
def test_the_interpreted_kernel_is_the_gather_path(window, heads, kv_heads):
    """Positions under, at and past the window; a window whose first key
    stands last in its page (``pos - window + 1 = 3 mod 4``) and first in it,
    inside its block and at its start (``pos - window + 1`` a multiple of 4);
    the table's every width in use; two blocks a row (the CPU's block is 2
    pages) with the lower bound in the first."""
    positions = [0, 2, window - 2, window - 1, window, window + 2,
                 window + 3 - (window % 4), 4 * window + 1, 4 * window + 2,
                 61]
    args = pools_and_tables(positions, window, heads=heads, kv_heads=kv_heads)
    want = pa.paged_window_attention(*args, window=window, kernel="xla")
    got = pa.paged_window_attention(*args, window=window, kernel="interpret")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    # ... and the window binds: without the lower bound the rows past the
    # window read other keys.
    unbound = pa.paged_window_attention(
        *args[:4], args[4], window=10**6, kernel="xla")
    past = [i for i, pos in enumerate(positions) if pos >= window]
    under = [i for i, pos in enumerate(positions) if pos < window]
    assert np.abs(np.asarray(unbound - want)[under]).max() < 2e-6
    assert np.abs(np.asarray(unbound - want)[past]).max() > 1e-3


def test_rows_out_of_the_dispatch_read_nothing():
    """A row whose short table starts at the null page does nothing in the
    kernel and gives zeros; its neighbours are served as if it were not
    there (a row's last block starts the next LIVE row's first)."""
    positions = [9, 30, 17, 44, 12]
    args = pools_and_tables(positions, 8, absent=(1, 3))
    got = np.asarray(
        pa.paged_window_attention(*args, window=8, kernel="interpret"))
    want = np.asarray(pa.paged_window_attention(*args, window=8, kernel="xla"))
    assert np.abs(got[[1, 3]]).max() == 0.0
    assert np.abs(got - want)[[0, 2, 4]].max() < 2e-6


def test_the_kernels_block_is_the_short_tables_power_of_two():
    pool = jnp.zeros((5, 16, 8, 128), jnp.bfloat16)
    # v5e's block of 16 pages holds a row's 9: ONE block; the plain lookup
    # would walk a block of 8 and one of 1.
    assert pa.kv_block_pages(
        9, pool, jnp.bfloat16, pages_per_block=16, short=True) == 9
    assert pa.kv_block_pages(9, pool, jnp.bfloat16, pages_per_block=16) == 9
    assert pa.kv_block_pages(9, pool, jnp.bfloat16, short=True) <= 9
    assert pa.kv_block_pages(128, pool, jnp.bfloat16, pages_per_block=16) == 16
    assert pa.window_group_pages(128, 16) == 9
    assert pa.window_group_pages(128, 16, 512) == 41
