"""Paged KV-cache management: host-side page accounting for the device pool.

The device holds ONE global cache per attention layer, laid out
``[num_pages, page_size, Hkv, D]`` (see ``models/transformer.py``'s paged
decode mode). This module owns the host half: a refcounted allocator over
physical page ids, a per-sequence :class:`BlockTable` mapping logical pages
to physical ones, and a :class:`PrefixCache` hash-trie that maps
page-aligned token prefixes to already-computed pages so shared prompts are
prefilled once. Invariants that keep sharing copy-free and leak-proof:

* **Page 0 is the NULL page** — never allocated. Inactive decode slots and
  padded block-table entries all point at it; the attention visibility mask
  guarantees nothing read from it survives the softmax, so retired pages
  need no zeroing before reuse (stale K/V beyond a row's ``seq_len`` is
  masked exactly like stale cache beyond ``cache_index`` in offline decode).
* **Every page is in exactly one of three states**: *free* (content
  meaningless), *referenced* (refcount >= 1 readers hold it in a block
  table), or *cached-idle* (refcount 0 but registered in the prefix trie;
  content is valid K/V, kept on an LRU and evicted only under allocation
  pressure). A double-unref or a leak is an immediate ``AssertionError`` in
  :meth:`PagedBlockAllocator.check_invariants`, not a silent cross-request
  cache corruption. The scheduler property tests drive randomized
  submit/finish/preempt/evict cycles against this.
* **Writers own their write page exclusively.** A shared page (refcount
  > 1) is never written in place: the scheduler copies it first
  (copy-on-write) so concurrent extenders of a cached partial page cannot
  clobber each other's tokens. Pages with refcount 1 may be extended in
  place even while registered — appending beyond a registered prefix never
  changes the prefix content a future matcher reads.
* **Draft pages move in lockstep with target pages** (speculative
  decoding): the draft model's pool is built with the SAME
  ``(num_pages, page_size)`` geometry, so one physical page id names the
  same logical token span in BOTH pools (:class:`PagePoolGroup`). One
  allocator and one block table per sequence then govern both pools at
  once — allocate/ref/unref/retire/evict are decided once on the shared
  id — and rejected-token rollback is O(1) in both pools for the same
  reason retire is copy-free: reads past ``seq_len`` are masked, so stale
  speculative K/V is dead by construction.
* **A model's window layers are a GROUP of their own** (:class:`WindowGroup`):
  pools of their own size, a second allocator over their own id space and a
  :class:`WindowTable` a sequence, beside the table, allocator and trie above,
  which the full layers keep unchanged. A window table gives back the pages
  its window has left behind as the sequence advances, so it is short (a
  decode row's ``window_pages``) where the full table holds the whole
  context; the trie never sees its pages (the engine refuses a prefix cache
  beside such a group), and each allocator's invariants and quiescence are
  held for its own group.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

NULL_PAGE = 0


def chain_next(prev: str, chunk: Sequence[int]) -> str:
    """One link of the content-addressed page chain: the key of a full
    page holding ``chunk`` whose predecessor page hashed to ``prev``
    (``"root"`` for the first page). Hash-chained, so each key commits to
    the ENTIRE page-aligned prefix — the identity shared by
    :meth:`PrefixCache.key_chain`, the elastic snapshot's ``trie_keys``,
    and the host page tier (``serving/hostkv.py``), which is what makes a
    page nameable across tiers and across processes."""
    return hashlib.sha256(
        (prev + "|" + ",".join(map(str, chunk))).encode()
    ).hexdigest()[:16]


class OutOfPages(RuntimeError):
    """Raised when an allocation cannot be satisfied even after evicting
    every cached-idle page — the scheduler's cue to preempt the
    lowest-priority running sequence."""


class PagedBlockAllocator:
    """Refcounted allocator over physical page ids ``1..num_pages-1``.

    The free list is LIFO: reuse stays hot (the page most recently retired
    is reassigned first) and, with the deterministic initial ordering, the
    whole engine is reproducible on CPU: identical submit/finish order
    yields identical physical page assignments.

    Refcounts support prefix sharing: :meth:`ref` adds a reader to a page
    another sequence already holds, :meth:`unref` drops one. When the count
    reaches zero the page either returns to the free list or — if the
    prefix cache registered it via :meth:`mark_cached` — parks on the
    cached-idle LRU, where its contents stay valid until allocation
    pressure evicts it (``evict_hook`` tells the trie to forget it first).
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(
                f"need >= 2 pages (page {NULL_PAGE} is reserved), got {num_pages}"
            )
        self.num_pages = num_pages
        # pop() takes from the end: seed the stack so pages come out
        # 1, 2, 3, ... on a fresh allocator.
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        # Cached-but-unreferenced pages, oldest first (LRU eviction order).
        self._idle: "OrderedDict[int, None]" = OrderedDict()
        # Pages registered in the prefix trie (referenced or idle).
        self._cached: set = set()
        # Names of the device pools this id space governs — one pool for a
        # plain engine, ("target", "draft") under speculative decoding (the
        # engine overwrites this from its PagePoolGroup). Page-leak
        # diagnostics name them: one leaked id pins K/V in EVERY pool.
        self.pool_names: Tuple[str, ...] = ("target",)
        # Called with the page id just before an idle page is recycled, so
        # the prefix trie can drop the nodes that point at it.
        self.evict_hook: Optional[Callable[[int], None]] = None
        self.evictions = 0
        # Copy-on-write page splits, counted here (the scheduler decides
        # them, but the allocator is the page ledger of record) so the
        # metrics registry reads every page-lifecycle counter off one
        # object. note_cow() increments it.
        self.cow_copies = 0
        # O(1) running state counts, maintained at every page transition
        # and cross-checked against the full sweep in check_invariants() —
        # the gauges the engine exports every step without debug=True.
        self._n_free = num_pages - 1
        self._n_referenced = 0
        self._n_idle = 0
        # Optional tracer / flight recorder (duck-typed; NULL by default)
        # so page evictions surface on the engine timeline and in
        # postmortem dumps.
        from distributed_pytorch_tpu.obs.flight import NULL_FLIGHT_RECORDER
        from distributed_pytorch_tpu.obs.tracer import NULL_TRACER

        self.tracer = NULL_TRACER
        self.flight = NULL_FLIGHT_RECORDER

    @property
    def num_free(self) -> int:
        """Pages allocatable right now (free list + evictable idle)."""
        return self._n_free + self._n_idle

    @property
    def num_allocated(self) -> int:
        """Pages with at least one reader."""
        return self._n_referenced

    @property
    def num_idle(self) -> int:
        """Cached pages with no readers (evictable under pressure)."""
        return self._n_idle

    def counters(self) -> Dict[str, int]:
        """O(1) gauge/counter snapshot — page-state populations (strict
        free list vs cached-idle, unlike :attr:`num_free` which pools
        them), plus the lifetime CoW-split and eviction counters."""
        return {
            "pages_free": self._n_free,
            "pages_referenced": self._n_referenced,
            "pages_cached_idle": self._n_idle,
            "cow_copies": self.cow_copies,
            "page_evictions": self.evictions,
        }

    def note_cow(self) -> None:
        """The scheduler split a shared page copy-on-write."""
        self.cow_copies += 1

    @staticmethod
    def pages_needed(n_tokens: int, page_size: int) -> int:
        return -(-n_tokens // page_size) if n_tokens > 0 else 0

    def _evict_one(self) -> None:
        page, _ = self._idle.popitem(last=False)  # oldest first
        self._cached.discard(page)
        self.evictions += 1
        self._n_idle -= 1
        if self.evict_hook is not None:
            self.evict_hook(page)
        self.tracer.instant("page_evict", page=page)
        self.flight.record("page_evict", page=page)
        self._free.append(page)
        self._n_free += 1

    def allocate(self, n: int = 1) -> List[int]:
        """Take ``n`` fresh pages (refcount 1 each) or raise
        :class:`OutOfPages` taking NONE — partial grabs would leak on the
        error path. Cached-idle pages are evicted LRU-first to satisfy the
        request when the free list runs dry."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > self.num_free:
            raise OutOfPages(
                f"need {n} pages, {len(self._free)} free + "
                f"{len(self._idle)} cached-idle "
                f"of {self.num_pages - 1} allocatable"
            )
        pages = []
        for _ in range(n):
            if not self._free:
                self._evict_one()
            page = self._free.pop()
            self._ref[page] = 1
            self._n_free -= 1
            self._n_referenced += 1
            pages.append(page)
        return pages

    def ref(self, page: int) -> None:
        """Add a reader to ``page`` — either sharing a live page or
        reactivating a cached-idle one (a prefix-cache hit)."""
        if page in self._ref:
            self._ref[page] += 1
        elif page in self._idle:
            del self._idle[page]
            self._ref[page] = 1
            self._n_idle -= 1
            self._n_referenced += 1
        else:
            raise AssertionError(
                f"ref of page {page} that is neither live nor cached-idle"
            )

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def unref(self, page: int) -> None:
        """Drop one reader. At zero readers the page parks on the
        cached-idle LRU when the trie registered it, else frees."""
        count = self._ref.get(page)
        if count is None:
            raise AssertionError(
                f"unref of page {page} that has no readers "
                "(double free or foreign page)"
            )
        if count > 1:
            self._ref[page] = count - 1
            return
        del self._ref[page]
        self._n_referenced -= 1
        if page in self._cached:
            self._idle[page] = None  # most-recently-used end
            self._n_idle += 1
        else:
            self._free.append(page)
            self._n_free += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reader from each page (block-table release)."""
        for page in pages:
            self.unref(page)

    def mark_cached(self, page: int) -> None:
        """The prefix trie registered ``page``: at refcount 0 it will idle
        (content retained) instead of freeing."""
        assert page in self._ref or page in self._idle, (
            f"mark_cached on page {page} that is not live"
        )
        self._cached.add(page)

    def touch(self, page: int) -> None:
        """LRU-touch a cached-idle page (trie hit on an existing node)."""
        if page in self._idle:
            self._idle.move_to_end(page)

    def check_invariants(self) -> None:
        """Free + referenced + cached-idle partition the allocatable pages
        exactly; every cached page is live or idle; refcounts positive."""
        free_set = set(self._free)
        idle_set = set(self._idle)
        ref_set = set(self._ref)
        assert len(free_set) == len(self._free), "duplicate page in free list"
        assert NULL_PAGE not in free_set, "null page leaked into free list"
        assert NULL_PAGE not in ref_set, "null page was allocated"
        assert NULL_PAGE not in idle_set, "null page in the idle pool"
        assert not (free_set & ref_set), (
            f"pages both free and referenced: {free_set & ref_set}"
        )
        assert not (free_set & idle_set), (
            f"pages both free and cached-idle: {free_set & idle_set}"
        )
        assert not (idle_set & ref_set), (
            f"pages both cached-idle and referenced: {idle_set & ref_set}"
        )
        assert all(c >= 1 for c in self._ref.values()), (
            "non-positive refcount"
        )
        assert self._cached <= (ref_set | idle_set), (
            f"trie-registered pages neither live nor idle: "
            f"{self._cached - ref_set - idle_set}"
        )
        assert idle_set <= self._cached, (
            f"idle pages not registered in the trie: {idle_set - self._cached}"
        )
        total = len(free_set) + len(ref_set) + len(idle_set)
        assert total == self.num_pages - 1, (
            f"page leak in pool(s) {'/'.join(self.pool_names)}: "
            f"{len(free_set)} free + {len(ref_set)} referenced "
            f"+ {len(idle_set)} idle != {self.num_pages - 1} allocatable"
        )
        # The O(1) running gauges must agree with the sweep-derived truth —
        # a drifted counter is as much a bug as a leaked page.
        assert self._n_free == len(free_set), (
            f"pages_free gauge drifted: {self._n_free} != {len(free_set)}"
        )
        assert self._n_referenced == len(ref_set), (
            f"pages_referenced gauge drifted: "
            f"{self._n_referenced} != {len(ref_set)}"
        )
        assert self._n_idle == len(idle_set), (
            f"pages_cached_idle gauge drifted: "
            f"{self._n_idle} != {len(idle_set)}"
        )

    def assert_quiescent(self) -> None:
        """Teardown gate (engine close / post-drain): no page may still be
        referenced. Cached-idle pages are fine — they are reclaimable and
        die with the device arrays — but a nonzero referenced gauge here is
        a leaked block table, the exact silent loss close() exists to
        catch. One page id pins K/V in every governed pool, so the message
        names them all (target vs target/draft)."""
        assert self._n_referenced == 0, (
            f"teardown leaked {self._n_referenced} referenced page(s) in "
            f"pool(s) {'/'.join(self.pool_names)}: {sorted(self._ref)}"
        )
        self.check_invariants()


class BlockTable:
    """One sequence's logical-page -> physical-page map."""

    def __init__(self):
        self.pages: List[int] = []

    def __len__(self) -> int:
        return len(self.pages)

    def ensure(
        self, n_tokens: int, page_size: int, allocator: PagedBlockAllocator
    ) -> int:
        """Grow the table to cover ``n_tokens`` positions; returns how many
        pages were newly allocated. All-or-nothing per call: a failed grow
        raises :class:`OutOfPages` without taking any pages."""
        need = PagedBlockAllocator.pages_needed(n_tokens, page_size)
        grow = need - len(self.pages)
        if grow <= 0:
            return 0
        self.pages.extend(allocator.allocate(grow))
        return grow

    def release(self, allocator: PagedBlockAllocator) -> int:
        """Drop this table's reader from every page (retire/preempt);
        returns the count released. No device-side work: a page with other
        readers lives on, a trie-registered page idles with its contents
        intact, anything else frees (stale contents are masked)."""
        n = len(self.pages)
        if n:
            allocator.free(self.pages)
            self.pages = []
        return n

    def as_row(self, width: int) -> np.ndarray:
        """``[width]`` int32 row for the device block-table batch, padded
        with the null page."""
        if len(self.pages) > width:
            raise ValueError(
                f"table holds {len(self.pages)} pages, row width is {width}"
            )
        row = np.full((width,), NULL_PAGE, np.int32)
        row[: len(self.pages)] = self.pages
        return row


class WindowTable(BlockTable):
    """One sequence's table in a WINDOW group (:class:`WindowGroup`):
    ``pages[i]`` is logical page ``first + i``. The pages before ``first``
    held nothing a window can still meet and have gone back to the group's
    allocator (:meth:`trim`); the table grows at its end as any table does."""

    def __init__(self):
        super().__init__()
        self.first = 0

    def ensure(
        self, n_tokens: int, page_size: int, allocator: PagedBlockAllocator
    ) -> int:
        need = PagedBlockAllocator.pages_needed(n_tokens, page_size)
        grow = need - self.first - len(self.pages)
        if grow <= 0:
            return 0
        self.pages.extend(allocator.allocate(grow))
        return grow

    def trim(
        self, live_from: int, page_size: int, allocator: PagedBlockAllocator
    ) -> int:
        """Give back every page wholly before position ``live_from``;
        returns how many went."""
        drop = min(live_from // page_size - self.first, len(self.pages))
        if drop <= 0:
            return 0
        allocator.free(self.pages[:drop])
        del self.pages[:drop]
        self.first += drop
        return drop

    def release(self, allocator: PagedBlockAllocator) -> int:
        self.first = 0
        return super().release(allocator)

    def as_row(self, width: int, first_page: int = 0) -> np.ndarray:
        """``[width]`` int32 row of the SHORT table a window layer is
        handed: entry ``j`` is logical page ``first_page + j`` where the
        table holds it, the null page elsewhere."""
        row = np.full((width,), NULL_PAGE, np.int32)
        skip = first_page - self.first  # of this table's pages
        held = self.pages[max(skip, 0) : max(skip, 0) + width + min(skip, 0)]
        at = max(-skip, 0)
        row[at : at + len(held)] = held
        return row


def window_span_pages(window: int, page_size: int, tokens: int = 1) -> int:
    """Pages a sequence holds in a window group while ``tokens`` new tokens in
    a row are written and read: from the first one's window's first key,
    which may stand last in its page, to the last one's own
    (``ops/paged_attention.py`` ``window_group_pages``: the same arithmetic,
    kept free of JAX here)."""
    return 1 + -(-(window + tokens - 2) // page_size)


class WindowGroup:
    """A block-table GROUP whose layers attend inside a window: a pool size,
    an allocator and a :class:`WindowTable` a sequence of its own, beside the
    full layers' (whose table, allocator and trie are what they always
    were). A query at position ``t`` reads the keys ``(t - window, t]``, so
    once a sequence has ``len_cached`` positions cached, every page wholly
    before ``len_cached - (window - 1)`` is dead to it and goes back to the
    group's allocator (:meth:`trim`, after every prefill piece and decode
    dispatch: the device runs its programs in the order they were launched,
    so a page handed on is written by a LATER program than the last that
    read it). A decoding sequence so holds at most ``decode_pages`` pages
    here and a sequence inside a piece of ``chunk`` tokens ``piece_pages``.

    ``free_ahead`` moves the rule that many tokens forward; 0 is the rule.
    The tests and the benchmark's control plant 1 (a page freed one step
    early), and nothing else sets it."""

    free_ahead = 0

    def __init__(
        self, allocator: PagedBlockAllocator, *, window: int, page_size: int,
        chunk: int,
    ):
        if window < 1:
            raise ValueError(f"a window group needs a window, got {window}")
        self.allocator = allocator
        self.window = window
        self.page_size = page_size
        # What the layers' short tables are wide.
        self.decode_pages = window_span_pages(window, page_size)
        self.piece_pages = window_span_pages(window, page_size, chunk)
        self.pages_freed = 0  # lifetime, by trim alone
        self.trims = 0  # the trims among them that freed a page
        self.pages_held_peak = 0  # the most ONE sequence held at once

    def first_page(self, position: int) -> int:
        """The logical page a short table's first entry stands for when its
        first new token is at ``position``."""
        return max(position - (self.window - 1), 0) // self.page_size

    def trim(self, table: WindowTable, len_cached: int) -> int:
        live_from = max(0, len_cached - (self.window - 1) + self.free_ahead)
        freed = table.trim(live_from, self.page_size, self.allocator)
        self.pages_freed += freed
        self.trims += freed > 0
        return freed

    def note_held(self, table: WindowTable) -> None:
        self.pages_held_peak = max(self.pages_held_peak, len(table.pages))


class PagePoolGroup:
    """Named device page pools sharing ONE physical page-id space — the
    ``"target"`` model's pool always, plus a ``"draft"`` pool when the
    engine runs speculative decoding.

    Every pool is built with the SAME ``(num_pages, page_size)`` geometry
    (per-layer shapes ``[num_pages, page_size, Hkv, D]`` differ freely — a
    draft model is narrower), so a physical page id names the same logical
    token span in every pool. That is the whole lockstep mechanism: ONE
    :class:`PagedBlockAllocator` and ONE :class:`BlockTable` per sequence
    govern all pools at once — allocation, refcounting, prefix-cache
    adoption, copy-on-write, and release are decided once on the shared id
    and apply to target and draft K/V alike. The engine prefills and
    decode-writes both pools for every position, so a page's draft K/V is
    always exactly as valid as its target K/V, including pages resurrected
    from the prefix trie by a later request.

    Rejected-token rollback needs NO device work in any pool: the attention
    visibility mask hides everything past a row's ``seq_len``, so lowering
    the host-side ``len_cached`` IS the rollback — stale speculative K/V
    (target's verify writes and the draft's proposal writes alike) is dead
    by construction and simply overwritten when the real continuation is
    fed (write-then-attend)."""

    def __init__(self, **pools):
        if "target" not in pools:
            raise ValueError("PagePoolGroup needs at least a 'target' pool")
        self.pools = dict(pools)

    def __getitem__(self, name: str):
        return self.pools[name]

    def __setitem__(self, name: str, value) -> None:
        if name not in self.pools:
            raise KeyError(
                f"unknown pool {name!r}; declared: {tuple(self.pools)}"
            )
        self.pools[name] = value

    def __contains__(self, name: str) -> bool:
        return name in self.pools

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self.pools)

    def copy_page(self, copy_fn, src, dst) -> None:
        """Fan the engine's compiled page-copy out over EVERY pool — the
        device half of copy-on-write must clone a shared page's draft K/V
        in the same step as its target K/V, or a later speculative write
        through the fresh id would diverge the two pools. ``copy_fn`` is
        one program shared by every pool, or a mapping pool-name ->
        program when pools carry their own shardings (the mesh-sharded
        engine compiles one per pool so in/out shardings stay explicit)."""
        per_pool = isinstance(copy_fn, dict)
        for name in self.pools:
            fn = copy_fn[name] if per_pool else copy_fn
            self.pools[name] = fn(self.pools[name], src, dst)


class PrefixCache:
    """Hash-trie over page-aligned token prefixes -> physical pages.

    Nodes live at full-page granularity: the child key is
    ``(parent_node_id, tuple(page_size tokens))``, so two prompts share a
    node exactly when they share that page-aligned prefix — token content is
    compared exactly (no hash-collision corruption). Each node pins one
    physical page of already-computed K/V. A retired request additionally
    registers its final *partial* page under the last full node, keyed by
    its (< page_size) token tuple; a later request may extend it, with the
    scheduler copy-on-writing when more than one extender holds it.

    Lookup walks full-page children greedily, then tries the longest
    matching partial child, never consuming a request's last token (the
    decode step must be fed at least one). Every page returned is ref'd on
    behalf of the caller. Registration dedupes: if a node already exists
    for the same (parent, tokens), the existing page wins and the caller's
    page stays private (freed normally at release).

    Eviction is driven by the allocator: when allocation pressure recycles
    a cached-idle page, ``_on_evict`` removes every trie entry pointing at
    it. Descendants of an evicted node become unreachable and drain off the
    LRU naturally — readers are unaffected either way because block tables
    hold refs independently of the trie.
    """

    ROOT = 0

    def __init__(self, allocator: PagedBlockAllocator, page_size: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.allocator = allocator
        self.page_size = page_size
        self._next_id = 1
        # (parent_id, full-page token tuple) -> (node_id, page)
        self._full: Dict[Tuple[int, Tuple[int, ...]], Tuple[int, int]] = {}
        # parent_id -> {partial token tuple -> page}
        self._partial: Dict[int, Dict[Tuple[int, ...], int]] = {}
        # page -> list of trie entries pointing at it (a page can carry a
        # partial node and later the full node that extends it in place).
        self._by_page: Dict[int, List[tuple]] = {}
        allocator.evict_hook = self._on_evict
        # Second trie level: a HostPageTier (serving/hostkv.py) catches
        # full-page evictions d2h and serves them back on later prefix
        # hits. None keeps the classic single-tier behavior bit-for-bit.
        self.host = None
        # Device pages whose h2d fetch is planned but not yet executed —
        # their device content is garbage until the engine's fetch
        # program lands, so an eviction racing the plan must NOT spill
        # them (the host tier already holds the key).
        self.fetch_pending: set = set()
        # node id -> its content-addressed chain key (ROOT = "root").
        # Maintained incrementally at registration so the scheduler can
        # extend a device match into the host tier in O(pages), not by
        # re-hashing the whole prefix.
        self._node_key: Dict[int, str] = {self.ROOT: "root"}
        self.lookups = 0
        self.hits = 0  # lookups that matched at least one token
        self.tokens_hit = 0
        self.tokens_hit_host = 0
        self.tokens_missed = 0

    # ------------------------------------------------------------- queries

    @property
    def num_nodes(self) -> int:
        return len(self._full) + sum(len(d) for d in self._partial.values())

    def _walk(self, tokens: Sequence[int], limit: int):
        """Longest cached match of ``tokens[:limit]``: yields the full-page
        chain then at most one partial page. Returns
        ``(pages, matched, node)`` WITHOUT taking refs."""
        pages: List[int] = []
        node = self.ROOT
        matched = 0
        page_size = self.page_size
        while matched + page_size <= limit:
            entry = self._full.get(
                (node, tuple(tokens[matched : matched + page_size]))
            )
            if entry is None:
                break
            node, page = entry
            pages.append(page)
            matched += page_size
        best_len = 0
        best_page = None
        for ptoks, page in self._partial.get(node, {}).items():
            m = len(ptoks)
            if (
                m > best_len
                and matched + m <= limit
                and tuple(tokens[matched : matched + m]) == ptoks
            ):
                best_len, best_page = m, page
        if best_page is not None:
            pages.append(best_page)
            matched += best_len
        return pages, matched, node

    def key_chain(self, tokens: Sequence[int]) -> List[str]:
        """Content-addressed keys for the page-aligned trie chain covering
        ``tokens``' currently cached prefix — the KV metadata the elastic
        snapshot records per request. Key ``i`` digests the first
        ``(i+1) * page_size`` tokens (hash-chained, so each key commits to
        the whole prefix, not just its own page): identical token prefixes
        produce identical chains on ANY engine, letting a restore target
        predict which pages its own trie will re-serve without shipping
        device K/V. Takes no refs and does not touch the LRU."""
        keys: List[str] = []
        node = self.ROOT
        matched = 0
        prev = "root"
        page_size = self.page_size
        while matched + page_size <= len(tokens):
            chunk = tuple(tokens[matched : matched + page_size])
            entry = self._full.get((node, chunk))
            if entry is None:
                break
            prev = chain_next(prev, chunk)
            keys.append(prev)
            node = entry[0]
            matched += page_size
        return keys

    def key_chain_tiered(
        self, tokens: Sequence[int]
    ) -> Tuple[List[str], List[str]]:
        """:meth:`key_chain` split by tier: the device chain, then the
        host-resident continuation beyond it — the residency record the
        elastic snapshot persists so a restore target knows which pages
        the adopter can re-serve by h2d fetch instead of re-prefill."""
        keys = self.key_chain(tokens)
        host_keys: List[str] = []
        if self.host is not None:
            matched = len(keys) * self.page_size
            prev = keys[-1] if keys else "root"
            while matched + self.page_size <= len(tokens):
                chunk = tuple(tokens[matched : matched + self.page_size])
                key = chain_next(prev, chunk)
                if not self.host.match(key, chunk):
                    break
                host_keys.append(key)
                prev = key
                matched += self.page_size
        return keys, host_keys

    def node_key(self, node: int) -> Optional[str]:
        """The content-addressed chain key of ``node`` (``"root"`` for
        ROOT); None for a node that was evicted out from under its id."""
        return self._node_key.get(node)

    def host_continuation(
        self, tokens: Sequence[int], matched: int, node: int, limit: int
    ):
        """Full-page windows of ``tokens[matched:limit]`` the HOST tier
        can serve, continuing the chain from device node ``node`` —
        ``[(key, chunk), ...]`` in order. Empty when no host tier is
        attached, when the device match ended mid-page (a partial page
        breaks the full-page chain), or at the first window the host
        cannot serve. Pure query: no refs, pins, or LRU motion."""
        out: List[Tuple[str, Tuple[int, ...]]] = []
        if self.host is None or matched % self.page_size:
            return out
        prev = self._node_key.get(node)
        if prev is None:
            return out
        while matched + self.page_size <= limit:
            chunk = tuple(tokens[matched : matched + self.page_size])
            key = chain_next(prev, chunk)
            if not self.host.match(key, chunk):
                break
            out.append((key, chunk))
            prev = key
            matched += self.page_size
        return out

    def peek(self, tokens: Sequence[int]) -> int:
        """How many leading tokens of ``tokens`` (capped at ``len - 1``)
        are cached right now in EITHER tier — admission's feasibility
        estimate. Takes no refs and does not touch the LRU."""
        limit = max(0, len(tokens) - 1)
        _, matched, node = self._walk(tokens, limit)
        if self.host is not None:
            matched += self.page_size * len(
                self.host_continuation(tokens, matched, node, limit)
            )
        return matched

    def lookup(self, tokens: Sequence[int]):
        """Match the longest cached prefix of ``tokens`` (never the last
        token), ref every matched page for the caller, and return
        ``(pages, n_cached_tokens, last_full_node_id)``."""
        limit = max(0, len(tokens) - 1)
        pages, matched, node = self._walk(tokens, limit)
        for page in pages:
            self.allocator.ref(page)
        self.lookups += 1
        if matched:
            self.hits += 1
        self.tokens_hit += matched
        self.tokens_missed += limit - matched
        return pages, matched, node

    def note_host_hit(self, n_tokens: int) -> None:
        """The scheduler extended the last :meth:`lookup` by ``n_tokens``
        served from the host tier: reclassify them from missed (where
        lookup counted them) to host-hit, keeping the totals exact."""
        self.tokens_hit_host += n_tokens
        self.tokens_missed -= n_tokens

    # ---------------------------------------------------------- mutation

    def register_full(
        self, parent: int, tokens: Tuple[int, ...], page: int
    ) -> Tuple[int, bool]:
        """Register a freshly filled full page under ``parent``. If the
        node already exists the existing page wins (the caller's page stays
        private); returns ``(node_id, registered)``."""
        assert len(tokens) == self.page_size, (
            f"full node needs {self.page_size} tokens, got {len(tokens)}"
        )
        key = (parent, tokens)
        entry = self._full.get(key)
        if entry is not None:
            self.allocator.touch(entry[1])
            return entry[0], False
        node_id = self._next_id
        self._next_id += 1
        self._full[key] = (node_id, page)
        self._by_page.setdefault(page, []).append(("full", key))
        parent_key = self._node_key.get(parent)
        if parent_key is not None:
            self._node_key[node_id] = chain_next(parent_key, tokens)
        self.allocator.mark_cached(page)
        return node_id, True

    def register_partial(
        self, parent: int, tokens: Tuple[int, ...], page: int
    ) -> bool:
        """Register a retiring request's final partial page (``< page_size``
        tokens) under ``parent``. First writer wins on identical content."""
        if not tokens:
            return False
        assert len(tokens) < self.page_size, (
            f"partial node must hold < {self.page_size} tokens"
        )
        children = self._partial.setdefault(parent, {})
        if tokens in children:
            self.allocator.touch(children[tokens])
            return False
        children[tokens] = page
        self._by_page.setdefault(page, []).append(("partial", parent, tokens))
        self.allocator.mark_cached(page)
        return True

    def _on_evict(self, page: int) -> None:
        """Allocation pressure recycled ``page``: forget every trie entry
        pointing at it before its contents are overwritten — but first,
        when a host tier is attached, spill full-page entries d2h so the
        prefix survives demotion instead of costing a re-prefill. A page
        whose h2d fetch is still pending holds garbage and is NEVER
        spilled (the host tier already owns the key); partial pages are
        not spilled either — the content-addressed chain names full
        pages only."""
        entries = self._by_page.pop(page, [])
        pending = page in self.fetch_pending
        self.fetch_pending.discard(page)
        for entry in entries:
            if entry[0] == "full":
                full = self._full.pop(entry[1], None)
                if full is None:
                    continue
                key = self._node_key.pop(full[0], None)
                if self.host is not None and key is not None and not pending:
                    # Dispatches the d2h gather; the engine drains it
                    # into the host buffers before the page's new
                    # content could be read back.
                    self.host.note_evict(page, key, entry[1][1])
            else:
                children = self._partial.get(entry[1])
                if children is not None:
                    children.pop(entry[2], None)
                    if not children:
                        del self._partial[entry[1]]

    def stats(self) -> Dict[str, float]:
        # Host-served tokens were reclassified out of tokens_missed by
        # note_host_hit, so the three buckets partition every looked-up
        # token: device hit / host hit / miss.
        looked = self.tokens_hit + self.tokens_hit_host + self.tokens_missed
        return {
            "prefix_lookups": self.lookups,
            "prefix_hits": self.hits,
            "prefix_tokens_hit": self.tokens_hit,
            "prefix_tokens_hit_host": self.tokens_hit_host,
            "prefix_tokens_missed": self.tokens_missed,
            "prefix_hit_rate": self.tokens_hit / looked if looked else 0.0,
            "prefix_hit_rate_total": (
                (self.tokens_hit + self.tokens_hit_host) / looked
                if looked else 0.0
            ),
            "prefix_nodes": self.num_nodes,
        }
