"""Request-level continuous-batching scheduler.

The host-side policy half of the engine: maintains the waiting queue and the
active slot set, interleaves chunked prefill with batched decode under a
per-step token budget, preempts under page pressure, and retires finished
sequences every step so new requests join mid-flight.

Design decisions, in the order they bite:

* **Priority = submission order** (request id, lower wins). Preemption only
  ever evicts a strictly LOWER-priority victim than the sequence that needs
  pages — or, failing that, preempts the requester itself — so the oldest
  running request always makes forward progress and two cache-hungry
  requests cannot livelock trading pages.
* **Decode before prefill in the budget**: every DECODE-state slot reserves
  one token of the step budget first, then the remainder goes to prefill
  chunks. Running sequences never starve (TPOT stays flat), while admitted
  prompts still chunk in within a bounded number of steps (TTFT bounded by
  prompt_len / leftover_budget).
* **Prefill covers positions [0, L-1)** of a request's token list; the LAST
  token always goes through the shared batched decode step, whose sampled
  output is the first new token. This mirrors ``generate``'s serial loop
  exactly (the body at position t decides token t+1), which is what makes
  served output token-identical to offline decode.
* **A prompt is prefilled in pieces, each one program**: a piece is what is
  left of the prompt, of ``max_prefill_chunk`` and of the step's budget,
  whichever is least, and the engine pads it to the next multiple of
  ``g = min(PREFILL_GRANULE, max_prefill_chunk)`` tokens, so it compiles
  ``max_prefill_chunk / g`` prefill programs and a prompt costs one
  program a ``max_prefill_chunk`` tokens, not one a power of two. A piece
  that is not the request's last is cut DOWN to whole granules, so only a
  request's last piece is ragged; a budget sliver under ``g`` waits for
  the next step rather than cost a program of its own, unless the step
  has planned no prefill at all: then the oldest prefilling request takes
  the sliver ragged, so a budget that stays under ``g`` (many decode rows
  under a small ``token_budget``) starves nobody. The budget is charged
  the tokens a piece holds, never its padding.
* **Prefill starts at the first uncached token**: with a
  :class:`~.kv_cache.PrefixCache` attached, admission looks the request's
  tokens up in the trie and adopts (refs) every matched page, so a shared
  system prompt is prefilled once fleet-wide. A writer about to extend a
  SHARED page (refcount > 1 — concurrent extenders of a cached partial
  page) gets a copy-on-write entry in the plan first; pages it owns alone
  are extended in place.
* **Preempted sequences keep their generated tokens** and re-enter the
  waiting queue at their original priority; on re-admission the prefix
  cache usually re-serves the pages they just released (release only idles
  registered pages), so re-prefill cost shrinks to the uncached tail.
* **Decode results may resolve a step late** (the engine's overlapped
  loop): :meth:`note_decode_dispatched` advances the host-known state
  (cache position, a PENDING placeholder token) at dispatch, and
  :meth:`resolve_decoded` fills in the sampled value when the device
  readback lands. Everything the planner needs (page pressure, budget,
  max_new_tokens) is host-known at dispatch; only stop-token detection
  waits for the value, costing at most one speculative decode step that
  :meth:`resolve_decoded` rolls back.
* **Speculative decode rows advance by a VARIABLE amount** (``gamma > 0``):
  one scheduled "decode" is a whole draft+verify round that writes
  ``gamma`` K/V positions and emits 1..gamma tokens, so the budget charges
  ``gamma`` per running row and :meth:`_ensure_pages` covers the full
  chunk (``len_cached + gamma``). Acceptance resolves PER ROW via
  :meth:`resolve_spec` — a row that accepted its whole chunk advances by
  gamma while its neighbor advances by 1; no minimum-across-batch stall.
  Rollback of the rejected tail is free: ``len_cached`` simply advances by
  the emitted count, and K/V written past it is masked (and overwritten
  write-then-attend when the real continuation is fed). The prefix trie
  only ever registers pages fully below ``len_cached``, so rejected
  garbage can never be cached, and copy-on-write is decided on the one
  page containing ``len_cached`` exactly as in the single-token path —
  every later page a round touches was freshly allocated for this row.
* **A model may name a second block-table group** (``window_group``, a
  :class:`~.kv_cache.WindowGroup`: the layers that attend inside a window).
  A sequence then holds a table in each group, ``table`` on ``allocator`` as
  ever and ``window_table`` on the group's own allocator; pages are ensured
  in both before a piece or a decode row is planned (a shortage in EITHER
  preempts by the one priority rule), released from both on retire, cancel
  and preemption (a preempted sequence is prefilled again from position 0),
  and after every piece and decode dispatch the window table gives back the
  pages its window has left behind. Such a request is planned ONE piece a
  step: a piece's pages are reckoned after the one before has given its
  own back.
"""

from __future__ import annotations

import bisect
import dataclasses
import enum
import time
from typing import List, Optional, Tuple

from distributed_pytorch_tpu.obs.flight import NULL_FLIGHT_RECORDER
from distributed_pytorch_tpu.obs.tracer import NULL_TRACER
from distributed_pytorch_tpu.serving.kv_cache import (
    BlockTable,
    OutOfPages,
    PagedBlockAllocator,
    PrefixCache,
    WindowGroup,
    WindowTable,
    window_span_pages,
)

# Placeholder for a sampled token whose device readback has not landed yet
# (overlapped stepping). Never a valid vocab id; never visible through
# poll() — ``generated`` only ever holds resolved values.
PENDING_TOKEN = -1


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request generation parameters. ``temperature <= 0`` is greedy;
    ``seed`` drives a per-request RNG folded with the token index, so a
    request's sampled stream is independent of batch composition and
    survives preemption. ``top_k``/``top_p`` are engine-level (static in the
    compiled step), not per-request. ``deadline_s`` is a wall-clock budget
    from submission: a request still unfinished after that many seconds is
    retired with the EXPIRED terminal state at the next schedule pass and
    its pages freed (partial output stays pollable). ``stop_sequences``
    generalizes ``stop_token`` to multi-token suffixes: the request
    finishes when its generated tail matches any sequence (the matching
    tokens stay in the output, same as a stop token). Detection is
    host-side at resolve time, so it composes with every engine mode
    including speculative decoding."""

    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0
    stop_token: Optional[int] = None
    deadline_s: Optional[float] = None
    stop_sequences: Tuple[Tuple[int, ...], ...] = ()


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"
    # Terminal without completing: deadline elapsed / cancelled (client
    # cancel, engine close). Partial output remains pollable; pages freed.
    EXPIRED = "expired"
    CANCELLED = "cancelled"


# States from which a request never runs again. A late decode readback for a
# terminal request resolves harmlessly (resolve_decoded discards the value).
_TERMINAL = (
    RequestState.FINISHED,
    RequestState.EXPIRED,
    RequestState.CANCELLED,
)


def _adapter_bound(req: "Request") -> bool:
    """True when ``req`` decodes under LoRA-merged weights. Its K/V is
    computed under DIFFERENT params than base-model requests', so it must
    neither read from nor publish to the token-keyed prefix trie — a
    token-identical prefix under other weights is not the same cache
    entry."""
    mods = req.mods
    return mods is not None and getattr(mods, "adapter", None) is not None


def _flight_trace(req: "Request") -> dict:
    """Flight-recorder stamp for the fleet trace identity: ``{}`` for
    untraced requests (dump shape unchanged), ``{"trace_id": ...}`` when
    the request carries one — so ``replay_to_tracer()`` output merges into
    the fleet trace and a dead replica's last moments land on the victim
    request's waterfall."""
    return {"trace_id": req.trace_id} if req.trace_id is not None else {}


def _stops_on_sequence(req: "Request") -> bool:
    """True when ``req.generated`` ends with any of its stop sequences."""
    gen = req.generated
    for seq in req.params.stop_sequences:
        n = len(seq)
        if n and len(gen) >= n and tuple(gen[-n:]) == tuple(seq):
            return True
    return False


@dataclasses.dataclass
class Request:
    """One in-flight generation request. ``tokens`` = prompt + generated;
    ``len_cached`` counts how many of them have K/V in the paged cache.
    Invariant while in DECODE state: ``len_cached == len(tokens) - 1`` — the
    next decode step feeds ``tokens[len_cached]`` and appends the sample
    (as :data:`PENDING_TOKEN` until the readback resolves it)."""

    req_id: int
    prompt: List[int]
    params: SamplingParams
    tokens: List[int] = dataclasses.field(default_factory=list)
    generated: List[int] = dataclasses.field(default_factory=list)
    len_cached: int = 0
    table: BlockTable = dataclasses.field(default_factory=BlockTable)
    # The sequence's table in the model's window group (empty and unused
    # where the scheduler has none).
    window_table: WindowTable = dataclasses.field(default_factory=WindowTable)
    state: RequestState = RequestState.WAITING
    slot: Optional[int] = None
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    preempt_count: int = 0
    # Positions in ``tokens`` holding PENDING_TOKEN, oldest first — decode
    # dispatches whose sampled value has not been read back yet.
    pending_idx: List[int] = dataclasses.field(default_factory=list)
    # Prefix-trie cursor: the node covering the first ``trie_pages`` full
    # pages of ``tokens`` (matched at admission, advanced as pages fill).
    trie_node: int = PrefixCache.ROOT
    trie_pages: int = 0
    # Tokens served from the prefix cache at FIRST admission (None until
    # then; 0 = a clean miss) — the TTFT hit/miss split keys off this.
    cached_prompt_tokens: Optional[int] = None
    # Tokens staged from the HOST page tier at first admission (planned
    # h2d fetches instead of re-prefill). The TTFT source split labels
    # device hits first, then host, then miss.
    host_prompt_tokens: Optional[int] = None
    # Admission-time estimate of uncached prefill work (queue backpressure).
    est_uncached: int = 0
    # Tenant-opaque payload carried through scheduling untouched — and
    # through the elastic snapshot/restore codec, so routing/billing context
    # survives an engine migration. Must be JSON-serializable to snapshot.
    metadata: Optional[dict] = None
    # Typed tenant identity (the front door's fair-share / quota / SLO
    # key). Promoted out of ``metadata`` so drain/restore and fleet
    # failover preserve tenancy without convention.
    tenant_id: str = "anon"
    # Streaming high-water mark: how many of ``generated`` have been
    # handed to the client. A drain snapshot records it so a restored
    # stream resumes exactly here — no replayed or skipped tokens.
    delivered: int = 0
    # Live per-request model mods (duck-typed: the engine binds a
    # ``serving.mods.ModState`` here). The scheduler only calls
    # ``note_token(token) -> bool`` on committed tokens; True finishes
    # the request (e.g. a grammar reached a forced end).
    mods: Optional[object] = None
    # Goodput accounting: prefill positions below this mark re-compute K/V
    # the engine already had (lost to preemption or a snapshot/restore);
    # ``rework_kind`` names the waste bucket they charge to.
    rework_until: int = 0
    rework_kind: str = "preempt_rework"
    # Fleet-wide trace identity, minted a layer up (front door / router)
    # and carried unchanged across preemption, drain hand-off, hedge
    # twins, and failover id-rebasing — req_ids are engine-local and
    # rebased on adoption; this string is the one name a request keeps.
    trace_id: Optional[str] = None

    def __post_init__(self):
        if not self.tokens:
            self.tokens = list(self.prompt)

    @property
    def n_generated(self) -> int:
        return len(self.generated)

    @property
    def n_issued(self) -> int:
        """Sampled tokens requested from the device so far, including ones
        whose readback is pending — the planner's max_new_tokens guard."""
        return len(self.tokens) - len(self.prompt)

    @property
    def remaining_prefill(self) -> int:
        return len(self.tokens) - 1 - self.len_cached

    @property
    def done(self) -> bool:
        return self.state in _TERMINAL


@dataclasses.dataclass
class StepPlan:
    """One engine step's worth of device work: copy-on-write page copies
    (``(slot, src_page, dst_page)``, executed first), host-tier page
    fetches (``(key, dst_page, parent_node, tokens, node_id)``, h2d
    stages executed before any prefill/decode that could read them),
    prefill pieces (executed in order, each ``(slot, tokens)``: the tokens
    the piece holds, whatever width the engine pads it to), then
    one batched decode over ``decode_slots``. ``empty`` deliberately
    ignores ``fetches``: the engine executes them BEFORE its empty-plan
    early return, so a fetch planned for a request that was preempted in
    the same schedule still lands (the trie entry stays valid)."""

    copies: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list
    )
    fetches: List[Tuple[str, int, int, Tuple[int, ...], int]] = (
        dataclasses.field(default_factory=list)
    )
    prefill: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    decode_slots: List[int] = dataclasses.field(default_factory=list)

    @property
    def empty(self) -> bool:
        return not self.prefill and not self.decode_slots

#: Prefill programs come in widths of whole granules: a piece of a prompt
#: runs padded to the next multiple of ``min(PREFILL_GRANULE, cap)`` tokens.
#: One constant for every model (PERF.md §6, PR 32: on the chip 32 gains
#: under 1% for twice the programs, 128 loses 3.5%).
PREFILL_GRANULE = 64


class Scheduler:
    """Waiting queue + slot set + page-pressure policy (see module doc).

    ``prefix_cache`` enables automatic prefix caching; ``gamma > 0``
    switches decode planning to speculative rounds (each scheduled decode
    writes ``gamma`` K/V positions and resolves 1..gamma tokens via
    :meth:`resolve_spec`); ``debug=True`` runs the O(num_pages) allocator
    invariant sweep after every :meth:`schedule` call — kept on in tests,
    off on the serving hot path.
    """

    def __init__(
        self,
        allocator: PagedBlockAllocator,
        *,
        max_slots: int,
        page_size: int,
        pages_per_seq: int,
        token_budget: int = 64,
        max_prefill_chunk: int = 32,
        prefix_cache: Optional[PrefixCache] = None,
        gamma: int = 0,
        debug: bool = False,
        tracer=NULL_TRACER,
        flight=NULL_FLIGHT_RECORDER,
        window_group: Optional[WindowGroup] = None,
    ):
        if window_group is not None and (prefix_cache is not None or gamma):
            raise ValueError(
                "a window group is planned with neither a prefix cache (a "
                "hit would need the window layers' pages, which are gone) "
                "nor speculative rounds"
            )
        if token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        if gamma < 0:
            raise ValueError(f"gamma must be >= 0, got {gamma}")
        cap = max_prefill_chunk
        if cap < 1 or cap & (cap - 1):
            raise ValueError(
                f"max_prefill_chunk must be a power of two, got "
                f"{max_prefill_chunk} (the prefill programs' widths are "
                f"its whole granules)"
            )
        self.allocator = allocator
        self.max_slots = max_slots
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.token_budget = token_budget
        self.max_prefill_chunk = max_prefill_chunk
        self.prefill_granule = min(PREFILL_GRANULE, max_prefill_chunk)
        self.prefix_cache = prefix_cache
        self.window_group = window_group
        self.gamma = gamma
        self.debug = debug
        self.tracer = tracer
        self.flight = flight
        self.waiting: List[Request] = []  # kept sorted by req_id
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.preemptions = 0
        self.expired = 0
        self.cancelled = 0
        # Deadline sweeps cost a clock read + O(live) scan per schedule;
        # skip them entirely until a deadline-bearing request shows up.
        self._any_deadlines = False

    @property
    def cow_copies(self) -> int:
        """Lifetime copy-on-write splits (counted on the allocator — the
        page ledger of record — since the registry reads them there)."""
        return self.allocator.cow_copies

    # ------------------------------------------------------------- queries

    @property
    def running(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or any(r is not None for r in self.slots)

    def describe_requests(
        self, now: Optional[float] = None
    ) -> List[dict]:
        """Per-request live-state for ``/statusz``: every waiting and
        slotted request as one JSON-serializable dict — phase (the request
        state), slot, age since submit, prompt/cached/generated token
        counts, preemptions. Read-only; the engine calls it under the
        registry lock so a server-thread reader never sees a slot table
        mid-update."""
        if now is None:
            now = time.perf_counter()

        def describe(req: Request) -> dict:
            doc = {
                "req_id": req.req_id,
                "phase": req.state.value,
                "slot": req.slot,
                "age_s": max(0.0, now - req.submit_time),
                "prompt_len": len(req.prompt),
                "len_cached": req.len_cached,
                "generated": req.n_generated,
                "max_new_tokens": req.params.max_new_tokens,
                "preempt_count": req.preempt_count,
            }
            if req.trace_id is not None:
                doc["trace_id"] = req.trace_id
            return doc

        out = [describe(r) for r in self.waiting]
        out.extend(describe(r) for r in self.slots if r is not None)
        return out

    # ------------------------------------------------------------ mutation

    def add(self, req: Request) -> None:
        bisect.insort(self.waiting, req, key=lambda r: r.req_id)
        if req.params.deadline_s is not None:
            self._any_deadlines = True

    def _admit(
        self, req: Request, slot: int, plan: Optional[StepPlan] = None
    ) -> None:
        req.slot = slot
        req.len_cached = 0
        req.trie_node = PrefixCache.ROOT
        req.trie_pages = 0
        host_served = 0
        if self.prefix_cache is not None and not _adapter_bound(req):
            assert not req.table.pages, "admitting a request holding pages"
            pages, matched, node = self.prefix_cache.lookup(req.tokens)
            req.table.pages = pages
            req.len_cached = matched
            req.trie_node = node
            req.trie_pages = matched // self.page_size
            if plan is not None:
                host_served = self._admit_host_pages(req, plan)
            if req.cached_prompt_tokens is None:
                req.cached_prompt_tokens = matched
                req.host_prompt_tokens = host_served
        elif req.cached_prompt_tokens is None:
            req.cached_prompt_tokens = 0
            req.host_prompt_tokens = 0
        req.state = (
            RequestState.DECODE if req.remaining_prefill == 0
            else RequestState.PREFILL
        )
        self.slots[slot] = req
        if self.tracer.enabled:
            self.tracer.request_event(
                req.req_id, "admit",
                slot=slot,
                cached_tokens=req.len_cached,
                prompt_tokens=len(req.prompt),
                hit=req.len_cached > 0,
                readmission=req.preempt_count > 0,
                **self._group_pages(req),
            )
        if self.flight.enabled:
            self.flight.record(
                "admit",
                req_id=req.req_id,
                slot=slot,
                cached_tokens=req.len_cached,
                host_tokens=host_served,
                readmission=req.preempt_count > 0,
                **_flight_trace(req),
            )

    def _group_pages(self, req: Request) -> dict:
        """What an ``admit`` event says of a model with a window group: the
        pages the request comes to hold in each group if it runs to its
        last token (nothing where there is one group)."""
        group = self.window_group
        if group is None:
            return {}
        full = PagedBlockAllocator.pages_needed(
            len(req.prompt) + req.params.max_new_tokens, self.page_size
        )
        piece = min(self.max_prefill_chunk, max(1, len(req.prompt) - 1))
        return {
            "pages_full": full,
            "pages_window": min(full, window_span_pages(
                group.window, self.page_size, piece
            )),
        }

    def _release(self, req: Request) -> None:
        """Drop ``req``'s pages in every group."""
        if self.window_group is not None:
            req.window_table.release(self.window_group.allocator)
        req.table.release(self.allocator)

    def _admit_host_pages(self, req: Request, plan: StepPlan) -> int:
        """Extend ``req``'s device prefix match into the HOST tier: for
        every consecutive full-page window the host holds, allocate a
        device page, register it in the trie (making the chain a device
        hit for any later request), pin the host entry, and plan an h2d
        fetch — so chunked prefill starts at the first token covered by
        NEITHER tier. Stops at the first page the allocator cannot grant
        without preempting (a fetch is a cache optimization, never worth
        evicting live work for). Returns the host-served token count."""
        pc = self.prefix_cache
        if pc is None or pc.host is None:
            return 0
        limit = max(0, len(req.tokens) - 1)
        wanted = pc.host_continuation(
            req.tokens, req.len_cached, req.trie_node, limit
        )
        served = 0
        for key, chunk in wanted:
            try:
                (page,) = self.allocator.allocate(1)
            except OutOfPages:
                break
            # allocate() may itself evict a cached-idle device page, whose
            # host-side spill can LRU-drop an unpinned host entry — even
            # this very key. Re-verify before pinning; a vanished entry
            # ends the continuation (the chain is broken past it).
            if not pc.host.match(key, chunk):
                self.allocator.free([page])
                break
            node, registered = pc.register_full(req.trie_node, chunk, page)
            # The device walk just failed at (trie_node, chunk) in this
            # same schedule pass, so the registration cannot be a dupe.
            assert registered, "host continuation raced a device node"
            req.table.pages.append(page)
            pc.host.pin(key)
            pc.fetch_pending.add(page)
            plan.fetches.append((key, page, req.trie_node, chunk, node))
            req.trie_node = node
            req.trie_pages += 1
            req.len_cached += self.page_size
            served += self.page_size
        if served:
            pc.note_host_hit(served)
            if self.tracer.enabled:
                self.tracer.request_event(
                    req.req_id, "host_fetch_planned",
                    pages=served // self.page_size, tokens=served,
                )
        return served

    def _preempt(self, req: Request) -> None:
        """Evict ``req`` back to the waiting queue: page refs dropped
        (registered pages idle with contents intact, so re-admission
        usually re-matches them), generated tokens KEPT."""
        self.preemptions += 1
        req.preempt_count += 1
        # Positions up to len_cached must be re-prefilled on re-admission;
        # a later prefix-cache re-match shrinks the actual rework charged.
        req.rework_until = max(req.rework_until, req.len_cached)
        if self.tracer.enabled:
            self.tracer.request_event(
                req.req_id, "preempt",
                n_generated=req.n_generated,
                pages_released=len(req.table.pages),
            )
        if self.flight.enabled:
            self.flight.record(
                "preempt",
                req_id=req.req_id,
                n_generated=req.n_generated,
                pages_released=len(req.table.pages),
                **_flight_trace(req),
            )
        self._release(req)
        self.slots[req.slot] = None
        req.slot = None
        req.len_cached = 0
        req.state = RequestState.WAITING
        self.add(req)

    def retire(self, req: Request, now: Optional[float] = None) -> None:
        """Finished: register the final partial page in the prefix trie
        (full pages were registered as they filled), then drop every page
        ref and the slot. Registered pages idle on the LRU — demoted, not
        freed — so the next request with this prefix hits them; eviction
        happens lazily under OutOfPages pressure."""
        if (
            self.prefix_cache is not None
            and req.slot is not None
            and not _adapter_bound(req)
        ):
            self._register_filled(req)
            start = req.trie_pages * self.page_size
            valid = req.len_cached
            if req.pending_idx:
                valid = min(valid, req.pending_idx[0])
            if start < valid and req.trie_pages < len(req.table.pages):
                self.prefix_cache.register_partial(
                    req.trie_node,
                    tuple(req.tokens[start:valid]),
                    req.table.pages[req.trie_pages],
                )
        self._release(req)
        if req.slot is not None:
            self.slots[req.slot] = None
        elif req.state is RequestState.WAITING:
            # Finished while preempted (stop token resolved post-eviction).
            self.waiting.remove(req)
        req.slot = None
        req.state = RequestState.FINISHED
        req.finish_time = time.perf_counter() if now is None else now
        if self.tracer.enabled:
            self.tracer.request_end(
                req.req_id,
                n_generated=req.n_generated,
                preempt_count=req.preempt_count,
            )
        if self.flight.enabled:
            self.flight.record(
                "retire",
                req_id=req.req_id,
                n_generated=req.n_generated,
                preempt_count=req.preempt_count,
                **_flight_trace(req),
            )

    def cancel(
        self,
        req: Request,
        state: RequestState = RequestState.CANCELLED,
        now: Optional[float] = None,
    ) -> bool:
        """Terminal retirement WITHOUT completion — the one primitive that
        deadline expiry, client cancellation, and engine close all share
        (and that restore relies on to shed rows it cannot re-host). Frees
        the request's pages immediately (trie-registered pages demote to
        cached-idle, private ones free), vacates its slot or removes it
        from the waiting queue, and marks the terminal state; generated
        tokens stay pollable. ``pending_idx`` is deliberately KEPT: a
        decode readback still in flight for this row resolves through
        :meth:`resolve_decoded`'s discard branch. Returns False when the
        request was already terminal."""
        assert state in (RequestState.CANCELLED, RequestState.EXPIRED)
        if req.done:
            return False
        if req.slot is not None:
            self._release(req)
            self.slots[req.slot] = None
            req.slot = None
        elif req.state is RequestState.WAITING:
            self.waiting.remove(req)
            self._release(req)  # empty by invariant
        req.state = state
        req.finish_time = time.perf_counter() if now is None else now
        if state is RequestState.EXPIRED:
            self.expired += 1
        else:
            self.cancelled += 1
        if self.tracer.enabled:
            self.tracer.request_end(
                req.req_id,
                terminal=state.value,
                n_generated=req.n_generated,
            )
        if self.flight.enabled:
            self.flight.record(
                "cancel",
                req_id=req.req_id,
                terminal=state.value,
                n_generated=req.n_generated,
                **_flight_trace(req),
            )
        return True

    def expire_deadlines(self, now: Optional[float] = None) -> List[Request]:
        """Retire every live request whose ``deadline_s`` has elapsed since
        submission. Runs at the top of :meth:`schedule` (gated on any
        deadline-bearing request existing), so an expired row's pages are
        back in the pool before this step's planning needs them."""
        now = time.perf_counter() if now is None else now
        out: List[Request] = []
        for req in list(self.waiting) + self.running:
            dl = req.params.deadline_s
            if dl is not None and now - req.submit_time >= dl:
                if self.cancel(req, RequestState.EXPIRED, now=now):
                    out.append(req)
        return out

    def _reclaim_for(self, req: Request) -> bool:
        """Free pages for ``req`` by preempting ONE strictly lower-priority
        victim. Returns False — after preempting ``req`` itself — when no
        such victim exists."""
        victim = None
        for cand in self.running:
            if cand.req_id > req.req_id and (
                victim is None or cand.req_id > victim.req_id
            ):
                victim = cand
        if victim is None:
            # req is the lowest-priority page-holder; it yields.
            self._preempt(req)
            return False
        self._preempt(victim)
        return True

    def _ensure_pages(self, req: Request, n_tokens: int) -> bool:
        """Cover ``n_tokens`` positions of ``req``'s table, preempting
        strictly lower-priority victims as needed. Returns False — after
        preempting ``req`` itself — when even that cannot free enough."""
        group = self.window_group
        while True:
            try:
                req.table.ensure(n_tokens, self.page_size, self.allocator)
                if group is not None:
                    req.window_table.ensure(
                        n_tokens, self.page_size, group.allocator
                    )
                    group.note_held(req.window_table)
                return True
            except OutOfPages:
                if not self._reclaim_for(req):
                    return False

    def _cow_write_page(self, req: Request, plan: StepPlan) -> bool:
        """Guarantee ``req`` exclusively owns the page it is about to write
        (position ``len_cached``). A shared page — refcount > 1, i.e.
        concurrent extenders of a cached partial page — is copied first:
        the plan gains a ``(slot, src, dst)`` device copy, the table swaps
        to the fresh page, and the shared original keeps its other readers
        and its trie registration. Returns False iff ``req`` was preempted
        while reclaiming a page for the copy."""
        if self.prefix_cache is None:
            return True
        while True:
            idx = req.len_cached // self.page_size
            if idx >= len(req.table.pages):
                return True  # write lands on a page ensure() will allocate
            page = req.table.pages[idx]
            if self.allocator.refcount(page) <= 1:
                return True
            try:
                (fresh,) = self.allocator.allocate(1)
            except OutOfPages:
                if not self._reclaim_for(req):
                    return False
                continue  # a victim's release may also have unshared it
            plan.copies.append((req.slot, page, fresh))
            req.table.pages[idx] = fresh
            self.allocator.unref(page)
            self.allocator.note_cow()
            if self.tracer.enabled:
                self.tracer.request_event(
                    req.req_id, "cow_copy", src=page, dst=fresh
                )
            return True

    # ------------------------------------------------------------ planning

    def schedule(self) -> StepPlan:
        """Build the next step's plan. Mutates scheduler state (admission,
        prefix-cache lookup, page allocation, copy-on-write, preemption);
        the engine then executes the device work and reports back via
        :meth:`note_prefilled` / :meth:`note_decode_dispatched` /
        :meth:`resolve_decoded`."""
        plan = StepPlan()

        # 0. Deadline sweep — free expired rows' pages before planning.
        if self._any_deadlines:
            self.expire_deadlines()

        # 1. Admit waiting requests into free slots, oldest first. Pages
        # beyond the prefix-cache match are allocated lazily below, so
        # admission itself cannot fail.
        for slot in range(self.max_slots):
            if not self.waiting:
                break
            if self.slots[slot] is None:
                self._admit(self.waiting.pop(0), slot, plan)

        # 2. Decode set reserves budget first: each running sequence
        # charges its full device write — one token, or a gamma-wide
        # speculative round — and is guaranteed exclusive ownership of
        # (copy-on-write) and pages for every position it may touch. A
        # round may overshoot the budget by at most cost-1; gating on
        # budget <= 0 (not budget < cost) avoids livelock when
        # token_budget < gamma. Requests that already issued
        # max_new_tokens sit out — their last readback resolves this step.
        budget = self.token_budget
        cost = self.gamma if self.gamma else 1
        for req in sorted(self.running, key=lambda r: r.req_id):
            if (
                req.state is not RequestState.DECODE
                or budget <= 0
                or req.n_issued >= req.params.max_new_tokens
            ):
                continue
            if not self._cow_write_page(req, plan):
                continue  # req itself was preempted reclaiming copy space
            # A gamma-wide round may overhang max_seq_len (the needed
            # positions always fit; only wasted chunk width runs past the
            # end) — don't allocate pages for the overhang, the model
            # routes those writes to the null page.
            need = min(
                req.len_cached + cost, self.pages_per_seq * self.page_size
            )
            if self._ensure_pages(req, need):
                plan.decode_slots.append(req.slot)
                budget -= cost

        # 3. Remaining budget goes to prefill pieces, highest priority
        # first (module docstring: whole granules but for a request's last
        # piece, and a sliver only where the step would else prefill
        # nothing). Prefill starts at the first uncached token (len_cached
        # covers the prefix-cache match).
        granule = self.prefill_granule
        for req in sorted(self.running, key=lambda r: r.req_id):
            if req.state is not RequestState.PREFILL or budget <= 0:
                continue
            slot = req.slot
            if not self._cow_write_page(req, plan):
                continue  # preempted; nothing was planned for it yet
            planned = req.len_cached
            while budget > 0:
                remaining = len(req.tokens) - 1 - planned
                if remaining <= 0:
                    break
                piece = min(remaining, self.max_prefill_chunk, budget)
                if piece < remaining and (piece >= granule or plan.prefill):
                    piece -= piece % granule
                if piece <= 0:
                    break
                if not self._ensure_pages(req, planned + piece):
                    break  # req was preempted; its plan entries are dropped
                plan.prefill.append((slot, piece))
                planned += piece
                budget -= piece
                if self.window_group is not None:
                    break  # one piece a step (module docstring)
            if req.state is not RequestState.PREFILL:
                # Preempted while growing: drop any pieces already planned
                # for its (now free) slot.
                plan.prefill = [
                    (s, c) for (s, c) in plan.prefill if s != slot
                ]
        # A prefill allocation above may have preempted a (lower-priority)
        # request that was already planned for decode or a CoW copy — keep
        # only entries whose slot still holds a live request (slots freed
        # mid-schedule stay free until the next schedule's admission pass).
        plan.decode_slots = [
            s for s in plan.decode_slots
            if self.slots[s] is not None
            and self.slots[s].state is RequestState.DECODE
        ]
        plan.copies = [
            (s, src, dst) for (s, src, dst) in plan.copies
            if self.slots[s] is not None
        ]
        # Validate planned host fetches against the trie: a fetch whose
        # request was preempted mid-schedule is KEPT as long as its trie
        # entry survived (the page idles with to-be-valid content and
        # re-serves the prefix), but one whose destination page was
        # recycled by later allocation pressure has nowhere valid to
        # land — _on_evict already dropped the entry and the
        # fetch-pending mark, so only the host pin needs releasing.
        if plan.fetches:
            pc = self.prefix_cache
            kept = []
            for fetch in plan.fetches:
                key, page, parent, toks, node = fetch
                if pc._full.get((parent, toks)) == (node, page):
                    kept.append(fetch)
                else:
                    pc.fetch_pending.discard(page)
                    pc.host.unpin(key)
            plan.fetches = kept
        if self.debug:
            self.allocator.check_invariants()
            if self.window_group is not None:
                self.window_group.allocator.check_invariants()
        return plan

    # ----------------------------------------------------------- execution

    def _register_filled(self, req: Request) -> None:
        """Register every newly completed full page of ``req`` in the
        prefix trie (dedup: an existing node for the same prefix wins and
        the private page is simply not cached). Pages whose tokens are
        still PENDING readback are skipped until resolved."""
        if (
            self.prefix_cache is None
            or req.slot is None
            or _adapter_bound(req)
        ):
            return
        page = self.page_size
        valid = req.len_cached
        if req.pending_idx:
            valid = min(valid, req.pending_idx[0])
        while (req.trie_pages + 1) * page <= valid:
            k = req.trie_pages
            req.trie_node, _ = self.prefix_cache.register_full(
                req.trie_node,
                tuple(req.tokens[k * page : (k + 1) * page]),
                req.table.pages[k],
            )
            req.trie_pages = k + 1

    def note_prefilled(self, slot: int, chunk: int) -> None:
        req = self.slots[slot]
        assert req is not None, f"prefill completion for empty slot {slot}"
        if self.tracer.enabled:
            self.tracer.request_event(
                req.req_id, "prefill_chunk",
                chunk=chunk, start=req.len_cached,
            )
        req.len_cached += chunk
        assert req.len_cached <= len(req.tokens) - 1, (
            f"request {req.req_id} prefilled past its last token"
        )
        if self.window_group is not None:
            self.window_group.trim(req.window_table, req.len_cached)
        self._register_filled(req)
        if req.remaining_prefill == 0:
            req.state = RequestState.DECODE

    def note_decode_dispatched(self, slot: int) -> Request:
        """One decode step was ISSUED for ``slot``: advance the host-known
        state now (cache position, placeholder token) so the next schedule
        can plan around it; the sampled value lands later via
        :meth:`resolve_decoded`. Returns the request so the engine can pair
        it with the readback even if the slot changes hands meanwhile."""
        req = self.slots[slot]
        assert req is not None, f"decode dispatch for empty slot {slot}"
        assert req.state is RequestState.DECODE
        req.len_cached += 1
        assert req.len_cached == len(req.tokens), (
            f"request {req.req_id} decode out of sync"
        )
        if self.window_group is not None:
            self.window_group.trim(req.window_table, req.len_cached)
        req.pending_idx.append(len(req.tokens))
        req.tokens.append(PENDING_TOKEN)
        return req

    def resolve_decoded(
        self, req: Request, token: int, now: Optional[float] = None
    ) -> Optional[Request]:
        """Fill in the sampled value for ``req``'s oldest pending decode.
        Returns the request when this token FINISHED it (caller retires +
        records metrics). Handles the overlap edge cases: a request already
        finished by an earlier resolve discards this (speculative) value;
        a stop-token finish rolls back any speculative dispatch issued
        after it."""
        if req.done:
            # Speculative decode issued the step after a stop token — the
            # value is discarded and the placeholder tail dropped.
            if req.pending_idx:
                pos = req.pending_idx.pop(0)
                del req.tokens[pos:]
            return None
        pos = req.pending_idx.pop(0)
        assert req.tokens[pos] == PENDING_TOKEN, (
            f"request {req.req_id} resolve out of order"
        )
        token = int(token)
        req.tokens[pos] = token
        req.generated.append(token)
        if req.first_token_time is None:
            req.first_token_time = (
                time.perf_counter() if now is None else now
            )
        self._register_filled(req)
        stop = req.params.stop_token
        # Advance per-request mods (grammar state machines) on EVERY
        # committed token, before the finish check — the state must stay
        # consistent even when this token does not finish the request.
        mods_done = (
            req.mods.note_token(token) if req.mods is not None else False
        )
        if (
            req.n_generated >= req.params.max_new_tokens
            or (stop is not None and token == stop)
            or _stops_on_sequence(req)
            or mods_done
        ):
            # Roll back anything issued speculatively past the finish: the
            # extra KV write is garbage beyond the sequence (masked, and
            # its pages are released at retire).
            del req.tokens[pos + 1 :]
            req.pending_idx.clear()
            if req.state is not RequestState.WAITING:
                req.len_cached = len(req.tokens) - 1
            return req
        return None

    def note_decoded(
        self, slot: int, token: int, now: Optional[float] = None
    ) -> Optional[Request]:
        """Synchronous dispatch + resolve in one call — the non-overlapped
        path and the scheduler-only tests."""
        req = self.note_decode_dispatched(slot)
        return self.resolve_decoded(req, token, now=now)

    def resolve_spec(
        self, req: Request, tokens: List[int], now: Optional[float] = None
    ) -> Optional[Request]:
        """Apply one speculative verify round to ``req``: the accepted
        draft tokens plus the correction, in order. Speculative rounds
        resolve synchronously — the host needs the per-row accepted count
        before it can plan the next round — so there are no PENDING
        placeholders; every appended token advances ``len_cached`` with it
        and the DECODE invariant (``len_cached == len(tokens) - 1``) holds
        between rounds. Truncates at max_new_tokens / the stop token: the
        fixed-gamma device program may emit past either, and the rejected
        or overshoot K/V needs no cleanup (``len_cached`` simply stops
        short; stale positions are masked and overwritten write-then-attend
        by the real continuation). Returns the request when the round
        finished it."""
        assert req.state is RequestState.DECODE and not req.pending_idx, (
            f"request {req.req_id} spec resolve in bad state"
        )
        assert req.len_cached == len(req.tokens) - 1, (
            f"request {req.req_id} spec resolve out of sync"
        )
        finished = False
        stop = req.params.stop_token
        for token in tokens:
            token = int(token)
            req.tokens.append(token)
            req.len_cached += 1
            req.generated.append(token)
            if req.first_token_time is None:
                req.first_token_time = (
                    time.perf_counter() if now is None else now
                )
            mods_done = (
                req.mods.note_token(token)
                if req.mods is not None
                else False
            )
            if (
                req.n_generated >= req.params.max_new_tokens
                or (stop is not None and token == stop)
                or _stops_on_sequence(req)
                or mods_done
            ):
                finished = True
                break
        self._register_filled(req)
        return req if finished else None
