"""Serving subsystem tests: continuous-batching parity with offline
generate(), scheduler/allocator invariants under randomized load, preemption
determinism, and admission control. All on CPU (conftest pins
JAX_PLATFORMS=cpu) — the engine is deterministic there by construction.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.generation import generate
from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.serving import (
    HostPageTier,
    InferenceEngine,
    OutOfPages,
    PagedBlockAllocator,
    PrefixCache,
    QueueFull,
    Request,
    RequestTooLong,
    SamplingParams,
    Scheduler,
)
from distributed_pytorch_tpu.serving.kv_cache import NULL_PAGE, BlockTable


def tiny_lm(**kw):
    return TransformerLM(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        dtype=jnp.float32, **kw,
    )


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def offline_greedy(model, params, prompt, max_new):
    out = generate(
        model, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=max_new, temperature=0.0, rng=jax.random.PRNGKey(0),
    )
    return np.asarray(out)[0, len(prompt):].tolist()


STOP_PROMPTS = ([6, 1, 9, 9], [5, 7, 11], [3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8])


def prompt_with_a_late_stop(model, params, max_new=8):
    """A prompt, its greedy reference and a token at index >= 2 of that
    reference which did not occur before it: a stop token that ends the
    request early and not at once. A random model's greedy stream is often
    one token over and over, so the prompt is looked for, not assumed."""
    for prompt in STOP_PROMPTS:
        ref = offline_greedy(model, params, prompt, max_new)
        late = [t for i, t in enumerate(ref) if i >= 2 and t not in ref[:i]]
        if late:
            return prompt, ref, late[0]
    raise AssertionError("no prompt of STOP_PROMPTS has a late new token")


# ---------------------------------------------------------------- allocator


class TestPagedBlockAllocator:
    def test_null_page_reserved(self):
        alloc = PagedBlockAllocator(4)
        pages = alloc.allocate(3)
        assert NULL_PAGE not in pages
        assert sorted(pages) == [1, 2, 3]

    def test_all_or_nothing(self):
        alloc = PagedBlockAllocator(4)
        alloc.allocate(2)
        with pytest.raises(OutOfPages):
            alloc.allocate(2)
        # the failed call took nothing
        assert alloc.num_free == 1
        alloc.check_invariants()

    def test_double_free_detected(self):
        alloc = PagedBlockAllocator(4)
        pages = alloc.allocate(1)
        alloc.free(pages)
        with pytest.raises(AssertionError):
            alloc.free(pages)

    def test_block_table_grow_and_release(self):
        alloc = PagedBlockAllocator(8)
        table = BlockTable()
        assert table.ensure(5, 2, alloc) == 3  # ceil(5/2)
        assert table.ensure(6, 2, alloc) == 0  # already covered
        assert table.ensure(7, 2, alloc) == 1
        row = table.as_row(6)
        assert row.dtype == np.int32
        assert list(row[4:]) == [NULL_PAGE, NULL_PAGE]
        assert table.release(alloc) == 4
        alloc.check_invariants()
        assert alloc.num_free == 7


def assert_gauges_match_sweep(alloc):
    """The O(1) page-state gauges (what the engine exports every step)
    must equal an independent full sweep of the allocator's structures."""
    c = alloc.counters()
    assert c["pages_free"] == len(alloc._free)
    assert c["pages_referenced"] == len(alloc._ref)
    assert c["pages_cached_idle"] == len(alloc._idle)
    assert (
        c["pages_free"] + c["pages_referenced"] + c["pages_cached_idle"]
        == alloc.num_pages - 1
    )


# ---------------------------------------------------------- scheduler props


class TestSchedulerInvariants:
    def _drive(self, sched, plan):
        """Simulate the device side of one plan: complete every prefill
        chunk, then emit an arbitrary token for every decode slot."""
        finished = []
        for slot, chunk in plan.prefill:
            sched.note_prefilled(slot, chunk)
        for slot in plan.decode_slots:
            done = sched.note_decoded(slot, token=1, now=0.0)
            if done is not None:
                sched.retire(done, now=0.0)
                finished.append(done)
        return finished

    def test_no_block_leaked_over_randomized_cycles(self):
        """1k randomized submit/step cycles against a small pool: allocator
        invariants hold at every step and every page is free at the end."""
        rng = random.Random(1234)
        alloc = PagedBlockAllocator(17)
        sched = Scheduler(
            alloc, max_slots=4, page_size=2, pages_per_seq=8,
            token_budget=8, max_prefill_chunk=4, debug=True,
        )
        next_id = 0
        live = {}
        for cycle in range(1000):
            if rng.random() < 0.4 and len(live) < 32:
                prompt = [rng.randrange(48) for _ in range(rng.randint(1, 9))]
                req = Request(
                    req_id=next_id, prompt=prompt,
                    params=SamplingParams(
                        max_new_tokens=rng.randint(1, 16 - len(prompt))
                    ),
                )
                live[next_id] = req
                sched.add(req)
                next_id += 1
            plan = self._drive(sched, sched.schedule())
            for req in plan:
                del live[req.req_id]
            alloc.check_invariants()
            assert_gauges_match_sweep(alloc)
            for req in live.values():
                # every live table is page-aligned with what's cached
                assert len(req.table) >= PagedBlockAllocator.pages_needed(
                    req.len_cached, 2
                )
        # drain whatever is left
        for _ in range(2000):
            if not sched.has_work:
                break
            for req in self._drive(sched, sched.schedule()):
                del live[req.req_id]
        assert not sched.has_work
        assert not live
        alloc.check_invariants()
        assert_gauges_match_sweep(alloc)
        assert alloc.num_free == 16  # every allocatable page returned

    def test_preemption_only_evicts_lower_priority(self):
        """With a pool that fits one sequence, the oldest request finishes
        first — newer ones get preempted, never the oldest."""
        alloc = PagedBlockAllocator(5)  # 4 usable pages
        sched = Scheduler(
            alloc, max_slots=2, page_size=2, pages_per_seq=4,
            token_budget=8, max_prefill_chunk=4, debug=True,
        )
        reqs = [
            Request(req_id=i, prompt=[1, 2, 3],
                    params=SamplingParams(max_new_tokens=5))
            for i in range(2)
        ]
        for r in reqs:
            sched.add(r)
        order = []
        for _ in range(200):
            if not sched.has_work:
                break
            order.extend(
                r.req_id
                for r in TestSchedulerInvariants._drive(self, sched,
                                                        sched.schedule())
            )
        assert order and order[0] == 0, "oldest request must finish first"
        assert reqs[0].preempt_count == 0, (
            "highest-priority request must never be preempted"
        )
        assert reqs[1].preempt_count > 0
        alloc.check_invariants()
        assert alloc.num_free == 4


# ------------------------------------------------------------ prefix cache


class TestPrefixCacheTrie:
    def test_full_chain_lookup_refs_pages(self):
        alloc = PagedBlockAllocator(8)
        cache = PrefixCache(alloc, page_size=2)
        p1, p2 = alloc.allocate(2)
        n1, registered = cache.register_full(PrefixCache.ROOT, (1, 2), p1)
        assert registered
        n2, _ = cache.register_full(n1, (3, 4), p2)
        alloc.free([p1, p2])  # refcount 0 -> cached-idle, not freed
        assert alloc.num_idle == 2
        pages, matched, node = cache.lookup([1, 2, 3, 4, 5])
        assert pages == [p1, p2] and matched == 4 and node == n2
        assert alloc.refcount(p1) == 1 and alloc.refcount(p2) == 1
        alloc.check_invariants()

    def test_lookup_never_consumes_last_token(self):
        """The decode step must always be fed at least one real token, so
        a fully cached prompt still leaves its final token uncached."""
        alloc = PagedBlockAllocator(8)
        cache = PrefixCache(alloc, page_size=2)
        (p1,) = alloc.allocate(1)
        cache.register_full(PrefixCache.ROOT, (1, 2), p1)
        alloc.free([p1])
        pages, matched, _ = cache.lookup([1, 2])  # limit is len - 1 = 1
        assert pages == [] and matched == 0
        assert alloc.num_idle == 1  # untouched

    def test_partial_match_requires_complete_tuple(self):
        """A prefix-of-partial hit would hand out a page whose registered
        tail diverges from the new prompt — must be a miss."""
        alloc = PagedBlockAllocator(8)
        cache = PrefixCache(alloc, page_size=4)
        (p1,) = alloc.allocate(1)
        assert cache.register_partial(PrefixCache.ROOT, (7, 8, 9), p1)
        alloc.free([p1])
        pages, matched, _ = cache.lookup([7, 8, 1, 1, 1])
        assert matched == 0 and pages == []
        pages, matched, _ = cache.lookup([7, 8, 9, 1, 1])
        assert matched == 3 and pages == [p1]
        alloc.check_invariants()

    def test_register_dedupes_and_existing_page_wins(self):
        alloc = PagedBlockAllocator(8)
        cache = PrefixCache(alloc, page_size=2)
        p1, p2 = alloc.allocate(2)
        n1, first = cache.register_full(PrefixCache.ROOT, (1, 2), p1)
        n2, second = cache.register_full(PrefixCache.ROOT, (1, 2), p2)
        assert first and not second and n1 == n2
        alloc.free([p1, p2])
        assert alloc.num_idle == 1  # p2 stayed private and freed normally
        pages, _, _ = cache.lookup([1, 2, 3])
        assert pages == [p1]
        alloc.check_invariants()

    def test_eviction_removes_trie_entries(self):
        alloc = PagedBlockAllocator(4)  # 3 usable pages
        cache = PrefixCache(alloc, page_size=2)
        pages = alloc.allocate(3)
        node = PrefixCache.ROOT
        for i, p in enumerate(pages):
            node, _ = cache.register_full(node, (i, i), p)
        alloc.free(pages)
        assert alloc.num_idle == 3
        alloc.allocate(2)  # pressure: evicts the two LRU-oldest idle pages
        assert alloc.evictions == 2
        assert cache.num_nodes == 1
        # the chain head was evicted first, so the survivor is unreachable
        _, matched, _ = cache.lookup([0, 0, 1, 1, 2, 2, 9])
        assert matched == 0
        alloc.check_invariants()


class TestCowAllocatorProperty:
    PREFIXES = [[1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 9, 9], [4, 4]]

    def test_randomized_interleaving_no_leaks_refcounts_exact(self):
        """1.2k randomized submit/prefill/decode/retire/preempt/evict
        cycles over the refcounted CoW allocator with prefix caching on a
        deliberately tiny pool: after every cycle the allocator invariants
        hold AND every page's refcount equals the number of live block
        tables holding it; at drain nothing leaked. A host page tier
        (deliberately smaller than the churn needs) rides the same
        cycles, so spills and fetches race device eviction — its O(1)
        free/resident gauges are cross-asserted against the O(n) sweep
        after every cycle too, and it must be quiescent at drain."""
        rng = random.Random(99)
        alloc = PagedBlockAllocator(21)
        cache = PrefixCache(alloc, page_size=2)
        pool = np.zeros((21, 2, 1, 2), np.float32)
        tier = HostPageTier(
            {"target": pool}, num_host_pages=6, page_size=2,
            gather_fn=lambda page: {"target": pool[page]},
        )
        cache.host = tier
        sched = Scheduler(
            alloc, max_slots=4, page_size=2, pages_per_seq=8,
            token_budget=8, max_prefill_chunk=4,
            prefix_cache=cache, debug=True,
        )
        next_id = 0
        live = {}

        def check_refcounts():
            readers = {}
            for req in sched.running:
                for p in req.table.pages:
                    readers[p] = readers.get(p, 0) + 1
            for p in range(1, alloc.num_pages):
                assert alloc.refcount(p) == readers.get(p, 0), (
                    f"page {p}: refcount {alloc.refcount(p)} != "
                    f"{readers.get(p, 0)} readers"
                )

        def drive_one():
            plan = sched.schedule()
            # Mirror the engine's step order for the host tier: drain the
            # spills this schedule staged, then execute its fetches
            # (stage chunks, unpin, clear the fetch-pending guard).
            tier.drain_spills()
            for key, page, _parent, _toks, _node in plan.fetches:
                tier.chunks(key)
                tier.unpin(key)
                cache.fetch_pending.discard(page)
            for slot, chunk in plan.prefill:
                sched.note_prefilled(slot, chunk)
            for slot in plan.decode_slots:
                # tiny token alphabet so generated streams collide and the
                # trie caches (and CoW-shares) decode-time pages too
                done = sched.note_decoded(
                    slot, token=rng.randrange(4), now=0.0
                )
                if done is not None:
                    sched.retire(done, now=0.0)
                    del live[done.req_id]

        def check_host_gauges():
            # O(1) gauges vs an independent O(n) sweep, plus the tier's
            # own partition invariants — same contract as the allocator.
            assert tier.pages_resident == len(tier._entries)
            assert tier.pages_free == len(tier._free_slots)
            assert tier.pages_resident + tier.pages_free == tier.capacity
            tier.check_invariants()

        for _ in range(1200):
            if rng.random() < 0.45 and len(live) < 40:
                prefix = self.PREFIXES[rng.randrange(3)]
                tail = [rng.randrange(48) for _ in range(rng.randint(0, 5))]
                prompt = (prefix + tail)[:11]
                req = Request(
                    req_id=next_id, prompt=prompt,
                    params=SamplingParams(
                        max_new_tokens=rng.randint(1, 16 - len(prompt)),
                    ),
                )
                live[next_id] = req
                sched.add(req)
                next_id += 1
            drive_one()
            alloc.check_invariants()
            assert_gauges_match_sweep(alloc)
            check_refcounts()
            check_host_gauges()
        for _ in range(4000):
            if not sched.has_work:
                break
            drive_one()
        assert not sched.has_work and not live
        alloc.check_invariants()
        assert_gauges_match_sweep(alloc)
        check_refcounts()
        check_host_gauges()
        tier.assert_quiescent()
        assert alloc.num_allocated == 0
        assert alloc.num_free == 20, "pages leaked"
        assert cache.stats()["prefix_hit_rate"] > 0
        assert alloc.evictions > 0, "pool was sized to force eviction"
        s = cache.stats()
        assert tier.spills > 0, "churn was sized to force spills"
        assert tier.fetches > 0 and s["prefix_tokens_hit_host"] > 0, (
            "churn was sized so host fetches race device eviction"
        )
        assert tier.host_evictions > 0, (
            "host tier was sized smaller than the spill stream"
        )


# ------------------------------------------------------------- engine parity


class TestEngineParity:
    PROMPTS = [[5, 7, 11, 2, 9, 3], [1, 4, 8], [2, 2, 3, 17, 40], [6, 1, 9, 9]]

    def test_continuous_batching_matches_offline_generate(
        self, model_and_params
    ):
        """Greedy continuous batching — including requests submitted
        mid-flight — is token-identical to each prompt decoded alone with
        offline generate()."""
        model, params = model_and_params
        refs = [
            offline_greedy(model, params, p, 14 - len(p))
            for p in self.PROMPTS
        ]
        eng = InferenceEngine(
            model, params, max_slots=4, max_seq_len=64, page_size=4,
            token_budget=16, max_prefill_chunk=8,
        )
        ids = [
            eng.submit(p, SamplingParams(max_new_tokens=14 - len(p)))
            for p in self.PROMPTS[:2]
        ]
        for _ in range(3):
            eng.step()  # the late submissions join a half-drained batch
        ids += [
            eng.submit(p, SamplingParams(max_new_tokens=14 - len(p)))
            for p in self.PROMPTS[2:]
        ]
        eng.run()
        for rid, ref in zip(ids, refs):
            assert eng.poll(rid).generated == ref
        stats = eng.stats()
        assert stats["requests_completed"] == 4
        assert stats["pages_allocated"] == 0

    def test_preempted_sequence_reproduces_identical_tokens(
        self, model_and_params
    ):
        """A pool too small for all requests forces preemption; resumed
        sequences still emit exactly the offline token stream."""
        model, params = model_and_params
        prompts = self.PROMPTS[:3]
        refs = [offline_greedy(model, params, p, 8) for p in prompts]
        eng = InferenceEngine(
            model, params, max_slots=3, max_seq_len=16, page_size=2,
            num_pages=10, token_budget=8, max_prefill_chunk=4,
        )
        ids = [
            eng.submit(p, SamplingParams(max_new_tokens=8)) for p in prompts
        ]
        eng.run()
        assert eng.stats()["preemptions"] > 0, (
            "pool was sized to force preemption"
        )
        assert any(eng.poll(r).preempt_count > 0 for r in ids)
        for rid, ref in zip(ids, refs):
            assert eng.poll(rid).generated == ref
        eng.allocator.check_invariants()
        assert eng.allocator.num_free == 9

    def test_sampled_stream_independent_of_batch_composition(
        self, model_and_params
    ):
        """fold_in(seed, token_index) keys: the same request samples the
        same tokens whether it runs alone or beside other requests."""
        model, params = model_and_params
        sp = SamplingParams(max_new_tokens=10, temperature=1.0, seed=42)
        eng = InferenceEngine(model, params, max_slots=2, max_seq_len=32,
                              page_size=4)
        solo = eng.submit([5, 7, 11], sp)
        eng.run()
        eng2 = InferenceEngine(model, params, max_slots=2, max_seq_len=32,
                               page_size=4)
        eng2.submit(
            [1, 2, 3, 4],
            SamplingParams(max_new_tokens=6, temperature=0.7, seed=7),
        )
        both = eng2.submit([5, 7, 11], sp)
        eng2.run()
        assert eng.poll(solo).generated == eng2.poll(both).generated

    def test_stop_token_ends_request_early(self, model_and_params):
        model, params = model_and_params
        prompt, ref, stop = prompt_with_a_late_stop(model, params)
        eng = InferenceEngine(model, params, max_slots=2, max_seq_len=32,
                              page_size=4)
        rid = eng.submit(
            prompt, SamplingParams(max_new_tokens=8, stop_token=stop)
        )
        eng.run()
        # stop token included
        assert eng.poll(rid).generated == ref[:ref.index(stop) + 1]


# ------------------------------------------------------ prefix-cache parity


class TestPrefixCachingParity:
    PREFIX = [5, 7, 11, 2, 9, 3, 8, 1]  # two full pages at page_size=4

    def _engine(self, model, params, **kw):
        kw.setdefault("max_slots", 4)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("page_size", 4)
        kw.setdefault("token_budget", 16)
        kw.setdefault("max_prefill_chunk", 8)
        kw.setdefault("debug", True)
        return InferenceEngine(model, params, **kw)

    def test_cached_generation_identical_to_cold(self, model_and_params):
        """A second request sharing the first's prompt prefix starts
        prefill past the cached pages yet emits the exact offline
        stream."""
        model, params = model_and_params
        p1 = self.PREFIX + [4, 6]
        p2 = self.PREFIX + [2, 13]
        ref1 = offline_greedy(model, params, p1, 6)
        ref2 = offline_greedy(model, params, p2, 6)
        eng = self._engine(model, params)
        a = eng.submit(p1, SamplingParams(max_new_tokens=6))
        eng.run()
        assert eng.stats()["prefix_tokens_hit"] == 0  # cold start
        b = eng.submit(p2, SamplingParams(max_new_tokens=6))
        eng.run()
        assert eng.poll(a).generated == ref1
        assert eng.poll(b).generated == ref2
        s = eng.stats()
        assert s["prefix_tokens_hit"] >= len(self.PREFIX)
        assert s["prefix_hit_rate"] > 0
        assert s["cached_tokens_admitted"] >= len(self.PREFIX)
        assert s["ttft_s_hit_count"] == 1 and s["ttft_s_miss_count"] == 1
        eng.allocator.check_invariants()

    def test_shared_partial_page_copy_on_write_parity(
        self, model_and_params
    ):
        """Two multi-turn continuations both extend the SAME cached partial
        page concurrently: the scheduler must copy-on-write for one of
        them, and both still match offline decode exactly."""
        model, params = model_and_params
        base = [5, 7, 11, 2, 9]
        ref0 = offline_greedy(model, params, base, 2)
        eng = self._engine(model, params)
        r0 = eng.submit(base, SamplingParams(max_new_tokens=2))
        eng.run()
        assert eng.poll(r0).generated == ref0
        # 6 cached tokens = 1 full page + 2 in the retired partial page
        hist = base + ref0[:1]
        conts = [hist + [3], hist + [17]]
        refs = [offline_greedy(model, params, p, 5) for p in conts]
        ids = [
            eng.submit(p, SamplingParams(max_new_tokens=5)) for p in conts
        ]
        eng.run()
        for rid, ref in zip(ids, refs):
            assert eng.poll(rid).generated == ref
        assert eng.scheduler.cow_copies >= 1
        assert eng.stats()["prefix_tokens_hit"] > 0
        eng.allocator.check_invariants()
        assert eng.allocator.num_allocated == 0

    def test_eviction_under_pressure_keeps_parity(self, model_and_params):
        """A pool too small to retain every retired prefix forces LRU
        eviction of cached-idle pages; outputs stay exact throughout."""
        model, params = model_and_params
        eng = self._engine(
            model, params, max_slots=2, max_seq_len=16, page_size=2,
            num_pages=10, token_budget=8, max_prefill_chunk=4,
        )
        prompts = [[i, i + 1, i + 2] for i in range(0, 12, 3)]
        refs = [offline_greedy(model, params, p, 5) for p in prompts]
        ids = []
        for p in prompts:
            ids.append(eng.submit(p, SamplingParams(max_new_tokens=5)))
            eng.run()
        for rid, ref in zip(ids, refs):
            assert eng.poll(rid).generated == ref
        assert eng.allocator.evictions > 0
        eng.allocator.check_invariants()

    def test_feature_toggles_do_not_change_tokens(self, model_and_params):
        """prefix_cache / overlap on or off is a pure perf choice: sampled
        streams are bitwise identical across all four combinations."""
        model, params = model_and_params
        prompts = TestEngineParity.PROMPTS
        outs = []
        for kw in (
            {},
            {"prefix_cache": False},
            {"overlap": False},
            {"prefix_cache": False, "overlap": False},
        ):
            eng = self._engine(model, params, **kw)
            ids = [
                eng.submit(
                    p,
                    SamplingParams(
                        max_new_tokens=14 - len(p), temperature=0.8, seed=3
                    ),
                )
                for p in prompts
            ]
            eng.run()
            outs.append([eng.poll(r).generated for r in ids])
        assert outs[0] == outs[1] == outs[2] == outs[3]

    def test_overlap_speculative_stop_leaves_no_leaks(
        self, model_and_params
    ):
        """Under overlap a stop token is detected one step late; the
        speculative dispatch past it must be rolled back without leaking
        pages or placeholder tokens."""
        model, params = model_and_params
        prompt, ref, stop = prompt_with_a_late_stop(model, params)
        want = ref[:ref.index(stop) + 1]
        eng = self._engine(model, params, overlap=True)
        rid = eng.submit(
            prompt, SamplingParams(max_new_tokens=8, stop_token=stop)
        )
        eng.run()
        assert eng.poll(rid).generated == want
        req = eng.requests[rid]
        assert req.tokens == prompt + want
        assert not req.pending_idx
        assert eng.allocator.num_allocated == 0
        eng.allocator.check_invariants()

    def test_queue_token_budget_counts_only_uncached(self, model_and_params):
        """max_queue_tokens bounds queued UNCACHED prefill work: a prompt
        whose prefix is cached costs only its tail against the budget."""
        model, params = model_and_params
        long1 = self.PREFIX + [4, 6]
        long2 = self.PREFIX + [2, 13]
        eng = self._engine(model, params, max_queue_tokens=10)
        eng.submit(long1, SamplingParams(max_new_tokens=4))
        with pytest.raises(QueueFull):
            eng.submit(long2, SamplingParams(max_new_tokens=4))
        eng.run()
        # PREFIX's pages are cached now: the same prompts cost ~1 uncached
        # token each, so both fit the budget that just rejected one.
        eng.submit(long2, SamplingParams(max_new_tokens=4))
        eng.submit(self.PREFIX + [1, 1], SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.stats()["rejected_queue_full"] == 1


# --------------------------------------------------------------- admission


class TestAdmission:
    def test_queue_full_backpressure(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(
            model, params, max_slots=1, max_seq_len=16, page_size=4,
            max_queue=2,
        )
        eng.submit([1, 2], SamplingParams(max_new_tokens=2))
        eng.submit([3, 4], SamplingParams(max_new_tokens=2))
        with pytest.raises(QueueFull):
            eng.submit([5, 6], SamplingParams(max_new_tokens=2))
        eng.run()  # queue drains; admission reopens
        rid = eng.submit([5, 6], SamplingParams(max_new_tokens=2))
        eng.run()
        assert eng.poll(rid).finished
        assert eng.stats()["rejected_queue_full"] == 1

    def test_request_too_long_rejected_up_front(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(model, params, max_slots=1, max_seq_len=16,
                              page_size=4)
        with pytest.raises(RequestTooLong):
            eng.submit(list(range(12)), SamplingParams(max_new_tokens=8))

    def test_empty_prompt_rejected(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(model, params, max_slots=1, max_seq_len=16,
                              page_size=4)
        with pytest.raises(RequestTooLong):
            eng.submit([], SamplingParams(max_new_tokens=2))

    def test_latency_metrics_populated(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(model, params, max_slots=2, max_seq_len=32,
                              page_size=4)
        for p in ([1, 2, 3], [4, 5]):
            eng.submit(p, SamplingParams(max_new_tokens=4))
        eng.run()
        s = eng.stats()
        assert s["ttft_s_count"] == 2
        assert s["e2e_s_count"] == 2
        assert s["tpot_s_count"] == 2
        assert s["ttft_s_p50"] > 0
        assert s["tokens_generated"] == 8
