"""Hierarchical KV: host-RAM page tier behind the device prefix trie.

Covers the tier in isolation (numpy pools standing in for device arrays)
and wired into the engine: spill on eviction, fetch on a host-trie hit,
bitwise token parity tier-on vs tier-off, the double-entry byte
cross-check against the XLA transfer ledger, restore-via-fetch shrinking
``restore_reprefill`` goodput waste, and snapshot ``host_keys`` wire
round-trips.
"""

import json
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.serving import (
    HostPageTier,
    InferenceEngine,
    RequestSnapshot,
    SamplingParams,
    restore_engine,
    snapshot_engine,
)
from distributed_pytorch_tpu.serving.kv_cache import chain_next


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


# ------------------------------------------------------------- tier (unit)


class TestHostPageTierUnit:
    """The tier alone, with numpy 'device' pools: every state transition,
    the O(1) gauges vs the O(n) sweep, and the teardown gate."""

    PAGE = 2

    def _tier(self, capacity=3):
        # Fake device pool: page p holds the constant p, so drained host
        # content is trivially checkable.
        device = np.arange(8, dtype=np.float32)[:, None, None, None]
        device = np.broadcast_to(device, (8, self.PAGE, 2, 4)).copy()
        tier = HostPageTier(
            {"target": device},
            num_host_pages=capacity,
            page_size=self.PAGE,
            gather_fn=lambda page: {"target": device[page]},
        )
        return tier, device

    def test_spill_drain_fetch_roundtrip(self):
        tier, device = self._tier()
        key = chain_next("root", (5, 7))
        assert tier.note_evict(3, key, (5, 7))
        # PENDING: matchable, counted resident, not yet drained.
        assert tier.match(key, (5, 7))
        assert not tier.match(key, (5, 8)), "token window must verify"
        assert tier.pages_resident == 1 and tier.pending_spills == 1
        tier.check_invariants()
        moved = tier.drain_spills()
        assert moved == device[3].nbytes
        assert tier.spill_bytes_total == moved
        assert tier.pending_spills == 0
        chunk = tier.chunks(key)["target"]
        np.testing.assert_array_equal(chunk, device[3])
        assert tier.fetches == 1
        assert tier.fetch_bytes_total == device[3].nbytes
        tier.assert_quiescent()

    def test_duplicate_key_refreshes_lru_only(self):
        tier, _ = self._tier()
        key = chain_next("root", (1, 2))
        assert tier.note_evict(1, key, (1, 2))
        tier.drain_spills()
        # Content-addressed: a re-spill of the same chain key is a no-op
        # write-back, not a second slot.
        assert not tier.note_evict(2, key, (1, 2))
        assert tier.spills == 1 and tier.pages_resident == 1
        tier.check_invariants()
        tier.assert_quiescent()

    def test_host_lru_evicts_oldest_unpinned(self):
        tier, _ = self._tier(capacity=2)
        ka = chain_next("root", (1, 2))
        kb = chain_next("root", (3, 4))
        kc = chain_next("root", (5, 6))
        tier.note_evict(1, ka, (1, 2))
        tier.note_evict(2, kb, (3, 4))
        tier.drain_spills()
        tier.pin(ka)  # a planned fetch protects the oldest entry
        assert tier.note_evict(3, kc, (5, 6))
        tier.drain_spills()
        # kb (oldest UNPINNED) went, ka survived its pin.
        assert tier.match(ka, (1, 2)) and not tier.match(kb, (3, 4))
        assert tier.host_evictions == 1
        tier.check_invariants()
        tier.unpin(ka)
        tier.assert_quiescent()

    def test_spill_dropped_when_all_pinned(self):
        tier, _ = self._tier(capacity=1)
        ka = chain_next("root", (1, 2))
        tier.note_evict(1, ka, (1, 2))
        tier.drain_spills()
        tier.pin(ka)
        kb = chain_next("root", (3, 4))
        assert not tier.note_evict(2, kb, (3, 4))
        assert tier.spill_drops == 1
        assert tier.match(ka, (1, 2))
        tier.unpin(ka)
        tier.check_invariants()

    def test_quiescence_rejects_pins_and_undrained_spills(self):
        tier, _ = self._tier()
        key = chain_next("root", (9, 9))
        tier.note_evict(4, key, (9, 9))
        with pytest.raises(AssertionError):
            tier.assert_quiescent()  # undrained spill
        tier.drain_spills()
        tier.pin(key)
        with pytest.raises(AssertionError):
            tier.assert_quiescent()  # pinned entry
        tier.unpin(key)
        tier.assert_quiescent()


# --------------------------------------------------------- engine (parity)


def _engine(model, params, host_pages, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 9)
    kw.setdefault("token_budget", 16)
    kw.setdefault("max_prefill_chunk", 8)
    kw.setdefault("debug", True)
    return InferenceEngine(model, params, host_pages=host_pages, **kw)


# Disjoint 8-token prompts (two full pages each at page_size=4) so every
# prompt's pages evict the previous prompt's out of the 8-usable-page pool.
PROMPTS = [[i * 8 + j + 1 for j in range(8)] for i in range(5)]


def _run_working_set(eng):
    """Two passes over PROMPTS: pass 1 populates + spills, pass 2 should
    re-serve the spilled prefixes from the host tier."""
    outs = []
    for _ in range(2):
        for p in PROMPTS:
            rid = eng.submit(p, SamplingParams(max_new_tokens=4))
            eng.run()
            outs.append(eng.poll(rid).generated)
    return outs


class TestHostTierEngineParity:
    def test_token_parity_and_ledger_cross_check(self, model_and_params):
        """Working set 5x the device pool: tier-on serves prefixes from
        host RAM with BITWISE-identical tokens, and the tier's own byte
        counters match the XLA transfer ledger's tagged d2h/h2d rows
        exactly (double-entry bookkeeping)."""
        model, params = model_and_params
        off = _engine(model, params, host_pages=None)
        outs_off = _run_working_set(off)
        s_off = off.stats()
        off.close()

        on = _engine(model, params, host_pages=32, xla_ledger=True)
        outs_on = _run_working_set(on)
        s_on = on.stats()
        on.close()  # drains trailing spills, asserts both tiers quiescent

        assert outs_on == outs_off, "host tier changed generated tokens"
        assert s_on["prefix_tokens_hit_host"] > 0, "no host-tier hits"
        assert s_on["hostkv_spills"] > 0 and s_on["hostkv_fetches"] > 0
        # Tier-off never touches the host counters' namespace.
        assert "hostkv_spills" not in s_off
        assert s_off["prefix_tokens_hit_host"] == 0
        # Hit-rate split: device rate unchanged in meaning, total adds host.
        assert s_on["prefix_hit_rate_total"] > s_on["prefix_hit_rate"]
        # Double-entry byte cross-check, exact: the engine charged the
        # ledger the same sums the tier counted.
        md = on.xla.metadata()
        assert (
            md["bytes_d2h_by_tag"].get("hostkv_spill", 0)
            == on.hostkv.spill_bytes_total
        )
        assert (
            md["bytes_h2d_by_tag"].get("hostkv_fetch", 0)
            == on.hostkv.fetch_bytes_total
        )
        assert s_on["hostkv_spill_bytes"] > 0
        assert s_on["hostkv_fetch_bytes"] > 0
        # Zero leaked pages on either tier.
        assert s_on["pages_allocated"] == 0
        on.allocator.check_invariants()
        on.hostkv.check_invariants()

    def test_fetch_lands_before_dependent_decode(self, model_and_params):
        """A request admitted entirely through host pages (full-page
        prefix, one-token tail) decodes from fetched K/V in the same step
        the fetch executes — parity proves the h2d landed before the
        attention read."""
        model, params = model_and_params
        eng = _engine(model, params, host_pages=16)
        p = PROMPTS[0]
        ref_rid = eng.submit(p, SamplingParams(max_new_tokens=6))
        eng.run()
        ref = eng.poll(ref_rid).generated
        for q in PROMPTS[1:]:  # force p's pages host-side
            eng.submit(q, SamplingParams(max_new_tokens=2))
            eng.run()
        rid = eng.submit(p, SamplingParams(max_new_tokens=6))
        eng.run()
        assert eng.poll(rid).generated == ref
        assert eng.stats()["prefix_tokens_hit_host"] >= 4
        eng.close()


class TestQuantizedHostTier:
    """ISSUE 19: int8 KV pages ride spill/fetch unmodified — the tier is
    tree_map-generic, so the int8 pools and their 3-d float32 scale pools
    round-trip host RAM together, at the quantized byte size."""

    def test_int8_spill_fetch_round_trip(self, model_and_params):
        model, params = model_and_params
        off = _engine(model, params, host_pages=None, kv_quant="int8")
        outs_off = _run_working_set(off)
        off.close()

        on = _engine(
            model, params, host_pages=32, kv_quant="int8",
            paged_kernel=True, xla_ledger=True,
        )
        outs_on = _run_working_set(on)
        s_on = on.stats()
        on.close()

        # The tier must not change a token (the fetched int8 payload +
        # scales are the same content a re-prefill would re-quantize to).
        assert outs_on == outs_off, "host tier changed int8 tokens"
        assert s_on["prefix_tokens_hit_host"] > 0
        assert s_on["hostkv_spills"] > 0 and s_on["hostkv_fetches"] > 0
        # Double-entry bookkeeping stays exact at the quantized sizes.
        md = on.xla.metadata()
        assert (
            md["bytes_d2h_by_tag"].get("hostkv_spill", 0)
            == on.hostkv.spill_bytes_total
        )
        assert (
            md["bytes_h2d_by_tag"].get("hostkv_fetch", 0)
            == on.hostkv.fetch_bytes_total
        )
        assert s_on["pages_allocated"] == 0
        on.allocator.check_invariants()
        on.hostkv.check_invariants()

    def test_int8_page_bytes_are_quantized(self, model_and_params):
        """Per-page spill bytes = int8 payload + f32 scales, to the byte:
        layers x {K,V} x (page*Hkv*D x 1B + page*Hkv x 4B)."""
        model, params = model_and_params
        fp = _engine(model, params, host_pages=16)
        q8 = _engine(model, params, host_pages=16, kv_quant="int8")
        for eng in (fp, q8):
            _run_working_set(eng)
        n_layers = model.n_layers
        kv_heads = model.n_kv_heads or model.n_heads
        d = model.d_model // model.n_heads
        page = fp.page_size
        fp_page = n_layers * 2 * page * kv_heads * d * 4
        q8_page = n_layers * 2 * (page * kv_heads * d + page * kv_heads * 4)
        assert fp.hostkv.spill_bytes_total == fp.hostkv.counters()[
            "hostkv_spills"
        ] * fp_page
        assert q8.hostkv.spill_bytes_total == q8.hostkv.counters()[
            "hostkv_spills"
        ] * q8_page
        assert q8_page < fp_page / 2
        fp.close()
        q8.close()


# ------------------------------------------------- restore via host fetch


class TestRestoreViaHostFetch:
    def _warm_adopter(self, model, params, host_pages, prompt):
        """An adopter that ran ``prompt`` once and then had its pages
        evicted by disjoint work — host tier (when on) now holds the
        chain, device trie does not."""
        eng = _engine(
            model, params, host_pages=host_pages, goodput=True
        )
        eng.submit(prompt, SamplingParams(max_new_tokens=6))
        eng.run()
        for q in PROMPTS[1:]:
            eng.submit(q, SamplingParams(max_new_tokens=2))
            eng.run()
        if eng.goodput is not None:
            eng.goodput.reset()  # isolate the restore's waste
        return eng

    def test_restore_reprefill_waste_shrinks_with_host_tier(
        self, model_and_params
    ):
        """Satellite: ``restore_engine`` used to re-prefill recovered
        requests from token zero. With the snapshot's ``key_chain`` pages
        host-resident in the adopter, recovery goes through h2d fetch and
        the ``restore_reprefill`` goodput charge shrinks. Compared are the
        positions charged, not the seconds: a step of a toy engine takes a
        millisecond unless something compiles in it, and which side's
        restore meets a program for the first time (the fetch's, or a
        prefill width's) is no property of the host tier."""
        model, params = model_and_params
        prompt = PROMPTS[0]
        from tests.test_serving import offline_greedy

        ref = offline_greedy(model, params, prompt, 6)

        def victim_snapshot():
            victim = _engine(model, params, host_pages=None)
            rid = victim.submit(prompt, SamplingParams(max_new_tokens=6))
            while len(victim.poll(rid).generated) < 2:
                victim.step()
            snap = snapshot_engine(victim)
            victim.close()
            return snap

        results = {}
        for label, host_pages in (("host", 32), ("cold", None)):
            adopter = self._warm_adopter(
                model, params, host_pages, prompt
            )
            [rid] = restore_engine(
                adopter, victim_snapshot(), rebase_ids=True
            )
            hit_host0 = adopter.stats()["prefix_tokens_hit_host"]
            charged = []
            note_step = adopter.goodput.note_step

            def recording(dt_s, *, rework=None, **kw):
                charged.append((rework or {}).get("restore_reprefill", 0))
                return note_step(dt_s, rework=rework, **kw)

            adopter.goodput.note_step = recording
            adopter.run()
            assert adopter.poll(rid).generated == ref, (
                "restored stream diverged from offline decode"
            )
            results[label] = {
                "waste": adopter.goodput.wasted["restore_reprefill"],
                "positions": sum(charged),
                "host_hits": (
                    adopter.stats()["prefix_tokens_hit_host"] - hit_host0
                ),
            }
            adopter.close()

        assert results["host"]["host_hits"] >= 8, (
            "restore did not recover the prompt through the host tier"
        )
        assert results["cold"]["waste"] > 0, (
            "control restore should charge restore_reprefill"
        )
        assert results["host"]["positions"] < results["cold"]["positions"], (
            f"host-tier restore re-prefilled {results['host']['positions']} "
            f"positions, cold restore {results['cold']['positions']} — "
            "fetch recovery should shrink the reprefill charge"
        )


# --------------------------------------------------- snapshot host_keys


class TestSnapshotHostKeys:
    def test_host_keys_survive_wire_roundtrip(self, model_and_params):
        """``snapshot_engine`` records the host-resident continuation of
        each request's chain; the JSON codec round-trips it and old
        payloads without the field decode to ()."""
        model, params = model_and_params
        eng = _engine(model, params, host_pages=16)
        p = PROMPTS[0]
        eng.submit(p, SamplingParams(max_new_tokens=2))
        eng.run()
        for q in PROMPTS[1:3]:  # push p's pages to the host tier
            eng.submit(q, SamplingParams(max_new_tokens=2))
            eng.run()
        rid = eng.submit(p, SamplingParams(max_new_tokens=6))
        # Step once so the request is live with its fetched pages.
        eng.step()
        snap = snapshot_engine(eng)
        rec = next(r for r in snap.requests if r.req_id == rid)
        # The fetched pages re-entered the DEVICE trie; whatever stayed
        # host-only shows up in host_keys. Between the two tiers the full
        # two-page prompt chain must be accounted for.
        chain = []
        prev = "root"
        for i in range(0, 8, 4):
            prev = chain_next(prev, tuple(p[i : i + 4]))
            chain.append(prev)
        assert set(rec.trie_keys) | set(rec.host_keys) >= set(chain)
        # Wire round-trip.
        doc = json.loads(snap.to_json())
        back = type(snap).from_json(json.dumps(doc))
        rec2 = next(r for r in back.requests if r.req_id == rid)
        assert rec2.host_keys == rec.host_keys
        # Backward wire-compat: a pre-host-tier payload decodes to ().
        for entry in doc["requests"]:
            entry.pop("host_keys", None)
        old = type(snap).from_json(json.dumps(doc))
        assert all(r.host_keys == () for r in old.requests)
        eng.run()
        eng.close()
