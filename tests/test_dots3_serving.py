"""A model with learned sparse attention, windowed latent attention and
sigmoid-routed experts through ``InferenceEngine``: two pools a full layer and
pools of three widths under the ONE allocator, block table, prefix trie and
copy-on-write; the chip's share of the experts; the counters of what a decode
dispatch reads; the selected positions for who asks; what is refused.
Compared with ``benchmarks/reference/dots3_note.py`` on seeded weights at toy
widths, through logits (``dots3_toy``)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from dots3_toy import (  # noqa: E402
    LOGIT_TOL, SEED, TOY, reference, share, slice_experts, tokens, toy_program,
)
from distributed_pytorch_tpu.models import mla  # noqa: E402
from distributed_pytorch_tpu.obs.tracer import Tracer  # noqa: E402
from distributed_pytorch_tpu.ops import paged_attention as pa  # noqa: E402
from distributed_pytorch_tpu.serving import (  # noqa: E402
    InferenceEngine, SamplingParams,
)


ENGINE = dict(max_slots=3, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=11, prefix_cache=True)
HELD = (2, 5)  # this chip's share of the toy's 8 experts


@pytest.fixture(scope="module")
def held_program():
    cfg = share(HELD)
    weights = slice_experts(reference.make_weights(TOY, SEED), HELD)
    return (cfg, *toy_program(cfg, weights))


def engine_for(program, **kw):
    _, _, model, params = program
    return InferenceEngine(model, params, **{**ENGINE, **kw})


def serve(engine, prompts, new_tokens=8):
    ids = [engine.submit(p, SamplingParams(max_new_tokens=new_tokens))
           for p in prompts]
    engine.run()
    out = []
    for rid in ids:
        status = engine.poll(rid)
        assert status.state == "finished"
        out.append(list(status.generated))
    return out


def served_gap(program, prompt, generated):
    cfg, weights = program[:2]
    rows = [len(prompt) - 1 + i for i in range(len(generated))]
    logits = np.asarray(reference.logits_at(
        cfg, weights, list(prompt) + list(generated), rows))
    return logits.max(-1) - logits[np.arange(len(generated)), generated]


@pytest.mark.parametrize("kernel", [False, "xla", "interpret"])
@pytest.mark.parametrize("chunk", [1, 4, 32])
def test_chunked_prefill_then_paged_decode_matches_the_reference(
        held_program, chunk, kernel):
    prompt = tokens(38, seed=chunk)
    engine = engine_for(
        held_program, max_prefill_chunk=chunk, token_budget=chunk + 3,
        paged_kernel=kernel)
    (generated,) = serve(engine, [prompt], new_tokens=10)
    assert served_gap(held_program, prompt, generated).max() < LOGIT_TOL


def test_the_engine_finds_every_pool_of_every_width(held_program):
    """Two pools a full layer, one a sliding layer, three widths (the toy's
    16, 8 and 28 numbers, each in 128 lanes of float32): one allocator."""
    engine = engine_for(held_program, num_pages=9)
    leaves = jax.tree_util.tree_flatten_with_path(engine.cache)[0]
    names = sorted(path[-1].key for path, _ in leaves)
    assert names == ["cached_index"] * 2 + ["cached_latent"] * 4
    assert {leaf.shape for _, leaf in leaves} == {(9, 4, 128)}
    assert engine.latent_layers == 4 and engine.routed_layers == 3
    layers = engine.reads.layers
    assert layers["latent_sparse"] == layers["latent_window"] == 2
    # Six pools over four layers: 1.5 pools of 128 float32 lanes a layer.
    assert engine.stats()["page_bytes_per_token_layer"] == 6 * 128 * 4 // 4


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_a_prefix_hit_and_a_copy_on_write_serve_what_a_cold_engine_serves(
        held_program, kernel):
    """Two askers of one document side by side: the trie hands both the
    document's pages in ALL the pools (a hit that skipped the index keys
    would select among zeros), and the first to write on the shared last
    page copies it, in all of them."""
    document = tokens(27, seed=20)  # 6 whole pages and 3 tokens
    asks = [document + tokens(5, seed=21), document + tokens(6, seed=22)]
    engine = engine_for(held_program, paged_kernel=kernel)
    serve(engine, [document], new_tokens=1)
    before = engine.stats()
    served = serve(engine, asks, new_tokens=9)
    after = engine.stats()
    assert after["prefix_tokens_hit"] - before["prefix_tokens_hit"] >= 48
    assert after["cow_copies"] > before["cow_copies"]
    for prompt, generated in zip(asks, served):
        assert served_gap(held_program, prompt, generated).max() < LOGIT_TOL
    cold = engine_for(held_program, paged_kernel=kernel, prefix_cache=False)
    assert serve(cold, asks, new_tokens=9) == served


def test_the_counters_of_what_a_dispatch_reads(held_program):
    tracer = Tracer()
    engine = engine_for(held_program, paged_kernel="interpret", tracer=tracer)
    prompt = tokens(30, seed=5)
    serve(engine, [prompt], new_tokens=6)
    stats = engine.stats()
    # Decode feeds positions 29..34 (the prompt's last token, then five).
    positions = np.arange(29, 35)
    assert stats["decode_index_tokens_scored"] == int((positions + 1).sum())
    # A row alone: the table is narrower than the index kernel's block, ONE
    # block of its 16 pages of 4.
    assert stats["decode_index_tokens_fetched"] == 64 * len(positions)
    assert stats["decode_rows_grouped"] == 0
    assert stats["decode_kv_tokens_selected"] == 7 * len(positions)
    assert stats["decode_window_tokens_visible"] == 9 * len(positions)
    assert stats["decode_window_tokens_read"] == int(
        pa.window_tokens_read(positions, 9, 4).sum())
    steps = [e["args"] for e in tracer.events
             if e["name"] == "step" and e.get("ph") == "X"
             and "decode_index_tokens_scored" in e["args"]]
    assert len(steps) == len(positions)
    assert sum(a["decode_kv_tokens_selected"] for a in steps) == 42
    assert all(a["decode_index_tokens_scored_distinct"]
               == a["decode_index_tokens_scored"] for a in steps)
    # A layer's mean of the latents read: (2 x 7 + 2 x 12) / 4 at position 29.
    assert steps[0]["decode_kv_tokens_fetched"] == (2 * 7 + 2 * 12) // 4
    chosen = [e["args"] for e in tracer.events if e["name"] == "dsa.select"]
    assert len(chosen) == len(positions)
    assert chosen[0]["selected_share"] == pytest.approx(7 / 30)
    assert stats["decode_index_tokens_scored_distinct"] == sum(
        a["decode_index_tokens_scored"] for a in steps)


def test_askers_of_one_document_are_scored_as_a_group(
        held_program, monkeypatch):
    """Two questions on one cached document (6 whole pages: three of the
    index kernel's blocks at 2 pages a block) and one prompt of its own
    decode side by side. Through the kernels (interpreted) the index kernel
    scores the document's keys once for the two, and the tokens and every
    step's selected positions are those of the gather path's scores."""
    monkeypatch.setattr(pa, "INDEX_BLOCK_PAGES", 2)
    document = tokens(24, seed=60)
    prompts = [document + tokens(3, seed=61), document + tokens(4, seed=62),
               tokens(22, seed=63)]

    def run(kernel):
        tracer = Tracer()
        engine = engine_for(held_program, tracer=tracer, paged_kernel=kernel)
        serve(engine, [document + tokens(2, seed=59)], new_tokens=1)
        tracer.events.clear()
        before = engine.stats()["decode_index_tokens_fetched"]
        ids = [engine.submit(p, SamplingParams(max_new_tokens=6))
               for p in prompts]
        selected = []
        while not all(engine.poll(rid).finished for rid in ids):
            engine.step()
            selected.extend(np.asarray(a) for a in engine.selected_positions)
        steps = [e["args"] for e in tracer.events
                 if e["name"] == "step" and e.get("ph") == "X"
                 and "decode_index_tokens_scored" in e["args"]]
        served = [list(engine.poll(rid).generated) for rid in ids]
        stats = engine.stats()
        stats["decode_index_tokens_fetched"] -= before
        return served, selected, steps, stats

    served, selected, steps, stats = run("interpret")
    by_gather, gathered, gather_steps, gather_stats = run("xla")
    assert served == by_gather == run(False)[0]
    assert len(selected) == len(gathered) > 0
    for ours, theirs in zip(selected, gathered):
        # A row out of the dispatch selects nothing through the kernel (the
        # gather path scores its null page's first key).
        live = (ours >= 0).any(axis=(0, 2))
        assert live.any()
        assert np.array_equal(ours[:, live], theirs[:, live])
    for prompt, generated in zip(prompts, served):
        assert served_gap(held_program, prompt, generated).max() < LOGIT_TOL
    together = [a for a in steps if a["decode_rows"] == 3]
    assert together
    for a in together:
        assert a["decode_rows_grouped"] == 2
        # The document's 24 keys once, not twice: whole blocks of 8 keys,
        # so more than the distinct keys and fewer than the rows see.
        assert (a["decode_index_tokens_scored_distinct"]
                <= a["decode_index_tokens_fetched"]
                < a["decode_index_tokens_scored"])
        assert (a["decode_index_tokens_scored"]
                - a["decode_index_tokens_scored_distinct"]) == 24
    assert stats["decode_rows_grouped"] == sum(
        a["decode_rows_grouped"] for a in steps) >= 2 * len(together)
    assert stats["decode_index_tokens_fetched"] == sum(
        a["decode_index_tokens_fetched"] for a in steps)
    # The gather path's scoring groups nobody and reads every slot's table.
    assert gather_stats["decode_rows_grouped"] == 0
    whole = ENGINE["max_slots"] * ENGINE["max_seq_len"]
    assert all(a["decode_index_tokens_fetched"] == whole
               and a["decode_rows_grouped"] == 0 for a in gather_steps)


def test_the_selected_positions_come_back_to_who_asks(held_program):
    engine = engine_for(held_program, paged_kernel="interpret")
    rid = engine.submit(tokens(20, seed=8), SamplingParams(max_new_tokens=3))
    seen = []
    while not engine.poll(rid).finished:
        engine.step()
        seen.extend(np.asarray(a) for a in engine.selected_positions)
    assert seen and seen[0].shape == (2, ENGINE["max_slots"], 7)
    row = seen[0][:, engine.requests[rid].slot or 0]
    assert ((row >= 0) & (row <= 19)).all()
    assert all(len(set(layer.tolist())) == 7 for layer in row)
    gather = engine_for(held_program)  # the gather path masks, keeps no list
    serve(gather, [tokens(20, seed=8)], new_tokens=3)
    assert gather.selected_positions == []


@pytest.mark.parametrize("given, what", [
    (dict(kv_quant="int8"), "kv_quant"), (dict(host_pages=8), "host_pages"),
])
def test_what_knows_one_kind_of_page_is_refused(held_program, given, what):
    with pytest.raises(ValueError, match=f"latent layers.*{what}"):
        engine_for(held_program, **given)


def test_a_documents_index_keys_are_copied_in_runs(held_program, monkeypatch):
    """A document prefilled in ONE go holds neighbouring pages, in every
    pool (one table a sequence): the index kernel copies a block of
    neighbours (2 pages here) as one copy, the windowed latent call its
    window's 3 pages one by one (a block of 2: turns of one page), and the
    engine's ``decode_page_copies`` / ``decode_pages_in_runs``, in
    ``stats()`` and on the ``step`` slice, are a call of each counted by the
    kernels' copy loops walked a page at a time on the staged tables."""
    from page_copy_loops import index_copies_by_loop, latent_copies_by_loop

    monkeypatch.setattr(pa, "INDEX_BLOCK_PAGES", 2)
    document = tokens(24, seed=80)
    askers = [document + tokens(3, seed=81), document + tokens(4, seed=82)]
    tracer = Tracer()
    engine = engine_for(
        held_program, tracer=tracer, paged_kernel="interpret",
        max_prefill_chunk=32, token_budget=35)
    assert engine.reads.blocks == {"index": 2, "window": 2}
    dispatches, count = [], engine.reads.count

    def counted(tables, positions, traced):
        dispatches.append((tables.copy(), positions.copy(),
                           engine.reads.groups(tables, positions)))
        return count(tables, positions, traced)

    engine.reads.count = counted
    serve(engine, [document + tokens(2, seed=79)], new_tokens=1)
    served = serve(engine, askers, new_tokens=6)
    stats = engine.stats()
    steps = [e["args"] for e in tracer.events
             if e["name"] == "step" and e.get("ph") == "X"
             and "decode_page_copies" in e["args"]]
    assert len(steps) == len(dispatches) > 0
    for a, (tables, positions, groups) in zip(steps, dispatches):
        windows = pa.window_tables(tables, positions, 4, 9)
        rows = np.arange(len(tables), dtype=np.int32)
        index = index_copies_by_loop(tables, positions, *groups, 4, 2)
        window = latent_copies_by_loop(
            *windows[:2], rows, np.zeros_like(rows), 4, 2)
        assert window[1] == 0 and window[0] == 3 * len(tables)
        assert (a["decode_page_copies"], a["decode_pages_in_runs"]) == (
            index[0] + window[0], index[1])
        # The document's three blocks of two neighbours, at least, in runs
        # (once where the askers are served as a group).
        assert (np.diff(tables[:, :6], axis=1) == 1).all()
        assert a["decode_pages_in_runs"] >= 6
    assert stats["decode_page_copies"] == sum(
        a["decode_page_copies"] for a in steps)
    assert stats["decode_pages_in_runs"] == sum(
        a["decode_pages_in_runs"] for a in steps) > 0
    gather = engine_for(held_program, max_prefill_chunk=32, token_budget=35)
    serve(gather, [document + tokens(2, seed=79)], new_tokens=1)
    assert serve(gather, askers, new_tokens=6) == served
    assert gather.stats()["decode_page_copies"] == 0
