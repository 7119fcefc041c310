"""A model with latent attention and routed experts through
``InferenceEngine``: ONE latent pool a layer under the allocator, the block
tables, the prefix trie and copy-on-write that K/V pools have always had; the
chip's share of the experts; the new counters. Everything is compared with
``benchmarks/reference/deepseek_v2.py`` on seeded weights at toy widths,
through logits: a served (greedy) token's reference logit has to lie within
``LOGIT_TOL`` of the reference's best at its position (``deepseek_toy``)."""

import jax
import numpy as np
import pytest

from deepseek_toy import (
    LOGIT_TOL, SEED, TOY, reference, share, slice_experts, tokens, toy_program,
)

from distributed_pytorch_tpu.obs.tracer import Tracer
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams
from distributed_pytorch_tpu.serving.mesh import make_serving_mesh

ENGINE = dict(max_slots=3, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=11, prefix_cache=True)
HELD = (2, 5)  # this chip's share of the toy's 8 experts


@pytest.fixture(scope="module")
def program():
    """The toy holding experts 2-4 of 8, as the benchmark's cell holds 8 of
    64: ``(cfg, weights, model, params)``."""
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
    """How far below the reference's best logit each served token's
    reference logit lies, at the positions that predicted them."""
    cfg, weights = program[:2]
    rows = [len(prompt) - 1 + i for i in range(len(generated))]
    logits = np.asarray(reference.logits_at(
        cfg, weights, list(prompt) + list(generated), rows))
    return logits.max(-1) - logits[np.arange(len(generated)), generated]


@pytest.mark.parametrize("kernel", [False, "interpret"])
@pytest.mark.parametrize("chunk", [1, 4, 32])
def test_chunked_prefill_then_paged_decode_matches_the_reference(
        program, chunk, kernel):
    """Prefill in padded pieces of every cap, then decode through latent
    pages (the gather loop, and the Pallas kernel interpreted) and the held
    experts, as served; against the reference's one expanded pass."""
    prompt = tokens(38, seed=chunk)
    engine = engine_for(
        program, max_prefill_chunk=chunk, token_budget=chunk + 3,
        paged_kernel=kernel)
    (generated,) = serve(engine, [prompt], new_tokens=10)
    assert served_gap(program, prompt, generated).max() < LOGIT_TOL


def test_the_engine_reads_the_kind_of_page_from_the_model(program):
    """ONE pool a layer, ``[num_pages, page, lanes]``, and its bytes a
    token in ``stats()``: the toy's 16 numbers rounded up to 128 lanes of
    float32."""
    engine = engine_for(program, num_pages=9)
    leaves = jax.tree_util.tree_flatten_with_path(engine.cache)[0]
    assert sorted(path[-1].key for path, _ in leaves) == ["cached_latent"] * 3
    assert {leaf.shape for _, leaf in leaves} == {(9, 4, 128)}
    assert engine.latent_layers == 3 and engine.routed_layers == 2
    assert engine.stats()["page_bytes_per_token_layer"] == 128 * 4


def test_multi_head_pages_report_their_bytes_too():
    from distributed_pytorch_tpu.models.transformer import TransformerLM
    import jax.numpy as jnp

    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = InferenceEngine(model, params, **ENGINE)
    # K and V, 2 heads of 8, float32.
    assert engine.stats()["page_bytes_per_token_layer"] == 2 * 2 * 8 * 4
    assert engine.latent_layers == 0


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_a_second_question_on_a_document_prefills_only_itself(program, kernel):
    """The trie serves the document: the second request's prefill carries
    its question (and the document's last partial page, which the first
    request's tokens run on in) and nothing else, and it serves what an
    engine with the trie off serves."""
    document = tokens(27, seed=20)  # 6 whole pages and 3 tokens
    first, second = document + tokens(5, seed=21), document + tokens(6, seed=22)
    engine = engine_for(program, paged_kernel=kernel)
    serve(engine, [first])
    before = engine.stats()
    (generated,) = serve(engine, [second], new_tokens=9)
    after = engine.stats()
    # 24 tokens in whole pages are hits; the rest, less the last token,
    # which the decode step feeds, is prefilled.
    assert after["prefix_tokens_hit"] - before["prefix_tokens_hit"] == 24
    assert after["prefill_tokens"] - before["prefill_tokens"] == 33 - 24 - 1
    (cold,) = serve(
        engine_for(program, prefix_cache=False, paged_kernel=kernel),
        [second], new_tokens=9)
    assert generated == cold
    assert served_gap(program, second, generated).max() < LOGIT_TOL


def test_the_partial_last_page_is_copied_on_write(program):
    """A document prefilled alone (one new token, so that its last partial
    page holds the document's tail and nothing else) leaves that page in the
    trie; askers extend it, and while more than one holds it each copies it
    first (the last holder writes on in place, past the tail the trie's key
    covers). The document's own pages stay what they were: a third asker,
    alone, still matches."""
    document = tokens(22, seed=30)  # 5 whole pages and 2 tokens
    engine = engine_for(program)
    serve(engine, [document], new_tokens=1)
    askers = [document + tokens(4 + i, seed=31 + i) for i in range(3)]
    before = engine.stats()
    served = serve(engine, askers[:2], new_tokens=6)  # side by side
    assert engine.stats()["cow_copies"] - before["cow_copies"] >= 1
    # All 22 of the document's tokens are hits: whole pages and the partial.
    hit = engine.stats()["prefix_tokens_hit"] - before["prefix_tokens_hit"]
    assert hit == 2 * 22
    served += serve(engine, askers[2:], new_tokens=6)
    for prompt, generated in zip(askers, served):
        assert served_gap(program, prompt, generated).max() < LOGIT_TOL


def test_re_prefill_after_a_forced_preemption_serves_the_same_tokens(program):
    """Too few pages for two long requests: one is preempted and prefilled
    again, prompt and generated tokens."""
    prompts = [tokens(30, seed=6), tokens(28, seed=7)]
    engine = engine_for(program, num_pages=1 + 14, prefix_cache=False)
    served = serve(engine, prompts, new_tokens=16)
    assert engine.scheduler.preemptions > 0
    for prompt, generated in zip(prompts, served):
        (alone,) = serve(
            engine_for(program, prefix_cache=False), [prompt], new_tokens=16)
        assert generated == alone
        assert served_gap(program, prompt, generated).max() < LOGIT_TOL


@pytest.mark.parametrize("kw, why", [
    (dict(mesh="mesh"), "KV-head axis"),
    (dict(host_pages=4), "KV heads and a head size"),
    (dict(kv_quant="int8"), "one scale a"),
    (dict(draft_model="self", draft_params="self"), "speculative verify"),
])
def test_what_a_model_with_latent_layers_cannot_be_served_with(
        program, kw, why):
    _, _, model, params = program
    if "mesh" in kw:
        kw = dict(mesh=make_serving_mesh(1, 2), prefix_cache=False)
    if "draft_model" in kw:
        kw = dict(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match="latent layers") as err:
        engine_for(program, **kw)
    assert why in str(err.value)


def step_args(tracer):
    return [e["args"] for e in tracer.events
            if e["name"] == "step" and e.get("ph") == "X"]


def test_the_distinct_token_counter_on_steps_with_known_tables(program):
    """Two askers of one 24-token document (6 whole pages) decode side by
    side: the steps' ``decode_kv_tokens_visible`` counts the document twice,
    ``decode_kv_tokens_distinct`` once."""
    document = tokens(24, seed=40)
    tracer = Tracer()
    engine = engine_for(program, tracer=tracer, paged_kernel="interpret")
    serve(engine, [document + tokens(3, seed=41)], new_tokens=2)
    tracer.events.clear()
    askers = [document + tokens(3, seed=42), document + tokens(3, seed=43)]
    serve(engine, askers, new_tokens=6)
    both = [a for a in step_args(tracer) if a["decode_rows"] == 2]
    assert both
    for a in both:
        visible, distinct = (a["decode_kv_tokens_visible"],
                             a["decode_kv_tokens_distinct"])
        assert visible - distinct == 24  # the document, counted once
        # ... and read once: the kernel serves the two as a group.
        assert a["decode_kv_tokens_fetched"] < visible
        assert a["decode_rows_grouped"] == 2
    admits = [e["args"] for e in tracer.events if e["name"] == "admit"]
    assert [(a["prompt_tokens"], a["cached_tokens"]) for a in admits] == [
        (27, 24), (27, 24)]


def test_askers_of_one_document_are_served_as_a_group(program):
    """Three questions on one cached document (6 whole pages) decode side by
    side: through the kernel (interpreted) the rows are served as a group,
    the document's pages read once a step, and the greedy tokens are the
    gather path's; a fourth asker, alone, is a row served alone."""
    from distributed_pytorch_tpu.ops.paged_attention import pages_walked

    document = tokens(24, seed=50)
    askers = [document + tokens(3 + i, seed=51 + i) for i in range(3)]
    late = document + tokens(5, seed=55)

    def run(kernel):
        tracer = Tracer()
        engine = engine_for(program, tracer=tracer, paged_kernel=kernel)
        serve(engine, [document + tokens(2, seed=49)], new_tokens=2)
        tracer.events.clear()
        served = serve(engine, askers, new_tokens=6)
        together = engine.stats()["decode_rows_grouped"]
        served += serve(engine, [late], new_tokens=4)
        return served, together, engine.stats(), step_args(tracer)

    served, together, stats, steps = run("interpret")
    gathered, _, gather_stats, gather_steps = run("xla")
    assert served == gathered
    for prompt, generated in zip(askers + [late], served):
        assert served_gap(program, prompt, generated).max() < LOGIT_TOL
    side_by_side = [a for a in steps if a["decode_rows"] == 3]
    assert side_by_side
    for a in side_by_side:
        assert a["decode_rows_grouped"] == 3
        # The document once and a page or two a row, where the rows see
        # the document three times over.
        assert a["decode_kv_tokens_fetched"] < a["decode_kv_tokens_visible"]
        assert a["decode_kv_tokens_fetched"] >= a["decode_kv_tokens_distinct"]
    assert together == sum(
        a.get("decode_rows_grouped", 0) for a in steps) > 0
    # The late asker has no sharer: no group, and its row walks its own
    # table from the start, whole blocks of 2 pages and a last one of 1 or 2
    # (PR 35's kernel counted that last block whole).
    lone = [a for a in steps if a["decode_rows"] == 1][-3:]
    assert stats["decode_rows_grouped"] == together
    for a in lone:
        assert a["decode_rows_grouped"] == 0
        pos = a["decode_kv_tokens_visible"] - 1
        assert a["decode_kv_tokens_fetched"] == 4 * int(
            pages_walked(np.asarray(pos // 4 + 1), 2))
        assert a["decode_kv_tokens_fetched"] >= a["decode_kv_tokens_visible"]
    # The gather path reads every slot's whole table, and groups nobody.
    assert gather_stats["decode_rows_grouped"] == 0
    assert all(a["decode_rows_grouped"] == 0 for a in gather_steps
               if "decode_rows_grouped" in a)
    assert all(a["decode_kv_tokens_fetched"] == 3 * 64 for a in gather_steps
               if "decode_kv_tokens_fetched" in a)


def test_distinct_tokens_of_a_handmade_dispatch(program):
    engine = engine_for(program, num_pages=20)
    tables = np.zeros((3, engine.pages_per_seq), np.int32)
    tables[0, :3] = [5, 6, 7]  # 10 tokens cached: sees 11 = 4 + 4 + 3
    tables[1, :3] = [5, 6, 9]  # shares two pages, sees 4 + 4 + 1
    tables[2, :1] = [7]  # shares row 0's LAST page and sees 4 of it
    positions = np.asarray([10, 8, 3])
    # pages 5, 6 whole (8), page 7 at the most any row sees (4), page 9 (1)
    counts = engine.reads.count(tables, positions, traced=True)
    assert counts["decode_kv_tokens_distinct"] == 8 + 4 + 1
    assert int(positions.sum()) + 3 == 11 + 9 + 4


def test_a_documents_neighbouring_pages_are_copied_in_runs(
        program, monkeypatch):
    """A document prefilled in ONE go holds neighbouring pages of the pool
    (the free list deals ascending numbers), and the latent kernel copies a
    turn of neighbours as one copy: at a block of 16 pages (turns of 2) the
    engine's ``decode_page_copies`` / ``decode_pages_in_runs``, in
    ``stats()`` and on the ``step`` slice, are what the kernel's copy loop
    walked a page at a time starts on the dispatch's staged tables, and the
    tokens are the gather path's."""
    from page_copy_loops import latent_copies_by_loop

    from distributed_pytorch_tpu.ops import paged_attention as pa

    monkeypatch.setattr(
        pa, "block_pages", lambda pages_per_seq, *a, **kw: min(16, pages_per_seq))
    document = tokens(24, seed=70)
    askers = [document + tokens(3, seed=71), document + tokens(4, seed=72)]
    tracer = Tracer()
    engine = engine_for(
        program, tracer=tracer, paged_kernel="interpret",
        max_prefill_chunk=32, token_budget=35)
    assert engine.reads.blocks == {"latent": 16}
    dispatches, count = [], engine.reads.count

    def counted(tables, positions, traced):
        dispatches.append((tables.copy(), positions.copy(),
                           engine.reads.groups(tables, positions)))
        return count(tables, positions, traced)

    engine.reads.count = counted
    serve(engine, [document + tokens(2, seed=69)], new_tokens=1)
    served = serve(engine, askers, new_tokens=6)
    stats = engine.stats()
    steps = [a for a in step_args(tracer) if "decode_page_copies" in a]
    assert len(steps) == len(dispatches) > 0
    for a, (tables, positions, groups) in zip(steps, dispatches):
        assert (a["decode_page_copies"], a["decode_pages_in_runs"]) == (
            latent_copies_by_loop(tables, positions, *groups, 4, 16))
        # The document's six pages stand side by side: three runs a row.
        assert (np.diff(tables[:, :6], axis=1) == 1).all()
        assert a["decode_pages_in_runs"] >= 6 * len(tables)
    assert stats["decode_page_copies"] == sum(
        a["decode_page_copies"] for a in steps)
    assert stats["decode_pages_in_runs"] == sum(
        a["decode_pages_in_runs"] for a in steps) > 0
    gather = engine_for(program, max_prefill_chunk=32, token_budget=35)
    serve(gather, [document + tokens(2, seed=69)], new_tokens=1)
    assert serve(gather, askers, new_tokens=6) == served
    # The gather path starts no page copy.
    assert gather.stats()["decode_page_copies"] == 0
