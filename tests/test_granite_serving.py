"""A model with Mamba-2 layers and routed experts through ``InferenceEngine``:
a ``[H, P, N]`` state a slot beside the KV pages, the chip's share of the
experts, and the routing counters. Everything is compared with
``benchmarks/reference/granite.py`` on seeded weights at toy widths, through
logits: a served (greedy) token's reference logit has to lie within
``LOGIT_TOL`` of the reference's best at its position (``granite_toy``)."""

import jax
import numpy as np
import pytest

from granite_toy import (
    LOGIT_TOL, SEED, TOY, reference, share, slice_experts, tokens, toy_program,
)

from distributed_pytorch_tpu.models.mamba import STATE_KEYS
from distributed_pytorch_tpu.obs.tracer import Tracer
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams
from distributed_pytorch_tpu.serving.mesh import make_serving_mesh

ENGINE = dict(max_slots=2, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=10, prefix_cache=False)
HELD = (0, 4)  # this chip's share of the toy's 8 experts


@pytest.fixture(scope="module")
def program():
    """The toy holding experts 0-3 of 8, as the benchmark's cell holds 36 of
    72: ``(cfg, weights, model, params)``."""
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


@pytest.mark.parametrize("chunk", [1, 4, 32])
def test_chunked_prefill_then_paged_decode_matches_the_reference(
        program, chunk):
    """Prefill in chunks of one power of two, then decode through pages,
    states and the held experts, as served; against the reference's one pass
    over the sequence, given the same share."""
    prompt = tokens(38, seed=chunk)
    engine = engine_for(
        program, max_prefill_chunk=chunk, token_budget=chunk + 2)
    (generated,) = serve(engine, [prompt], new_tokens=10)
    assert served_gap(program, prompt, generated).max() < LOGIT_TOL


def test_a_one_token_prompt_starts_from_zeros_in_the_decode_program(program):
    first = tokens(21, seed=4)
    engine = engine_for(program, max_slots=1)
    serve(engine, [first])  # leaves its state in the slot
    (generated,) = serve(engine, [[7]], new_tokens=12)
    assert served_gap(program, [7], generated).max() < LOGIT_TOL


def test_re_prefill_after_a_forced_preemption_serves_the_same_tokens(program):
    """Too few pages for two long requests: one is preempted and prefilled
    again, prompt and generated tokens, into whatever slot comes free."""
    prompts = [tokens(30, seed=6), tokens(28, seed=7)]
    tracer = Tracer()
    engine = engine_for(program, num_pages=1 + 14, tracer=tracer)
    served = serve(engine, prompts, new_tokens=16)
    assert engine.scheduler.preemptions > 0
    resets = [e["args"] for e in tracer.events if e["name"] == "state.reset"]
    assert {r["cause"] for r in resets} == {"admit", "preempt"}
    for prompt, generated in zip(prompts, served):
        (alone,) = serve(engine_for(program), [prompt], new_tokens=16)
        assert generated == alone
        assert served_gap(program, prompt, generated).max() < LOGIT_TOL


def test_the_state_the_engine_keeps_is_the_mixers(program):
    """``[max_slots, H, P, N]`` float32 and ``[max_slots, K-1, H P + 2 G N]``
    a Mamba-2 layer, found by ``STATE_KEYS`` whatever their shapes."""
    engine = engine_for(program)
    shapes = sorted(
        leaf.shape for path, leaf in
        jax.tree_util.tree_flatten_with_path(engine.cache)[0]
        if path[-1].key in STATE_KEYS)
    assert shapes == [(2, 3, 160), (2, 3, 160), (2, 8, 16, 16), (2, 8, 16, 16)]
    assert engine.state_layers == 2 and engine.routed_layers == 3
    assert engine.state_bytes_per_slot == 2 * (8 * 16 * 16 + 3 * 160) * 4


def test_what_a_model_with_routed_layers_cannot_be_served_with(program):
    with pytest.raises(ValueError, match="recurrent layers"):
        engine_for(program, prefix_cache=True)
    with pytest.raises(ValueError, match="mesh"):
        engine_for(program, mesh=make_serving_mesh(1, 2))


def routing_events(tracer):
    return [e["args"] for e in tracer.events if e["name"] == "moe.routing"]


def test_the_routing_counters_on_steps_with_known_routing(program):
    """One request of 9 prompt tokens and 4 new ones, chunks of 8: step 0
    prefills 8 tokens (one program), step 1 decodes the ninth, steps 2-4 the
    generated ones; three routed layers, 3 choices a token. The counters are
    written one step late and name the step they belong to."""
    cfg, weights, model, params = program
    tracer = Tracer()
    engine = engine_for(program, tracer=tracer)
    prompt = tokens(9, seed=12)
    (generated,) = serve(engine, [prompt], new_tokens=4)
    engine.finish_inflight()
    events = routing_events(tracer)
    steps = [e["args"]["step"] for e in tracer.events
             if e["name"] == "step" and e["args"]["decode_rows"]
             + e["args"]["prefill_tokens"] > 0]
    assert [e["step"] for e in events] == steps
    assert [e["moe_programs"] for e in events] == [1] * len(events)
    pairs = [e["moe_pairs_held"] + e["moe_pairs_absent"] for e in events]
    assert pairs == [8 * 3 * 3] + [1 * 3 * 3] * 4
    # The reference's router on the same tokens says which pairs were held.
    toks = prompt + generated[:-1]
    want = known_routing(cfg, weights, toks)
    assert sum(e["moe_pairs_held"] for e in events) == want["held"]
    assert sum(e["moe_pairs_absent"] for e in events) == want["absent"]
    assert events[0]["moe_experts_hit"] == want["hit_first_8"]
    for e in events[1:]:  # one token: each held expert it chose got 1
        assert e["moe_tokens_per_expert_max"] <= 3
        assert e["moe_experts_hit"] == e["moe_pairs_held"]
        assert e["moe_tokens_per_expert_mean"] == pytest.approx(
            e["moe_pairs_held"] / 4)


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_the_products_kernel_follows_the_engines_and_counts_its_rows(
        program, kernel):
    """``paged_kernel`` settles the grouped products' mode as it settles the
    attention kernels' (no option of their own): the engine says which
    (``moe_product``), serves tokens the reference agrees with either way, and
    counts the rows the kernel multiplied by the kernel's own tiling rule. A
    one-token program's groups are single rows inside the first tile: a tile
    a reached expert and layer."""
    from distributed_pytorch_tpu.ops.grouped_matmul import ROW_TILE

    tracer = Tracer()
    engine = engine_for(program, tracer=tracer, paged_kernel=kernel)
    prompt = tokens(9, seed=12)
    (generated,) = serve(engine, [prompt], new_tokens=4)
    engine.finish_inflight()
    assert served_gap(program, prompt, generated).max() < LOGIT_TOL
    events = routing_events(tracer)
    stats = engine.stats()
    assert stats["moe_product"] == kernel
    assert stats["moe_pairs_held"] == sum(e["moe_pairs_held"] for e in events)
    assert stats["moe_rows_computed"] == sum(
        e["moe_rows_computed"] for e in events)
    if kernel == "xla":  # the compiler's product walks no tile of this rule
        assert stats["moe_rows_computed"] == 0
        return
    for e in events[1:]:
        assert e["moe_rows_computed"] == ROW_TILE * e["moe_experts_hit"]
    first = events[0]  # 8 tokens: whole tiles, no fewer rows than pairs
    assert first["moe_rows_computed"] % ROW_TILE == 0
    assert first["moe_rows_computed"] >= max(
        first["moe_pairs_held"], ROW_TILE * first["moe_experts_hit"])
    assert first["moe_rows_computed"] <= ROW_TILE * 2 * first["moe_experts_hit"]


def known_routing(cfg, weights, toks):
    """What the counters of ``toks`` (8 in a chunk, then one by one) have to
    add up to, from the reference's own routing."""
    routed = np.asarray(reference.routing_at(cfg, weights, toks))  # [L, T, E]
    held = routed[..., HELD[0]:HELD[1]]
    return {"held": int(held.sum()), "absent": int(routed.sum() - held.sum()),
            "hit_first_8": int(held[:, :8].any(axis=1).sum())}


def test_an_untraced_engine_holds_the_last_steps_counts_and_reads_none(program):
    """``routing_counts`` is the last step's programs' counts, tracer or no
    tracer: laid along a request's tokens in the order the programs ran,
    they are the reference's routing of those tokens (float32 at toy widths:
    the same experts). Nothing is queued for a trace that nobody takes."""
    cfg, weights = program[:2]
    engine = engine_for(program)
    prompt = tokens(9, seed=12)
    rid = engine.submit(prompt, SamplingParams(max_new_tokens=5))
    programs = []
    while engine.poll(rid).state != "finished":
        engine.step()
        assert all(isinstance(c, jax.Array) for c in engine.routing_counts)
        programs += [np.asarray(c) for c in engine.routing_counts]
    assert engine._routing_due is None
    toks = prompt + list(engine.poll(rid).generated)[:-1]
    want = np.asarray(reference.routing_at(cfg, weights, toks)).astype(int)
    start, sizes = 0, []
    for counts in programs:
        sizes.append(int(counts[0].sum()) // cfg["num_experts_per_tok"])
        stop = start + sizes[-1]
        assert np.array_equal(counts, want[:, start:stop].sum(axis=1))
        start = stop
    assert start == len(toks) and sizes[:2] == [8, 1]


def test_rows_outside_the_dispatched_group_are_not_counted(program):
    engine = engine_for(program, overlap=False)
    for seed in (1, 2):
        engine.submit(tokens(9, seed=seed), SamplingParams(max_new_tokens=6))
    while not all(r and r.n_generated >= 2 for r in engine.scheduler.slots):
        engine.step()
    engine.finish_inflight()
    # One program over both rows, dispatched for slot 0 alone.
    engine.routing_counts = []
    engine._dispatch_decode([0], engine.params, engine._zero_prev)
    (counts,) = engine.routing_counts
    assert np.asarray(counts).sum(axis=-1).tolist() == [1 * 3] * 3
