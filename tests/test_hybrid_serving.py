"""A model with recurrent (Mamba) layers through ``InferenceEngine``: the
per-slot state beside the KV pages. Everything is compared with
``benchmarks/reference/jamba.py`` on seeded weights at toy widths, through
logits: a served (greedy) token's reference logit has to lie within
``LOGIT_TOL`` of the reference's best at its position (``hybrid_toy``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_toy import LOGIT_TOL, served_gap, tokens, toy_program

from distributed_pytorch_tpu.models.mamba import STATE_KEYS
from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.obs.tracer import Tracer
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams
from distributed_pytorch_tpu.serving.elastic import (
    restore_engine,
    snapshot_engine,
)

ENGINE = dict(max_slots=2, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=10, prefix_cache=False)


@pytest.fixture(scope="module")
def program():
    return toy_program()


def engine_for(program, **kw):
    _, model, params = program
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


def slot_states(engine, slot):
    return [
        np.asarray(leaf[slot])
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            engine.cache)[0]
        if path[-1].key in STATE_KEYS
    ]


@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16, 32])
def test_chunked_prefill_then_paged_decode_matches_the_full_forward(
        program, chunk):
    """Prefill in chunks of one power of two, then decode through pages and
    state, as served; against the reference's one pass over the sequence."""
    prompt = tokens(38, seed=chunk)
    engine = engine_for(
        program, max_prefill_chunk=chunk, token_budget=chunk + 2)
    (generated,) = serve(engine, [prompt], new_tokens=10)
    assert served_gap(program[0], prompt, generated).max() < LOGIT_TOL


def test_every_chunking_leaves_the_same_state(program):
    """Chunks of every power of two give the state one pass gives: the
    carry between chunks is the float32 state itself, so only the order of
    the conv's and the projections' sums can differ."""
    prompt = tokens(33)  # 32 tokens prefilled, the last one decoded
    states = {}
    for chunk in (1, 4, 32):
        engine = engine_for(
            program, max_prefill_chunk=chunk, token_budget=chunk + 2)
        engine.submit(prompt, SamplingParams(max_new_tokens=4))
        while engine.scheduler.slots[0] is None or (
                engine.scheduler.slots[0].remaining_prefill):
            engine.step()
        states[chunk] = slot_states(engine, 0)
    for chunk in (1, 4):
        for got, want in zip(states[chunk], states[32]):
            assert np.abs(want).max() > 0
            np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_row_outside_the_dispatched_group_keeps_its_state_bit_for_bit(
        program):
    engine = engine_for(program, overlap=False)
    for seed in (1, 2):
        engine.submit(tokens(9, seed=seed), SamplingParams(max_new_tokens=6))
    while not all(r and r.n_generated >= 2 for r in engine.scheduler.slots):
        engine.step()
    before = [slot_states(engine, s) for s in (0, 1)]
    # One program over all rows, dispatched for slot 0 alone: slot 1 is
    # parked on the null page, as _step_impl's per-adapter groups park it.
    engine._dispatch_decode([0], engine.params, engine._zero_prev)
    after = [slot_states(engine, s) for s in (0, 1)]
    for was, now in zip(before[1], after[1]):
        assert np.array_equal(was, now)
    assert any(not np.array_equal(a, b) for a, b in zip(before[0], after[0]))


def test_slot_reuse_starts_from_zeros(program):
    """The second request of a slot reads nothing of the first: it is served
    as it is served alone (the same programs on the same inputs)."""
    first, second = tokens(21, seed=4), tokens(17, seed=5)
    engine = engine_for(program, max_slots=1)
    serve(engine, [first])
    assert any(np.abs(s).max() > 0 for s in slot_states(engine, 0))
    (reused,) = serve(engine, [second])
    (alone,) = serve(engine_for(program, max_slots=1), [second])
    assert reused == alone
    assert served_gap(program[0], second, reused).max() < LOGIT_TOL


def test_re_prefill_after_a_forced_preemption_starts_from_zeros(program):
    """Too few pages for two long requests: one is preempted and prefilled
    again, prompt and generated tokens, into whatever slot comes free."""
    prompts = [tokens(30, seed=6), tokens(28, seed=7)]
    tracer = Tracer()
    engine = engine_for(program, num_pages=1 + 14, tracer=tracer)
    served = serve(engine, prompts, new_tokens=16)
    assert engine.scheduler.preemptions > 0
    resets = [e["args"] for e in tracer.events if e["name"] == "state.reset"]
    assert {r["cause"] for r in resets} == {"admit", "preempt"}
    # A request preempted again before its first chunk ran starts once.
    assert len(resets) == engine.state_resets
    assert 2 < len(resets) <= 2 + engine.scheduler.preemptions
    for prompt, generated in zip(prompts, served):
        (alone,) = serve(engine_for(program), [prompt], new_tokens=16)
        assert generated == alone
        assert served_gap(program[0], prompt, generated).max() < LOGIT_TOL


def test_overlap_on_and_off_serve_the_same_logits(program):
    prompts = [tokens(19, seed=8), tokens(11, seed=9), tokens(25, seed=10)]
    served = {}
    for overlap in (True, False):
        engine = engine_for(program, overlap=overlap)
        served[overlap] = serve(engine, prompts, new_tokens=9)
    assert served[True] == served[False]
    for prompt, generated in zip(prompts, served[True]):
        assert served_gap(program[0], prompt, generated).max() < LOGIT_TOL


def test_five_requests_on_two_slots_against_each_served_alone(program):
    prompts = [tokens(n, seed=20 + n) for n in (7, 23, 1, 16, 30)]
    together = serve(engine_for(program), prompts, new_tokens=7)
    for prompt, generated in zip(prompts, together):
        (alone,) = serve(engine_for(program), [prompt], new_tokens=7)
        assert generated == alone
        assert served_gap(program[0], prompt, generated).max() < LOGIT_TOL


def test_a_one_token_prompt_resets_its_slot_in_the_decode_program(program):
    engine = engine_for(program, max_slots=1)
    serve(engine, [tokens(12, seed=11)])
    resets = engine.state_resets
    prompt = tokens(1, seed=12)
    (generated,) = serve(engine, [prompt], new_tokens=5)
    assert engine.state_resets == resets + 1
    assert served_gap(program[0], prompt, generated).max() < LOGIT_TOL


def test_int8_kv_pages_are_served(program):
    """``kv_quant`` concerns the attention layers' pages only, and is
    served: the states stay what they are, the logits move by what int8
    pages cost an attention layer (measured 2e-3 here; the limit leaves a
    factor of five)."""
    prompt = tokens(26, seed=13)
    engine = engine_for(program, kv_quant="int8")
    (generated,) = serve(engine, [prompt])
    assert served_gap(program[0], prompt, generated).max() < 1e-2
    scan = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        engine.cache)[0] if path[-1].key == "scan_state"]
    assert scan and all(leaf.dtype == jnp.float32 for leaf in scan)


def _draft():
    model = TransformerLM(
        vocab_size=128, d_model=16, n_layers=1, n_heads=2, d_ff=32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return dict(draft_model=model, draft_params=params)


def _mesh():
    from distributed_pytorch_tpu.serving.mesh import make_serving_mesh

    return dict(mesh=make_serving_mesh(data=1, model=1))


@pytest.mark.parametrize("kw, sentence", [
    (lambda: dict(prefix_cache=True),
     "prefix_cache=True yet: a prefix hit skips positions"),
    (_draft, "draft_model yet: a rejected proposal cannot be rolled back"),
    (lambda: dict(host_pages=8), "host_pages yet: the host tier spills"),
    (_mesh, "mesh yet: the serving mesh shards KV page pools only"),
])
def test_what_cannot_be_served_is_refused_at_construction(
        program, kw, sentence):
    with pytest.raises(ValueError, match=sentence):
        engine_for(program, **kw())


def test_elastic_restore_is_refused(program):
    engine = engine_for(program)
    engine.submit(tokens(9), SamplingParams(max_new_tokens=4))
    engine.step()
    snapshot = snapshot_engine(engine)
    with pytest.raises(ValueError, match="recurrent layers cannot restore"):
        restore_engine(engine_for(program), snapshot)


def test_state_gauges_in_the_trace_the_status_and_the_registry(program):
    tracer = Tracer()
    engine = engine_for(program, tracer=tracer)
    per_slot = sum(s.nbytes for s in slot_states(engine, 0))
    assert engine.state_bytes_per_slot == per_slot > 0
    engine.submit(tokens(9), SamplingParams(max_new_tokens=4))
    engine.step()
    assert engine.status()["state"] == {
        "layers": 3, "bytes_per_slot": per_slot, "state_slots_in_use": 1,
        "state_bytes": per_slot, "resets": 1}
    snapshot = engine.registry.snapshot()
    flat = str(snapshot)
    for name in ("state_slots_in_use", "state_bytes", "state_resets_total"):
        assert name in flat
    steps = [e for e in tracer.events if e["name"] == "step"]
    assert steps[-1]["args"]["state_slots_in_use"] == 1
    assert steps[-1]["args"]["state_bytes"] == per_slot
    chunk = next(e for e in tracer.events if e["name"] == "prefill.chunk")
    assert chunk["args"]["tokens"] == 8 and chunk["args"]["start"] == 0
    engine.run()


def test_an_engine_without_recurrent_layers_is_told_nothing_new():
    model = TransformerLM(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, d_ff=32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    tracer = Tracer()
    engine = InferenceEngine(
        model, params, max_slots=2, max_seq_len=32, page_size=4,
        tracer=tracer)
    engine.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=3))
    engine.run()
    assert engine.state_layers == 0 and engine.state_bytes_per_slot == 0
    assert "state" not in engine.status()
    assert not any("state" in e["name"] for e in tracer.events)
    step = next(e for e in tracer.events if e["name"] == "step")
    assert "state_bytes" not in step["args"]
