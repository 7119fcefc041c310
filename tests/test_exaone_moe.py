"""A model of the ``exaone_moe`` shape through ``InferenceEngine``: K/V layers
with a window three to every full one, each kind on block tables of its own
(``serving/kv_cache.py`` ``WindowGroup``: the pages behind a window go back to
their group's allocator), heads of a size that is not ``d_model / n_heads``
with an RMSNorm of their own, the rotation on the window layers alone, a dense
layer then sigmoid-routed experts whose gates are scaled, of which this chip
holds a share, and a sliced vocabulary. Everything is compared with
``benchmarks/reference/exaone_moe.py`` on seeded weights at toy widths, on the
LOGITS of every decode step the engine dispatched, with its own staged tables
(``engine_logits``), over a prompt prefilled in pieces and a decode that runs
past several windows; each planted fault has to fail that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from exaone_toy import (
    LOGIT_TOL, SEED, TOY, reference, share, slice_share, tokens, toy_program,
)

from distributed_pytorch_tpu.models import moe
from distributed_pytorch_tpu.obs.tracer import Tracer
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

HELD, VOCAB = (4, 8), 80  # this chip's share: 4 of 16 experts, 80 of 96 rows
ENGINE = dict(max_slots=3, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=11, prefix_cache=False, debug=True)
PROMPT, NEW = 21, 30  # positions 20-49: past five windows of 8


@pytest.fixture(scope="module")
def program():
    """``(cfg, weights)`` of the toy's share."""
    cfg = share(HELD, VOCAB)
    return cfg, slice_share(reference.make_weights(TOY, SEED), HELD, VOCAB)


def engine_logits(model, params, prompt, new=NEW, *, free_ahead=0, **kw):
    """``prompt`` served by a fresh engine for ``new`` greedy tokens: the
    tokens, and the logits ``[new, V]`` of its row at every decode step, as
    the engine's OWN decode program computes them from the cache and the
    tables it staged (a twin of the program's forward, run on the same
    operands before the program donates the cache)."""
    engine = InferenceEngine(model, params, **{**ENGINE, **kw})
    engine.scheduler.window_group.free_ahead = free_ahead
    decode_step = engine._decode_step

    def forward(params, cache, tok, tables, lens, *window):
        return engine._forward(
            params, cache, tok[:, None], block_tables=tables, seq_lens=lens,
            **engine._decode_state_kw(tables, lens), window_tables=window[0],
        )[0]

    twin, rows = jax.jit(forward), []

    def recording(params, cache, toks, prev, use_prev, tables, lens, *rest):
        tok = jnp.where(use_prev > 0, prev, toks)
        rows.append(np.asarray(
            twin(params, cache, tok, tables, lens, *rest[3:]))[slot[0]])
        return decode_step(
            params, cache, toks, prev, use_prev, tables, lens, *rest)

    engine.__dict__["_decode_step"] = recording
    rid = engine.submit(prompt, SamplingParams(max_new_tokens=new))
    slot = [0]  # the first free slot takes the one request
    engine.run()
    status = engine.poll(rid)
    assert status.state == "finished" and len(rows) == new
    engine.close()
    return list(status.generated), np.stack(rows), engine


def gap_to_reference(program, got, prompt, generated):
    """The widest distance of the engine's decode-step logits from the
    reference's full forward over the same tokens."""
    cfg, weights = program
    want = np.asarray(reference.forward(
        cfg, weights, list(prompt) + generated[:-1]))
    return np.abs(got - want[len(prompt) - 1:]).max()


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_prefill_in_pieces_then_decode_past_several_windows(program, kernel):
    """Prompt in pieces of 8 that cross page and window boundaries, then 30
    decode steps over positions 20-49 (a window of 8 on pages of 4): the
    window layers through their group's short tables (gather path, and the
    K/V kernel interpreted, two blocks a row), against the reference."""
    _, model, params = toy_program(*program)
    prompt = tokens(PROMPT, seed=1, vocab=VOCAB)
    generated, got, engine = engine_logits(
        model, params, prompt, paged_kernel=kernel)
    assert gap_to_reference(program, got, prompt, generated) < LOGIT_TOL
    stats = engine.stats()
    # A window layer's sequence held a piece's pages at most, and three
    # while it decoded; every page behind the windows came back.
    group = engine.scheduler.window_group
    assert (group.piece_pages, group.decode_pages) == (5, 3)
    assert stats["window_pages_held_peak"] <= group.piece_pages
    assert stats["window_pages_freed"] == (PROMPT + NEW - 1 - 8) // 4 + 1 - 1
    assert stats["window_pages_held"] == 0  # the request has retired
    assert stats["decode_window_tokens_visible"] == 8 * NEW
    # Both table groups' K/V kernel calls name how they compute a block;
    # the gather path has none.
    forms = {key: value for key, value in stats.items()
             if key.endswith("_decode_block_form")}
    assert forms == ({} if not kernel else {
        "kv_decode_block_form": "stored",
        "kv_window_decode_block_form": "stored"})


def _whole_projection_norm(program):
    """The heads' ``[dh]`` scales spread over the whole projection, and the
    statistics taken over it: only the statistics differ."""
    cfg, weights = program
    _, model, params = toy_program(cfg, weights, qk_norm=True)
    for i in range(cfg["num_hidden_layers"]):
        attention = params[f"block_{i}"]["attention"]
        for name, heads in (("q_norm", 8), ("k_norm", 2)):
            scale = attention[name]["scale"]
            attention[name] = {"scale": jnp.tile(scale[None], (heads, 1))}
    return model, params


def _window(n):
    return dict(attention_variants=(("attention_window", (
        ("rope", True), ("rope_theta", 10000.0), ("window", n))),))


def _gates_with_the_bias(scores, top_k, gating, bias=None):
    s = jax.nn.sigmoid(scores) + bias
    chosen, experts = jax.lax.top_k(s, top_k)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True), experts


def _gates_not_renormalised(scores, top_k, gating, bias=None):
    s = jax.nn.sigmoid(scores)
    _, experts = jax.lax.top_k(s + bias, top_k)
    return jnp.take_along_axis(s, experts, axis=-1), experts


FAULTS = {
    "a window of 9": dict(changed=_window(9)),
    "a window of 7": dict(changed=_window(7)),
    "the rotation left on in the full layers": dict(changed=dict(rope=True)),
    "the norm over the whole projection": dict(build=_whole_projection_norm),
    "the gates without their 2.5": dict(changed=dict(routed_scale=1.0)),
    "the bias in the gates": dict(route=_gates_with_the_bias),
    "gates not renormalised": dict(route=_gates_not_renormalised),
    "a page freed one step early": dict(free_ahead=1),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_program_made_wrong_in_one_way_is_caught(program, monkeypatch, name):
    fault = FAULTS[name]
    if "route" in fault:
        monkeypatch.setattr(moe, "route", fault["route"])
    if "build" in fault:
        model, params = fault["build"](program)
    else:
        _, model, params = toy_program(*program, **fault.get("changed", {}))
    prompt = tokens(PROMPT, seed=1, vocab=VOCAB)
    generated, got, _ = engine_logits(
        model, params, prompt, free_ahead=fault.get("free_ahead", 0))
    assert gap_to_reference(program, got, prompt, generated) > 1e3 * LOGIT_TOL


@pytest.mark.parametrize("given, what", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(host_pages=8, prefix_cache=True), "prefix_cache=True"),
    (dict(window_pages=5), "cannot hold one piece"),
])
def test_what_cannot_follow_a_second_group_is_refused(program, given, what):
    _, model, params = toy_program(*program)
    with pytest.raises(ValueError, match=what):
        InferenceEngine(model, params, **{**ENGINE, **given})


def test_a_model_without_window_layers_builds_one_group(program):
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = InferenceEngine(model, params, **ENGINE)
    assert engine.window_group is None and engine.kv_window == 0
    assert engine.scheduler.window_group is None
    assert "window_pages_held" not in engine.stats()
    with pytest.raises(ValueError, match="no attention_window layers"):
        InferenceEngine(model, params, window_pages=9, **ENGINE)


def test_a_window_in_a_plain_paged_layer_is_still_refused():
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=64,
        attention_window=8)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    with pytest.raises(ValueError, match="attention_window"):
        InferenceEngine(model, params, **ENGINE)


def test_the_groups_pools_spans_and_counters(program):
    """Each group's pools at its own size; the ``step`` slice, the
    ``window.free`` instant, the ``prefill.chunk`` slice and the ``admit``
    event say what the window group held, freed and reserved."""
    _, model, params = toy_program(*program)
    tracer = Tracer()
    engine = InferenceEngine(
        model, params, tracer=tracer, num_pages=40, window_pages=17,
        **ENGINE)
    shapes = {
        path[0].key: leaf.shape[0]
        for path, leaf in jax.tree_util.tree_flatten_with_path(engine.cache)[0]
        if path[-1].key == "cached_key"}
    assert shapes == {f"block_{i}": 40 if i % 4 == 3 else 17 for i in range(8)}
    split = engine.pool_bytes_by_group()
    page = 2 * 4 * 2 * 6 * 4  # K and V, 4 tokens, 2 heads of 6, float32
    assert split == {"window_bytes": 6 * 17 * page, "full_bytes": 2 * 40 * page}
    ids = [engine.submit(tokens(n, seed=n, vocab=VOCAB),
                         SamplingParams(max_new_tokens=12))
           for n in (21, 34)]
    engine.run()
    assert all(engine.poll(rid).state == "finished" for rid in ids)
    events = tracer.events
    steps = [e["args"] for e in events
             if e["name"] == "step" and e.get("ph") == "X"]
    freed = [e["args"] for e in events if e["name"] == "window.free"]
    assert sum(a["window_pages_freed"] for a in steps) == sum(
        a["pages"] for a in freed) == engine.stats()["window_pages_freed"] > 0
    assert all(a["rows"] >= 1 for a in freed)
    assert max(a["window_pages_held"] for a in steps) <= 2 * 5
    assert {"window_pages_free", "decode_window_tokens_visible",
            "decode_window_tokens_read"} <= set(steps[-2])
    pieces = [e["args"] for e in events
              if e["name"] == "prefill.chunk" and e.get("ph") == "X"]
    assert pieces and all(1 <= a["window_pages"] <= 5 for a in pieces)
    admits = [e["args"] for e in events if e["name"] == "admit"]
    assert [(a["pages_full"], a["pages_window"]) for a in admits] == [
        (9, 5), (12, 5)]
    engine.close()  # both allocators quiescent


def test_preempted_sequences_are_prefilled_again_from_position_zero(program):
    """A full group too small for three requests at once: the youngest is
    preempted, gives back its pages in BOTH groups, is prefilled again from
    position 0 and serves what an engine with room serves."""
    _, model, params = toy_program(*program)
    prompts = [tokens(n, seed=n, vocab=VOCAB) for n in (30, 26, 22)]

    def serve(**kw):
        engine = InferenceEngine(model, params, **{**ENGINE, **kw})
        ids = [engine.submit(p, SamplingParams(max_new_tokens=20))
               for p in prompts]
        engine.run()
        out = [list(engine.poll(rid).generated) for rid in ids]
        stats = engine.stats()
        engine.close()
        return out, stats

    roomy, _ = serve()
    tight, stats = serve(num_pages=30)
    assert stats["preemptions"] > 0
    assert tight == roomy
