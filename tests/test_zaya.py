"""A model of the ``zaya`` shape through ``InferenceEngine``: every layer
compressed convolutional attention (K and V pages AND a two-token slot state)
and ONE expert of eight chosen by a router network that carries its state from
layer to layer, residual scales, a tied head. Everything is compared with
``benchmarks/reference/zaya.py`` on seeded weights at toy widths, on the
LOGITS of every decode step the engine dispatched, with its own staged tables
(``engine_logits``), over a prompt prefilled in pieces; each planted fault has
to fail that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zaya_toy import LOGIT_TOL, SEED, TOY, reference, tokens, toy_program

from distributed_pytorch_tpu.models import mamba, moe
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

ENGINE = dict(max_slots=3, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=11, prefix_cache=False, debug=True)
PROMPT, NEW = 21, 12


@pytest.fixture(scope="module")
def program():
    """``(cfg, weights)`` of the toy."""
    return TOY, reference.make_weights(TOY, SEED)


def engine_logits(model, params, prompt, new=NEW, **kw):
    """``prompt`` served by a fresh engine for ``new`` greedy tokens: the
    tokens, and the logits ``[new, V]`` of its row at every decode step, as
    the engine's OWN decode program computes them from the cache and the
    tables it staged (a twin of the program's forward, run on the same
    operands before the program donates the cache)."""
    engine = InferenceEngine(model, params, **{**ENGINE, **kw})
    decode_step = engine._decode_step

    def forward(params, cache, tok, tables, lens):
        return engine._forward(
            params, cache, tok[:, None], block_tables=tables, seq_lens=lens,
            **engine._decode_state_kw(tables, lens),
        )[0]

    twin, rows = jax.jit(forward), []

    def recording(params, cache, toks, prev, use_prev, tables, lens, *rest):
        tok = jnp.where(use_prev > 0, prev, toks)
        rows.append(np.asarray(twin(params, cache, tok, tables, lens))[0])
        return decode_step(
            params, cache, toks, prev, use_prev, tables, lens, *rest)

    engine.__dict__["_decode_step"] = recording
    rid = engine.submit(prompt, SamplingParams(max_new_tokens=new))
    engine.run()  # the first free slot, 0, takes the one request
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


@pytest.mark.parametrize("chunk, budget, kernel", [
    (1, 4, False), (4, 3, False), (8, 11, False), (8, 11, "interpret"),
    (64, 67, False), (64, 67, "interpret"),
])
def test_prefill_in_pieces_then_decode_against_the_full_forward(
        program, chunk, budget, kernel):
    """A prompt's first 20 tokens in pieces of 1, of 3 (a budget of 3 under a
    chunk of 4: every piece padded), of 8 (the last one padded from 4) and
    whole in ONE piece padded to 64: at every piece border the slot's
    tails are the convolutions' and the shift's left edge, and the valid
    length decides which token's are written back. Then 12 decode steps, on
    the gather path and through the interpreted K/V kernel."""
    _, model, params = toy_program(*program)
    prompt = tokens(PROMPT, seed=1)
    generated, got, engine = engine_logits(
        model, params, prompt, paged_kernel=kernel, max_prefill_chunk=chunk,
        token_budget=budget)
    assert gap_to_reference(program, got, prompt, generated) < LOGIT_TOL
    stats = engine.stats()
    assert stats["layer_kinds"] == "cca"
    assert stats["moe_router"] == "mlp_carry"
    # The prompt's last token goes through the decode program.
    assert stats["prefill_tokens"] == PROMPT - 1
    assert stats["prefill_programs"] == -(-(PROMPT - 1) // min(chunk, budget))
    assert stats.get("kv_decode_block_form") == ("stored" if kernel else None)


def _zeroed_tails(state, slots, lens):
    """The conv tails (rank 3) read as zeros; the shifted value's as it is."""
    rows = _load_rows(state, slots, lens)
    return jnp.zeros_like(rows) if state.ndim == 3 else rows


def _carry_dropped(self, n, carry=None):
    return _router_call(self, n, None)


def _choice_without_its_bias(scores, top_k, gating=moe.GATINGS[0], bias=None):
    return _route(scores, top_k, gating, None)


_load_rows, _route = mamba.load_rows, moe.route
_router_call = moe.CarryRouter.__call__


def cca_options(**changed):
    own = dict(dict(driver_options()["cca_options"]), **changed)
    return dict(cca_options=tuple(sorted(own.items())))


def driver_options():
    from zaya_toy import driver

    return driver.model_options(TOY)


FAULTS = {
    "the conv tails zeroed at a piece border": dict(
        patch=(mamba, "load_rows", _zeroed_tails)),
    "the value shift off": dict(changed=cca_options(value_shift=False)),
    "the rotation over the whole head": dict(changed=cca_options(rotary_dim=0)),
    "the convolutions without their biases": dict(
        changed=cca_options(conv_bias=False),
        without=("conv0_bias", "conv1_bias")),
    "no q-k mean": dict(changed=cca_options(qk_mean=False)),
    "the carry dropped": dict(patch=(moe.CarryRouter, "__call__", _carry_dropped)),
    "the choice made without its bias": dict(
        patch=(moe, "route", _choice_without_its_bias)),
    "the gate renormalised": dict(changed=dict(routed_gating="softmax_of_top_k")),
    "no residual scales": dict(
        changed=dict(residual_scales=False),
        without=("attn_skip_scale", "attn_branch_scale", "mlp_skip_scale",
                 "mlp_branch_scale")),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_program_made_wrong_in_one_way_is_caught(program, monkeypatch, name):
    fault = FAULTS[name]
    if "patch" in fault:
        monkeypatch.setattr(*fault["patch"])
    _, model, params = toy_program(*program, **fault.get("changed", {}))
    for drop in fault.get("without", ()):  # parameters the option removes
        for i in range(TOY["num_hidden_layers"]):
            layer = params[f"block_{i}"]
            (layer["cca"] if drop.startswith("conv") else layer).pop(drop)
    prompt = tokens(PROMPT, seed=1)
    generated, got, _ = engine_logits(model, params, prompt)
    assert gap_to_reference(program, got, prompt, generated) > 1e3 * LOGIT_TOL


def test_the_reference_with_int8_operands_is_another_model(program):
    """The benchmark's precision control: the reference computed with int8
    operands in every matmul lies far from itself in float32."""
    import os
    import sys

    from hybrid_toy import ROOT, load_by_path

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))  # its ``import run``
    try:
        control = load_by_path("benchmarks/control.py")
    finally:
        sys.path.pop(0)
    cfg, weights = program
    toks = tokens(PROMPT + NEW, seed=1)
    exact = np.asarray(reference.forward(cfg, weights, toks))
    low = np.asarray(reference.forward(
        cfg, weights, toks, einsum=control.int8_einsum))
    assert np.abs(low - exact).max() > 1e3 * LOGIT_TOL


def test_router_scores_in_bfloat16_are_caught(program, monkeypatch):
    monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
    _, model, params = toy_program(*program)
    prompt = tokens(PROMPT, seed=1)
    generated, got, _ = engine_logits(model, params, prompt)
    assert gap_to_reference(program, got, prompt, generated) > 1e2 * LOGIT_TOL


@pytest.mark.parametrize("given, what, why", [
    (dict(prefix_cache=True), "prefix_cache=True", "state snapshot"),
    (dict(draft=True), "draft_model", "rolled back"),
    (dict(host_pages=8), "host_pages", "KV pages only"),
    (dict(mesh=True), "mesh", "KV page pools only"),
])
def test_what_a_slot_state_cannot_follow_is_refused(program, given, what, why):
    """The four refusals of a model with recurrent layers hold for a layer
    that also has a block table, each with its reason."""
    _, model, params = toy_program(*program)
    kw = dict(given)
    if kw.pop("draft", False):
        kw.update(draft_model=model, draft_params=params)
    if kw.pop("mesh", False):
        from jax.sharding import Mesh

        kw["mesh"] = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                          ("data", "model"))
    with pytest.raises(ValueError, match=f"{what}.*{why}"):
        InferenceEngine(model, params, **{**ENGINE, **kw})


def test_preempted_sequences_are_prefilled_again_from_position_zero(program):
    """A pool too small for three requests at once: the youngest is
    preempted, is prefilled again from position 0 (its slot's state starts
    from zeros there, whatever the slot held) and serves what an engine with
    room serves."""
    _, model, params = toy_program(*program)
    prompts = [tokens(n, seed=n) for n in (30, 26, 22)]

    def serve(**kw):
        engine = InferenceEngine(model, params, **{**ENGINE, **kw})
        ids = [engine.submit(p, SamplingParams(max_new_tokens=20))
               for p in prompts]
        engine.run()
        out = [list(engine.poll(rid).generated) for rid in ids]
        stats = engine.stats()
        engine.close()
        return out, stats, engine.state_resets

    roomy, _, resets = serve()
    tight, stats, more = serve(num_pages=30)
    assert stats["preemptions"] > 0 and more > resets == 3
    assert tight == roomy
