"""The gated delta-rule mixer (``models/gated_delta.py``,
``ops/linear_attention.py``) against the benchmark's plain reference
(``benchmarks/reference/olmo_hybrid.py``: the recurrence a token at a time, as
written), at toy size on the CPU, float32: the recurrence's three forms, the
block's two Olmo options, planted faults on logits, and the model through the
engine's cache."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.models import gated_delta, mamba
from distributed_pytorch_tpu.models.transformer import (
    LAYER_TYPES,
    RECURRENT_TYPES,
    TransformerLM,
)
from distributed_pytorch_tpu.ops import linear_attention as la

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from hybrid_toy import ROOT, load_by_path  # noqa: E402

reference = load_by_path("benchmarks/reference/olmo_hybrid.py")
driver = load_by_path("benchmarks/drivers/serve_linear_hybrid.py")

with open(os.path.join(
        ROOT, "benchmarks", "tests", "toy_linear_hybrid", "configs",
        "toy-linear-hybrid.json")) as f:
    CFG = json.load(f)
HEADS, DK, DV = 4, 16, 64  # the toy's linear heads: two heads a lane pack


@pytest.fixture(scope="module")
def weights():
    return reference.make_weights(CFG, 11)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(1, 1024, size=90).tolist()


@pytest.fixture(scope="module")
def wanted(weights, tokens):
    return np.asarray(reference.logits_at(
        CFG, weights, tokens, list(range(len(tokens)))))


def logits_of(weights, tokens, **overrides):
    model, params = driver.build_program(CFG, weights, **overrides)
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(
            {"params": params}, jnp.asarray([tokens]))[0])


def draws(seed, batch, t):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q = gated_delta.l2_normalised(n(batch, t, HEADS, DK)) * DK**-0.5
    k = gated_delta.l2_normalised(n(batch, t, HEADS, DK))
    log_alpha = -jnp.exp(n(batch, t, HEADS)) * 0.2
    beta = 2.0 * jax.nn.sigmoid(n(batch, t, HEADS) + 1.0)
    return q, k, n(batch, t, HEADS, DV), log_alpha, beta, n(
        batch, HEADS, DK, DV)


def by_the_reference(q, k, v, log_alpha, beta, s0):
    """The recurrence exactly as the issue writes it, in NumPy float64."""
    q, k, v, log_alpha, beta, s = (
        np.asarray(x, np.float64) for x in (q, k, v, log_alpha, beta, s0))
    out = np.zeros(v.shape)
    eye = np.eye(DK)
    for b in range(q.shape[0]):
        for h in range(HEADS):
            state = s[b, h]
            for t in range(q.shape[1]):
                kt = k[b, t, h][:, None]
                state = np.exp(log_alpha[b, t, h]) * (
                    eye - beta[b, t, h] * kt @ kt.T) @ state + (
                        beta[b, t, h] * kt @ v[b, t, h][None, :])
                out[b, t, h] = state.T @ q[b, t, h]
            s[b, h] = state
    return out, s


# ------------------------------------------------------------- three forms


def test_the_layer_type_is_named_and_recurrent():
    assert "gated_delta" in LAYER_TYPES and "gated_delta" in RECURRENT_TYPES
    model = TransformerLM(
        layer_types=("gated_delta", "attention"), n_layers=2,
        linear_n_heads=2, linear_d_k=8, linear_d_v=8)
    assert model.recurrent_layers == 1


def test_beta_reaches_past_one_in_the_draws():
    """The negative eigenvalues ARE exercised: ``1 - beta`` below 0."""
    beta = draws(0, 2, 150)[4]
    assert float(beta.max()) > 1.9 and float((beta > 1).mean()) > 0.5


@pytest.mark.parametrize("t, block", [(150, 64), (64, 64), (40, 64), (130, 16), (1, 64)])
def test_the_blocked_form_is_the_recurrence(t, block):
    q, k, v, log_alpha, beta, s0 = draws(1, 2, t)
    want_o, want_s = by_the_reference(q, k, v, log_alpha, beta, s0)
    with jax.default_matmul_precision("highest"):
        o, s = la.gated_delta_blocks(q, k, v, log_alpha, beta, s0, block)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_padding_tokens_change_nothing():
    """``log_alpha = 0`` and ``beta = 0``: the state a padded piece leaves is
    the one its last real token left."""
    q, k, v, log_alpha, beta, s0 = draws(2, 1, 128)
    own = (jnp.arange(128) < 70)[None, :, None]
    with jax.default_matmul_precision("highest"):
        _, padded = la.gated_delta_blocks(
            q, k, v, jnp.where(own, log_alpha, 0.0),
            jnp.where(own, beta, 0.0), s0)
        _, short = la.gated_delta_blocks(
            q[:, :70], k[:, :70], v[:, :70], log_alpha[:, :70], beta[:, :70],
            s0)
    np.testing.assert_allclose(padded, short, atol=1e-6)


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_the_one_token_step_on_the_packed_slot_table(kernel):
    """The engine's decode step: every row of the slot table in place, the
    state as it is kept (two heads side by side on the lanes). A row out of
    the group keeps its state bit for bit; a row at position 0 starts from
    zeros whatever its slot held."""
    pack = la.lane_pack(HEADS, DV)
    assert pack == 2
    q, k, v, log_alpha, beta, s0 = draws(4, 6, 1)
    held = la.pack_state(s0, pack)
    assert held.shape == (6, HEADS // 2, DK, 2 * DV)
    np.testing.assert_array_equal(la.unpack_state(held, pack), s0)
    slots = jnp.asarray([0, 1, -1, 3, -1, 5])
    lens = jnp.asarray([7, 0, 3, 2, 0, 0])
    codes = la.row_codes(slots, lens)
    assert codes.tolist() == [1, 0, -1, 1, -1, 0]
    o, s = la.gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0], jnp.exp(log_alpha[:, 0]), beta[:, 0], held,
        codes, pack=pack, kernel=kernel)
    start = jnp.where((codes == 1)[:, None, None, None], s0, 0.0)
    want_o, want_s = by_the_reference(q, k, v, log_alpha, beta, start)
    live = np.asarray(codes) >= 0
    np.testing.assert_allclose(np.asarray(o)[live], want_o[live, 0], atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(la.unpack_state(s, pack))[live], want_s[live], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(s)[~live], np.asarray(held)[~live])


def test_lane_pack_fills_whole_tiles():
    assert la.lane_pack(30, 192) == 2  # the published sizes: 384 lanes
    assert la.lane_pack(8, 128) == 1 and la.lane_pack(8, 32) == 4
    assert la.lane_pack(3, 64) == 1  # no pack divides the heads
    assert la.state_bytes_moved(96 * 12, 30, 96, 192) == 2 * 96 * 12 * 2_211_840


# ----------------------------------------------------- the model, on logits


def test_the_model_is_the_reference_on_logits(weights, tokens, wanted):
    got = logits_of(weights, tokens)
    assert np.abs(got - wanted).max() < 2e-4 * np.abs(wanted).max()


@pytest.mark.parametrize("fault", [
    dict(linear_neg_eigval=False),  # beta without its factor 2
    dict(norm_placement="input"),  # the norm on the input, not the output
    dict(qk_norm=False),
])
def test_a_model_made_wrong_in_one_option_is_caught(
        weights, tokens, wanted, fault):
    if fault == dict(qk_norm=False):
        model, params = driver.build_program(CFG, weights, **fault)
        for name, layer in params.items():
            if "attention" in layer:
                layer["attention"] = {
                    k: v for k, v in layer["attention"].items()
                    if not k.endswith("_norm")}
        with jax.default_matmul_precision("highest"):
            got = np.asarray(model.apply(
                {"params": params}, jnp.asarray([tokens]))[0])
    else:
        got = logits_of(weights, tokens, **fault)
    assert np.abs(got - wanted).max() > 0.02 * np.abs(wanted).max()


def _mixer_faults():
    def no_l2(monkeypatch):
        monkeypatch.setattr(gated_delta, "l2_normalised", lambda x: x)

    def no_decay(monkeypatch):
        real = la.gated_delta_blocks
        monkeypatch.setattr(
            la, "gated_delta_blocks",
            lambda q, k, v, log_alpha, *rest, **kw: real(
                q, k, v, jnp.zeros_like(log_alpha), *rest, **kw))

    return {"no L2 norm": no_l2, "no decay": no_decay}


@pytest.mark.parametrize("name", sorted(_mixer_faults()))
def test_a_mixer_made_wrong_is_caught(
        weights, tokens, wanted, monkeypatch, name):
    _mixer_faults()[name](monkeypatch)
    got = logits_of(weights, tokens)
    assert np.abs(got - wanted).max() > 0.02 * np.abs(wanted).max()


# ------------------------------------------------- through the engine's cache


def cached_logits(weights, tokens, pieces, *, kernel="xla", tail_fault=False,
                  state_dtype=None, monkeypatch=None):
    """``tokens`` through a decode-mode model the way the engine runs it: the
    prompt in padded ``pieces`` (each ``(tokens, width)``) on slot 1 of three,
    then a token a decode step for the whole slot table with the other rows
    out of the group. Logits at every position that a decode step produced,
    and the slot's final states."""
    if state_dtype is not None:
        monkeypatch.setattr(mamba, "STATE_DTYPE", state_dtype)
    model, params = driver.build_program(CFG, weights)
    slots, page, pages = 3, 16, 8
    decode = model.clone(
        decode=True, page_size=page, num_pages=slots * pages + 1,
        paged_kernel=kernel)
    cache = decode.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32))["cache"]
    table = np.zeros((slots, pages), np.int32)
    table[1] = 1 + np.arange(pages)
    run = jax.jit(
        lambda cache, toks, tables, lens, slot, **kw: decode.apply(
            {"params": params, "cache": cache}, toks, block_tables=tables,
            seq_lens=lens, state_slots=slot, mutable=["cache"], **kw))
    done = 0
    with jax.default_matmul_precision("highest"):
        for n, width in pieces:
            piece = np.zeros((1, width), np.int32)
            piece[0, :n] = tokens[done:done + n]
            _, updated = run(
                cache, jnp.asarray(piece), jnp.asarray(table[1:2]),
                jnp.asarray([done]), jnp.asarray([1]),
                valid_lens=jnp.asarray([n]))
            cache = updated["cache"]
            if tail_fault:  # the conv's tail not carried from piece to piece
                cache = jax.tree_util.tree_map_with_path(
                    lambda path, x: jnp.zeros_like(x)
                    if path[-1].key == "conv_state" else x, cache)
            done += n
        rows = []
        for t in range(done, len(tokens)):
            toks = np.zeros((slots, 1), np.int32)
            toks[1, 0] = tokens[t]
            logits, updated = run(
                cache, jnp.asarray(toks), jnp.asarray(table),
                jnp.asarray([0, t, 0]), jnp.asarray([-1, 1, -1]))
            cache = updated["cache"]
            rows.append(np.asarray(logits[1, 0]))
    states = driver.scan_states(cache, 1, heads=HEADS)
    return np.stack(rows), states, done


PIECES = [(40, 64), (20, 32), (5, 16)]  # a prompt of 65 in three padded pieces


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_prefill_pieces_then_decode_is_the_references_full_pass(
        weights, tokens, wanted, kernel):
    got, states, done = cached_logits(weights, tokens, PIECES, kernel=kernel)
    assert np.abs(got - wanted[done:]).max() < 2e-4 * np.abs(wanted).max()
    want = np.asarray(reference.final_states(CFG, weights, tokens))
    assert states.shape == want.shape == (3, HEADS, DK, DV)
    assert np.abs(states - want).max() < 2e-4 * np.abs(want).max()


def test_a_conv_tail_not_carried_is_caught(weights, tokens, wanted):
    got, _, done = cached_logits(weights, tokens, PIECES, tail_fault=True)
    assert np.abs(got - wanted[done:]).max() > 0.02 * np.abs(wanted).max()


def test_a_state_kept_in_bfloat16_is_caught(
        weights, tokens, wanted, monkeypatch):
    sound, sound_states, done = cached_logits(weights, tokens, PIECES)
    got, states, _ = cached_logits(
        weights, tokens, PIECES, state_dtype=jnp.bfloat16,
        monkeypatch=monkeypatch)
    want = np.asarray(reference.final_states(CFG, weights, tokens))
    gap = lambda s: np.linalg.norm(s[0] - want[0]) / np.linalg.norm(want[0])  # noqa: E731
    assert gap(states) > 1e-3 > 20 * gap(sound_states)
    scale = np.abs(wanted).max()
    assert np.abs(got - wanted[done:]).max() > 20 * max(
        np.abs(sound - wanted[done:]).max(), 1e-6 * scale)


# ------------------------------------------------------------- the engine


def test_the_engine_serves_it_beside_other_requests_and_after_preemption(
        weights):
    """Six requests on three slots and too few pages: some are preempted and
    prefilled again from position 0 (``state.reset``); every request's
    greedy tokens are the ones it gets served alone, and the reference's."""
    from distributed_pytorch_tpu.obs.tracer import Tracer
    from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

    model, params = driver.build_program(CFG, weights)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 1024, size=n).tolist()
               for n in (20, 33, 12, 40, 9, 27)]
    want = 24

    def serve(which, **kw):
        tracer = Tracer()
        engine = InferenceEngine(
            model, params, max_seq_len=128, page_size=16,
            max_prefill_chunk=32, paged_kernel="auto", prefix_cache=False,
            overlap=True, tracer=tracer, **kw)
        with jax.default_matmul_precision("highest"):
            ids = [engine.submit(prompts[i], SamplingParams(max_new_tokens=want))
                   for i in which]
            engine.run()
        out = [engine.poll(i).generated for i in ids]
        stats = engine.stats()
        steps = [e["args"] for e in tracer.events
                 if e["name"] == "step" and e.get("ph") == "X"]
        chunks = [e["args"] for e in tracer.events
                  if e["name"] == "prefill.chunk" and e.get("ph") == "X"]
        engine.close()
        return out, stats, steps, chunks, engine

    together, stats, steps, chunks, engine = serve(
        range(6), max_slots=3, num_pages=9, token_budget=35)
    assert stats["preemptions"] > 0
    assert engine.state_layers == engine.reads.layers["gated_delta"] == 3
    # [3 slots, 2 packed heads, 16, 128] float32 + a conv tail of 3 x 384
    assert engine.state_bytes_per_slot == 3 * (4 * 16 * 64 + 3 * 384) * 4
    for i, got in enumerate(together):
        if i in (1, 3):  # the longest prompts, served alone
            alone, *_ = serve([i], max_slots=3, num_pages=25, token_budget=35)
            assert got == alone[0], i
        tokens = prompts[i] + got
        logits = np.asarray(reference.logits_at(
            CFG, weights, tokens,
            [len(prompts[i]) - 1 + j for j in range(want)]))
        gaps = logits.max(axis=-1) - logits[np.arange(want), got]
        assert gaps.max() < 1e-3
    # The counters: live rows x 3 gated-delta layers a decode program, each
    # state once in and once out; blocks a prefill piece.
    moved = 2 * HEADS * DK * DV * 4
    assert all(s["state_bytes_moved"] == s["state_slots_updated"] * moved
               and s["state_slots_updated"] == 3 * s["decode_rows"]
               for s in steps)
    assert stats["state_slots_updated"] == sum(
        s["state_slots_updated"] for s in steps) > 0
    assert stats["state_bytes_moved"] == stats["state_slots_updated"] * moved
    assert chunks and all(c["state_blocks"] == 1 for c in chunks)  # width 32
