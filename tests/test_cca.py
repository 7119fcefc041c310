"""``models/cca.py``'s CCAttention, a layer that keeps K/V pages AND a slot
state: its two uses (a prefill piece with the slot's tails as its left border,
the batched decode step over the slot table) against each other and against
the plain forward; the rules the engine hangs on a recurrent layer (the row
mask, a position 0 starts from zeros, padding leaves the state alone), for a
layer that also has a block table; the interpreted K/V kernel beside the
gather path; the rotation over part of a head; and what the engine's spans and
counters say of such a model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zaya_toy import LOGIT_TOL, TOY, reference, tokens, toy_program

from distributed_pytorch_tpu.models.cca import CCAttention
from distributed_pytorch_tpu.models.transformer import apply_rope
from distributed_pytorch_tpu.obs.tracer import Tracer
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

D_MODEL, HEADS, KV, DH, SLOTS, PAGE, PAGES = 32, 8, 2, 8, 3, 4, 24
CHANS, HALF = (HEADS + KV) * DH, KV * DH // 2


def layer(**kw):
    return CCAttention(
        D_MODEL, HEADS, KV, DH, rotary_dim=4, decode=True, page_size=PAGE,
        num_pages=PAGES, **kw)


@pytest.fixture(scope="module")
def setup():
    """``(params, an empty cache, inputs [T, d])`` of one layer."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 24, D_MODEL)), jnp.float32)
    variables = layer().init(
        jax.random.PRNGKey(1), jnp.zeros((SLOTS, 1, D_MODEL)))
    params = jax.tree_util.tree_map(
        lambda p: jnp.asarray(
            rng.standard_normal(p.shape) * 0.3, p.dtype), variables["params"])
    return params, variables["cache"], x


def piece(params, cache, x, start, slot, valid=None, kernel="", width=None):
    """One prefill piece ``x [1, t, d]`` of slot ``slot`` from ``start`` on
    (its table: pages ``1 + 6 slot`` on): ``(out, cache)``."""
    t = x.shape[1]
    width = width or t
    x = jnp.pad(x, [(0, 0), (0, width - t), (0, 0)])
    table = jnp.asarray([[1 + 6 * slot + i for i in range(6)]], jnp.int32)
    out, updated = layer(paged_kernel=kernel).apply(
        {"params": params, "cache": cache}, x, block_tables=table,
        seq_lens=jnp.asarray([start], jnp.int32),
        state_slots=jnp.asarray([slot], jnp.int32),
        valid_lens=None if valid is None else jnp.asarray([valid], jnp.int32),
        mutable=["cache"])
    return out[:, :t], updated["cache"]


def decode(params, cache, x_rows, positions, live, kernel=""):
    """The batched decode step: row ``r`` is slot ``r``."""
    tables = jnp.asarray(
        [[1 + 6 * r + i for i in range(6)] if live[r] else [0] * 6
         for r in range(SLOTS)], jnp.int32)
    slots = jnp.asarray(
        [r if live[r] else -1 for r in range(SLOTS)], jnp.int32)
    out, updated = layer(paged_kernel=kernel).apply(
        {"params": params, "cache": cache}, x_rows, block_tables=tables,
        seq_lens=jnp.asarray(positions, jnp.int32), state_slots=slots,
        mutable=["cache"])
    return out, updated["cache"]


def plain(params, x):
    """The whole sequence in ONE causal forward, no cache."""
    return CCAttention(D_MODEL, HEADS, KV, DH, rotary_dim=4).apply(
        {"params": params}, x)


@pytest.mark.parametrize("sizes", [(24,), (1,) * 24, (3, 8, 13), (8, 8, 8)])
def test_pieces_of_any_sizes_are_the_one_forward(setup, sizes):
    params, cache, x = setup
    want = np.asarray(plain(params, x))
    outs, start = [], 0
    for n in sizes:
        out, cache = piece(params, cache, x[:, start : start + n], start, 1)
        outs.append(np.asarray(out))
        start += n
    assert np.abs(np.concatenate(outs, axis=1) - want).max() < 2e-5


@pytest.mark.parametrize("kernel", ["", "interpret"])
def test_a_piece_then_decode_steps_are_the_one_forward(setup, kernel):
    """Slot 1 prefills 17 tokens (the last piece padded from 5 to 8), then
    decodes 7 one at a time beside an absent row and a row at position 0 whose
    slot held garbage: the interpreted kernel beside the gather path."""
    params, cache, x = setup
    want = np.asarray(plain(params, x))
    garbage = jax.tree_util.tree_map(
        lambda leaf: jnp.full_like(leaf, 7.0), cache)
    cache = garbage  # every slot's state and every page hold garbage
    _, cache = piece(params, cache, x[:, :12], 0, 1)
    _, cache = piece(params, cache, x[:, 12:17], 12, 1, valid=5, width=8)
    before = {k: np.asarray(v) for k, v in cache.items() if "state" in k}
    for pos in range(17, 24):
        rows = jnp.stack([x[0, 0], x[0, pos], x[0, 3]])[:, None]
        out, cache = decode(
            params, cache, rows, [0, pos, 9], [True, True, False], kernel)
        assert np.abs(np.asarray(out[1, 0]) - want[0, pos]).max() < 2e-5
        # Row 0 stands at position 0: zeros in place of what its slot held.
        assert np.abs(np.asarray(out[0, 0]) - want[0, 0]).max() < 2e-5
    for key, was in before.items():
        # The absent row's state, bit for bit; the live rows' moved.
        assert np.array_equal(np.asarray(cache[key])[2], was[2])
        assert not np.array_equal(np.asarray(cache[key])[1], was[1])


def test_padding_leaves_the_state_where_the_last_real_token_put_it(setup):
    params, cache, x = setup
    _, bare = piece(params, cache, x[:, :5], 0, 0)
    _, padded = piece(params, cache, x[:, :5], 0, 0, valid=5, width=16)
    _, nothing = piece(params, bare, x[:, 5:6], 5, 0, valid=0, width=8)
    for key in ("conv_state", "scan_state"):
        assert np.array_equal(np.asarray(bare[key]), np.asarray(padded[key]))
        assert np.array_equal(np.asarray(bare[key]), np.asarray(nothing[key]))
    assert bare["conv_state"].shape == (SLOTS, 2, CHANS)
    assert bare["scan_state"].shape == (SLOTS, HALF)
    assert bare["conv_state"].dtype == bare["scan_state"].dtype == jnp.float32
    # The padding's K and V went to the null page: the row's own pages hold
    # its five tokens (pages 1 and 2) and nothing past them.
    for key in ("cached_key", "cached_value"):
        assert not np.asarray(padded[key])[3:].any()
        assert np.array_equal(
            np.asarray(padded[key])[1:3], np.asarray(bare[key])[1:3])
        assert np.asarray(padded[key])[2, 0].any()
        assert not np.asarray(padded[key])[2, 1:].any()


def test_a_decode_mode_layer_without_its_operands_is_refused(setup):
    params, cache, x = setup
    with pytest.raises(ValueError, match="block_tables, seq_lens and state"):
        layer().apply({"params": params, "cache": cache}, x[:, :1],
                      mutable=["cache"])
    with pytest.raises(ValueError, match="keeps its K and V in pages"):
        CCAttention(D_MODEL, HEADS, KV, DH, decode=True).init(
            jax.random.PRNGKey(0), x[:, :1])


@pytest.mark.parametrize("rotary_dim", [4, 8, None])
def test_the_rotation_takes_the_width_it_rotates(rotary_dim):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 8)), jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 7], [0, 1, 2, 3, 4]])
    got = apply_rope(x, theta=100.0, positions=pos, rotary_dim=rotary_dim)
    n = rotary_dim or 8
    want = apply_rope(x[..., :n], theta=100.0, positions=pos)
    assert np.array_equal(np.asarray(got[..., :n]), np.asarray(want))
    assert np.array_equal(np.asarray(got[..., n:]), np.asarray(x[..., n:]))
    if n == 8:  # the whole head: the function it always was
        assert np.array_equal(
            np.asarray(got), np.asarray(apply_rope(x, theta=100.0, positions=pos)))


def test_the_plain_forward_of_the_model_is_the_reference():
    weights, model, params = toy_program()
    toks = tokens(21, seed=1)
    want = np.asarray(reference.forward(TOY, weights, toks))
    got = np.asarray(model.apply({"params": params}, jnp.asarray([toks])))[0]
    assert np.abs(got - want).max() < LOGIT_TOL


ENGINE = dict(max_slots=3, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=11, prefix_cache=False, debug=True)


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_the_engines_spans_and_counters_for_pages_and_state_at_once(kernel):
    """Two requests through a traced engine: the ``step`` slices' state
    counters (rows x layers, each state once in and once out), the
    ``prefill.chunk`` slices' ``state_blocks`` and key counts, a
    ``state.reset`` an admission, the ``moe.routing`` instants at ONE expert
    a token, ``routing_counts [layers, experts]``, and what ``stats()`` names."""
    weights, model, params = toy_program()
    layers, experts = TOY["num_hidden_layers"], TOY["num_experts"]
    tracer = Tracer()
    engine = InferenceEngine(
        model, params, tracer=tracer, paged_kernel=kernel, **ENGINE)
    state = (2 * (8 + 2) * 8 + 2 * 8 // 2) * 4  # u, a and half a value, f32
    assert engine.state_layers == layers
    assert engine.state_bytes_per_slot == layers * state
    assert engine.page_bytes_per_token_layer == 2 * 2 * 8 * 4
    ids = [engine.submit(tokens(n, seed=n), SamplingParams(max_new_tokens=6))
           for n in (13, 22)]
    seen = []
    while engine.scheduler.has_work or engine._inflight is not None:
        engine.step()
        seen.extend(np.asarray(c).shape for c in engine.routing_counts)
    assert all(engine.poll(rid).state == "finished" for rid in ids)
    engine.finish_inflight()
    assert seen and set(seen) == {(layers, experts)}
    events = tracer.events
    steps = [e["args"] for e in events
             if e["name"] == "step" and e.get("ph") == "X"]
    assert all(a["state_bytes_moved"]
               == 2 * state * a["state_slots_updated"] for a in steps)
    assert max(a["state_slots_updated"] for a in steps) == 2 * layers
    assert sum(a["state_slots_updated"] for a in steps) == (
        engine.stats()["state_slots_updated"]) > 0
    pieces = [e["args"] for e in events
              if e["name"] == "prefill.chunk" and e.get("ph") == "X"]
    assert len(pieces) == 2 + 3 and all(a["state_blocks"] == 1 for a in pieces)
    assert all(a["keys_walked"] <= a["keys_table"] == 64 for a in pieces)
    assert sum(e["name"] == "state.reset" for e in events) == 2
    routed = [e["args"] for e in events if e["name"] == "moe.routing"]
    assert routed
    for a in routed:
        # ONE expert a token and layer, every expert held.
        assert a["moe_pairs_absent"] == 0
        assert a["moe_pairs_held"] % layers == 0
        assert a["moe_experts_hit"] <= a["moe_pairs_held"]
        assert (a["moe_tokens_per_expert_mean"] * experts
                == pytest.approx(a["moe_pairs_held"]))
        assert (a["moe_tokens_per_expert_max"]
                >= a["moe_tokens_per_expert_mean"])
    total = sum(a["moe_pairs_held"] for a in routed)
    assert total == layers * (12 + 21 + 6 + 6)  # every token fed, once
    stats = engine.stats()
    assert stats["layer_kinds"] == "cca" and stats["moe_router"] == "mlp_carry"
    assert stats["moe_pairs_held"] == total
    assert stats["moe_product"] == ("interpret" if kernel else "xla")
    engine.close()


def test_a_model_without_such_layers_says_so():
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = InferenceEngine(model, params, **ENGINE)
    stats = engine.stats()
    assert stats["layer_kinds"] == "attention" and "moe_router" not in stats
    assert engine.state_layers == 0 and "state_slots_updated" not in stats
    engine.close()
