"""The Mamba mixer and the block options of ``models/transformer.py``,
against ``benchmarks/reference/jamba.py`` on seeded weights at toy widths.
Logits are compared, never sampled tokens; ``hybrid_toy.LOGIT_TOL`` says why
the tolerance is what it is."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_toy import (
    LOGIT_TOL, ROOT, SEED, TOY, reference, tokens, toy_program,
)

from distributed_pytorch_tpu.models.mamba import selective_scan
from distributed_pytorch_tpu.models.transformer import TransformerLM


def full_forward(model, params, toks):
    return np.asarray(model.apply({"params": params}, jnp.asarray([toks])))[0]


def test_training_mode_forward_matches_the_reference():
    weights, model, params = toy_program()
    toks = tokens(48)
    want = np.asarray(reference.logits_at(TOY, weights, toks, range(48)))
    got = full_forward(model, params, toks)
    assert np.abs(got - want).max() < LOGIT_TOL
    assert np.abs(want).max() > 1.0  # the comparison is not of zeros


def decode_model_and_cache(model, slots):
    dm = model.clone(decode=True, page_size=4, num_pages=1 + slots * 16)
    cache = dm.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32))["cache"]
    return dm, cache


def apply_decode(dm, params, cache, toks, table, start, slot_ids):
    logits, updated = dm.apply(
        {"params": params, "cache": cache}, jnp.asarray(toks, jnp.int32),
        block_tables=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.asarray(start, jnp.int32),
        state_slots=jnp.asarray(slot_ids, jnp.int32), mutable=["cache"])
    return np.asarray(logits), updated["cache"]


@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16, 32])
def test_prefill_in_chunks_then_decode_matches_the_full_forward(chunk):
    """A [1, chunk] program carrying slot 2's state chunk after chunk, then
    single-token steps of the whole slot table, against one pass over the
    sequence: every position's logits."""
    weights, model, params = toy_program()
    toks = tokens(40, seed=chunk)
    want = np.asarray(reference.logits_at(TOY, weights, toks, range(40)))
    slots, slot = 3, 2
    dm, cache = decode_model_and_cache(model, slots)
    table = np.zeros((slots, 16), np.int32)
    table[slot] = 1 + np.arange(16)
    got = []
    for start in range(0, 32, chunk):
        logits, cache = apply_decode(
            dm, params, cache, [toks[start:start + chunk]], table[slot][None],
            [start], [slot])
        got.append(logits[0])
    for pos in range(32, 40):
        row_toks = np.zeros((slots, 1), np.int32)
        row_toks[slot] = toks[pos]
        lens = np.zeros(slots, np.int32)
        lens[slot] = pos
        ids = np.full(slots, -1, np.int32)
        ids[slot] = slot
        tables = np.zeros_like(table)
        tables[slot] = table[slot]
        logits, cache = apply_decode(
            dm, params, cache, row_toks, tables, lens, ids)
        got.append(logits[slot])
    assert np.abs(np.concatenate(got) - want).max() < LOGIT_TOL


def states_of(cache):
    return {
        jax.tree_util.keystr(path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if path[-1].key in ("conv_state", "scan_state")
    }


def test_a_row_outside_the_mask_keeps_its_state_bit_for_bit():
    _, model, params = toy_program()
    slots = 3
    dm, cache = decode_model_and_cache(model, slots)
    tables = 1 + np.arange(slots * 16, dtype=np.int32).reshape(slots, 16)
    # Give every slot a state: one step of all rows from position 0.
    _, cache = apply_decode(
        dm, params, cache, [[5], [6], [7]], tables, [0, 0, 0], [0, 1, 2])
    before = states_of(cache)
    assert all(np.abs(v).max() > 0 for v in before.values())
    parked = np.array(tables)
    parked[1] = 0  # as the engine parks a row outside the group
    _, cache = apply_decode(
        dm, params, cache, [[8], [9], [10]], parked, [1, 0, 1], [0, -1, 2])
    after = states_of(cache)
    for name in before:
        assert np.array_equal(after[name][1], before[name][1]), name
        assert not np.array_equal(after[name][0], before[name][0]), name
        assert not np.array_equal(after[name][2], before[name][2]), name


def test_position_zero_starts_from_zeros_whatever_the_slot_held():
    _, model, params = toy_program()
    dm, clean = decode_model_and_cache(model, 2)
    table = (1 + np.arange(16, dtype=np.int32))[None]
    toks = [tokens(8)]
    want, _ = apply_decode(dm, params, clean, toks, table, [0], [1])
    dirty = jax.tree_util.tree_map(
        lambda leaf: jnp.full_like(leaf, 3.0), clean)
    got, _ = apply_decode(dm, params, dirty, toks, table, [0], [1])
    assert np.array_equal(got, want)
    # ... and from what the slot held at any other position.
    moved, _ = apply_decode(dm, params, dirty, toks, table, [8], [1])
    assert not np.allclose(moved, want, atol=1e-3)


def test_a_bfloat16_state_fails_the_tolerance_the_float32_state_meets(
        monkeypatch):
    """The configuration states a float32 scan state. Rounding ``h`` to
    bfloat16 after every token is another model, and the comparison that
    passes above has to say so."""
    from distributed_pytorch_tpu.models import mamba

    weights, model, params = toy_program()
    toks = tokens(64, seed=3)
    want = np.asarray(reference.logits_at(TOY, weights, toks, range(64)))
    assert np.abs(full_forward(model, params, toks) - want).max() < LOGIT_TOL
    monkeypatch.setattr(mamba, "STATE_DTYPE", jnp.bfloat16)
    low = np.abs(full_forward(model, params, toks) - want).max()
    assert low > 20 * LOGIT_TOL


def test_one_token_steps_and_the_scan_are_the_same_recurrence():
    rng = np.random.default_rng(1)
    b, t, di, n = 2, 9, 16, 4
    u, delta = rng.normal(size=(2, b, t, di)).astype(np.float32)
    delta = np.abs(delta) * 0.1
    bm, cm = rng.normal(size=(2, b, t, n)).astype(np.float32)
    a_t = -np.exp(rng.normal(size=(n, di))).astype(np.float32)
    h = jnp.zeros((b, n, di), jnp.float32)
    want_y, want_h = selective_scan(u, delta, a_t, bm, cm, h)
    ys = []
    for i in range(t):
        y, h = selective_scan(
            u[:, i:i + 1], delta[:, i:i + 1], a_t, bm[:, i:i + 1],
            cm[:, i:i + 1], h)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys, 1), want_y, atol=1e-6)
    np.testing.assert_allclose(h, want_h, atol=1e-6)


def test_all_attention_layer_types_with_the_old_defaults_change_nothing():
    """``layer_types`` naming attention everywhere, and every new field at
    its default, is today's model: same parameters, same outputs, bit for
    bit, and the same program."""
    kw = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4, d_ff=64,
              n_kv_heads=2, tie_embeddings=True)
    old = TransformerLM(**kw)
    new = TransformerLM(
        layer_types=("attention",) * 3, norm="layernorm", norm_eps=1e-6,
        mlp="gelu", use_bias=True, rope=True, **kw)
    toks = jnp.asarray([tokens(12)]) % 64
    v_old = old.init(jax.random.PRNGKey(1), toks)
    v_new = new.init(jax.random.PRNGKey(1), toks)
    assert jax.tree_util.tree_structure(v_old) == jax.tree_util.tree_structure(v_new)
    for a, b in zip(jax.tree_util.tree_leaves(v_old), jax.tree_util.tree_leaves(v_new)):
        assert np.array_equal(a, b)
    assert np.array_equal(old.apply(v_old, toks), new.apply(v_old, toks))
    text = lambda m: jax.jit(m.apply).lower(v_old, toks).as_text()  # noqa: E731
    assert text(old) == text(new)


@pytest.mark.parametrize("field, value, message", [
    ("norm", "batchnorm", "unknown norm"),
    ("mlp", "swiglu2", "unknown mlp kind"),
    ("layer_types", ("attention", "rwkv"), "unknown layer type"),
    ("layer_types", ("attention",), "layer_types names 1 layers"),
])
def test_an_unknown_block_option_is_refused(field, value, message):
    model = TransformerLM(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        **{field: value})
    with pytest.raises(ValueError, match=message):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_a_decode_mode_mamba_layer_must_be_told_whose_state_it_carries():
    _, model, params = toy_program()
    dm, cache = decode_model_and_cache(model, 2)
    with pytest.raises(ValueError, match="requires state_slots"):
        dm.apply(
            {"params": params, "cache": cache}, jnp.zeros((2, 1), jnp.int32),
            block_tables=jnp.zeros((2, 16), jnp.int32),
            seq_lens=jnp.zeros((2,), jnp.int32), mutable=["cache"])


def test_the_published_configuration_counts_what_the_issue_counted():
    """The counts the roofline readers use, at the published widths:
    3.029 B parameters, 9.32 MB of state a sequence, 1,024 B of KV a token."""
    with open(os.path.join(ROOT, "benchmarks/configs/jamba2-3b.json")) as f:
        cfg = json.load(f)
    kinds = reference.layer_types(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    p = reference.matmul_params(cfg)
    norms = 26 * (2 * 2560 + 160 + 32) + 2 * 2 * 2560 + 2560
    small = 26 * (4 * 5120 + 5120 + 5120 * 16 + 5120 + 5120)
    total = 26 * p["mamba"] + 2 * p["attention"] + p["head"] + norms + small
    assert abs(total / 1e9 - 3.029) < 0.002
    assert reference.state_bytes_per_slot(cfg) == 26 * (5120 * 16 * 4 + 5120 * 3 * 2)
    assert reference.kv_bytes_per_token(cfg) == 1024
    # One decode step of 128 rows at context 300: weights 6.06 GB, states
    # 2 x 128 x 9.32 MB, and little else: bound by memory.
    nbytes = reference.serve_min_bytes(cfg, 128, 0, 128 * 300, 0)
    assert 8.2e9 < nbytes < 8.8e9
    # A step's prefill chunks add their states and their tokens' KV, and no
    # second reading of the weights: the floor is the step's, not a program's.
    more = reference.serve_min_bytes(cfg, 128, 160, 128 * 300, 6)
    assert more - nbytes == 6 * 2 * reference.state_bytes_per_slot(cfg) + 160 * 1024
    flops = reference.serve_flops(cfg, 128, 128 * 300, 128)
    assert flops / 197e12 < nbytes / 819e9
