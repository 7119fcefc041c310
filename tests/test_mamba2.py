"""The Mamba-2 mixer (``models/mamba2.py``) and the block's newer options
(score scale, multipliers, a feed-forward by layer), against
``benchmarks/reference/granite.py`` on seeded weights at toy widths. Logits
are compared, never sampled tokens; ``granite_toy.LOGIT_TOL`` says why the
tolerance is what it is."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from granite_toy import (
    LOGIT_TOL, ROOT, TOY, driver, reference, tokens, toy_program,
)

from distributed_pytorch_tpu.models import mamba
from distributed_pytorch_tpu.models.mamba2 import ssd_blocked, ssd_recurrence
from distributed_pytorch_tpu.models.transformer import TransformerLM


@pytest.fixture(scope="module")
def program():
    return toy_program()


def full_forward(model, params, toks):
    return np.asarray(model.apply({"params": params}, jnp.asarray([toks])))[0]


def ssd_inputs(t, *, heads=8, p=4, n=8, groups=2, batch=2, seed=0):
    """Random inputs of the recurrence with the published kind of numbers:
    ``A`` in [-16, -1], ``dt`` log-uniform in [1e-3, 1e-1]."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(
        np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (batch, t, heads))),
        jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    h0 = f(batch, heads, p, n)
    return f(batch, t, heads, p), dt, a, f(batch, t, groups, n), f(batch, t, groups, n), h0


@pytest.mark.parametrize("recurrence", ["blocked", "scan"])
def test_training_mode_forward_matches_the_reference(program, recurrence):
    """The whole model over a sequence against the reference, with the
    reference's recurrence written as the definition (a scan over tokens)
    and as the blocked sum: the program's blocks of 64 against both."""
    weights, model, params = program
    toks = tokens(48)
    want = np.asarray(reference.logits_at(
        TOY, weights, toks, range(48),
        recurrence=reference.ssd_scan if recurrence == "scan" else None))
    got = full_forward(model, params, toks)
    assert np.abs(got - want).max() < LOGIT_TOL
    assert np.abs(want).max() > 0.1  # the comparison is not of zeros


@pytest.mark.parametrize("t, block", [
    (32, 8), (32, 32), (32, 64),  # blocks that divide the chunk, one block
    (30, 8), (33, 16), (7, 4), (2, 64),  # and that do not
])
def test_the_blocked_evaluation_is_the_recurrence(t, block):
    """Float32 rounding apart: outputs of order 1, differences measured at
    under 2e-6, whatever the block length."""
    args = ssd_inputs(t, seed=t + block)
    y_ref, h_ref = ssd_recurrence(*args)
    y, h = ssd_blocked(*args, block)
    assert np.abs(np.asarray(y_ref)).max() > 0.1
    np.testing.assert_allclose(y, y_ref, atol=1e-5)
    np.testing.assert_allclose(h, h_ref, atol=1e-5)


@pytest.mark.parametrize("t, block", [(40, 8), (37, 8), (5, 16), (256, 64)])
def test_the_references_blocked_sum_is_its_recurrence(t, block):
    """The two forms in ``reference/granite.py``: the definition and the
    paper's listing, held together to float32 rounding."""
    x, dt, a, b, c, _ = (v[0] if v.ndim > 1 else v
                         for v in ssd_inputs(t, batch=1, seed=t))
    y_ref, h_ref = reference.ssd_scan(x, dt, a, b, c)
    y, h = reference.ssd_blocked(x, dt, a, b, c, block)
    np.testing.assert_allclose(y, y_ref, atol=1e-5)
    np.testing.assert_allclose(h, h_ref, atol=1e-5)


def test_program_and_reference_recurrences_agree():
    """The program's recurrence over a batch from a given state and the
    reference's over one sequence from zeros: the same definition."""
    x, dt, a, b, c, _ = ssd_inputs(20, batch=1, seed=5)
    h0 = jnp.zeros((1, 8, 4, 8), jnp.float32)
    y, h = ssd_recurrence(x, dt, a, b, c, h0)
    y_ref, h_ref = reference.ssd_scan(x[0], dt[0], a, b[0], c[0])
    np.testing.assert_allclose(y[0], y_ref, atol=1e-6)
    np.testing.assert_allclose(h[0], h_ref, atol=1e-6)


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


scan_states = driver.scan_states


def served_logits(model, params, toks, chunk, prefilled=32):
    """Every position's logits from a [1, chunk] program carrying slot 2's
    state chunk after chunk over the first ``prefilled`` tokens, then
    single-token steps of the whole slot table; and the cache it leaves."""
    slots, slot = 3, 2
    dm, cache = decode_model_and_cache(model, slots)
    table = np.zeros((slots, 16), np.int32)
    table[slot] = 1 + np.arange(16)
    got = []
    for start in range(0, prefilled, chunk):
        logits, cache = apply_decode(
            dm, params, cache, [toks[start:start + chunk]], table[slot][None],
            [start], [slot])
        got.extend(logits[0])
    for pos in range(prefilled, len(toks)):
        batch = np.zeros((slots, 1), np.int32)
        batch[slot] = toks[pos]
        lens = np.zeros(slots, np.int32)
        lens[slot] = pos
        logits, cache = apply_decode(
            dm, params, cache, batch, table, lens, [-1, -1, slot])
        got.append(logits[slot, 0])
    return np.stack(got), cache, slot


@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16, 32])
def test_prefill_in_chunks_then_decode_matches_the_full_forward(program, chunk):
    """Every split of the prompt a power of two allows, then decode mode
    token by token, against one pass over the sequence: every position's
    logits, and the states after the last token."""
    weights, model, params = program
    toks = tokens(40, seed=chunk)
    want = np.asarray(reference.logits_at(TOY, weights, toks, range(40)))
    got, cache, slot = served_logits(model, params, toks, chunk)
    assert np.abs(got - want).max() < LOGIT_TOL
    want_h = np.asarray(reference.final_states(TOY, weights, toks))
    assert np.abs(want_h).max() > 1e-3
    np.testing.assert_allclose(scan_states(cache, slot), want_h, atol=1e-5)


def test_a_row_outside_the_mask_keeps_its_state_bit_for_bit(program):
    _, model, params = program
    slots = 3
    dm, cache = decode_model_and_cache(model, slots)
    tables = 1 + np.arange(slots * 16, dtype=np.int32).reshape(slots, 16)
    batch = np.asarray([[5], [6], [7]], np.int32)
    _, cache = apply_decode(dm, params, cache, batch, tables, [0, 0, 0], [0, 1, 2])
    before = [np.asarray(v) for v in jax.tree_util.tree_leaves(cache)]
    _, after = apply_decode(
        dm, params, cache, batch, tables, [1, 1, 1], [0, -1, 2])
    moved = kept = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(after)[0]:
        was = before.pop(0)
        if path[-1].key not in mamba.STATE_KEYS:
            continue
        assert np.array_equal(np.asarray(leaf)[1], was[1])
        kept += 1
        moved += not np.array_equal(np.asarray(leaf)[0], was[0])
    assert kept == 4 and moved == 4  # 2 Mamba-2 layers x (conv tail, state)


def test_position_zero_starts_from_zeros_whatever_the_slot_held(program):
    _, model, params = program
    dm, cache = decode_model_and_cache(model, 1)
    table = (1 + np.arange(16, dtype=np.int32))[None]
    toks = [tokens(8, seed=3)]
    clean, _ = apply_decode(dm, params, cache, toks, table, [0], [0])
    dirty = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 3.0
        if path[-1].key in mamba.STATE_KEYS else leaf, cache)
    again, _ = apply_decode(dm, params, dirty, toks, table, [0], [0])
    assert np.array_equal(clean, again)


def state_gap(got, want):
    """``|h - h_ref| / |h_ref|`` of the first Mamba-2 layer, as the
    benchmark's driver judges it."""
    return np.linalg.norm(got[0] - want[0]) / np.linalg.norm(want[0])


def test_a_bfloat16_state_fails_the_tolerance_the_float32_state_meets(
        program, monkeypatch):
    """The planted fault ``control_hybrid_moe.py --state bfloat16`` plants at
    the cell's size: ``h`` kept in bfloat16 between chunks and tokens. The
    gated RMSNorm after the recurrence hides most of it from the logits
    (1e-6 here), so, as in the cell, the state itself is read: float32
    rounding leaves 1e-7 of it, bfloat16 1e-3."""
    weights, model, params = program
    toks = tokens(40, seed=9)
    want = np.asarray(reference.final_states(TOY, weights, toks))
    _, cache, slot = served_logits(model, params, toks, 4)
    assert state_gap(scan_states(cache, slot), want) < 1e-5
    monkeypatch.setattr(mamba, "STATE_DTYPE", jnp.bfloat16)
    _, cache, slot = served_logits(model, params, toks, 4)
    assert scan_states(cache, slot).dtype == np.float32  # read back as float32
    assert state_gap(scan_states(cache, slot), want) > 1e-3


@pytest.mark.parametrize("field, value", [
    ("attention_multiplier", None),  # scores scaled by head_dim ** -0.5
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0),  # a multiplier left out
])
def test_a_scale_or_a_multiplier_left_out_is_caught(program, field, value):
    weights, model, params = program
    toks = tokens(48, seed=1)
    want = np.asarray(reference.logits_at(TOY, weights, toks, range(48)))
    got = full_forward(model.clone(**{field: value}), params, toks)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_the_new_fields_at_their_defaults_change_nothing():
    """Every field this file's model uses, at its default, is the model of
    before: same parameters, same outputs, the same StableHLO, for the plain
    block and for a hybrid of the older kind."""
    kw = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4, d_ff=64,
              n_kv_heads=2, tie_embeddings=True)
    for extra in ({}, dict(
            norm="rmsnorm", mlp="gated_silu", use_bias=False, rope=False,
            layer_types=("mamba", "attention", "mamba"), mamba_dt_rank=4)):
        old = TransformerLM(**kw, **extra)
        new = TransformerLM(
            attention_multiplier=None, embedding_multiplier=1.0,
            residual_multiplier=1.0, logits_scaling=1.0, ffn_types=None,
            routed_experts=0, routed_top_k=0, experts_held=None,
            shared_d_ff=0, mamba_n_heads=0, mamba_d_head=0, mamba_n_groups=1,
            **kw, **extra)
        toks = jnp.asarray([tokens(12)]) % 64
        v_old = old.init(jax.random.PRNGKey(1), toks)
        v_new = new.init(jax.random.PRNGKey(1), toks)
        for a, b in zip(jax.tree_util.tree_leaves(v_old),
                        jax.tree_util.tree_leaves(v_new)):
            assert np.array_equal(a, b)
        text = lambda m: jax.jit(m.apply).lower(v_old, toks).as_text()  # noqa: E731
        assert text(old) == text(new)
        # ... and "dense" named for every layer is the dense feed-forward.
        named = old.clone(ffn_types=("dense",) * 3)
        assert text(old) == text(named)


@pytest.mark.parametrize("field, value, message", [
    ("layer_types", ("mamba2", "attention"), "needs mamba_n_heads"),
    ("ffn_types", ("routed", "dense"), "needs routed_experts"),
    ("ffn_types", ("dense",), "ffn_types names 1 layers"),
    ("ffn_types", ("dense", "sparse"), "unknown feed-forward"),
])
def test_an_unknown_or_unsized_option_is_refused(field, value, message):
    model = TransformerLM(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        **{field: value})
    with pytest.raises(ValueError, match=message):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_a_decode_mode_mamba2_layer_must_be_told_whose_state_it_carries(program):
    _, model, params = program
    dm, cache = decode_model_and_cache(model, 2)
    with pytest.raises(ValueError, match="requires state_slots"):
        dm.apply(
            {"params": params, "cache": cache}, jnp.zeros((2, 1), jnp.int32),
            block_tables=jnp.zeros((2, 16), jnp.int32),
            seq_lens=jnp.zeros((2,), jnp.int32), mutable=["cache"])


def test_the_published_configuration_counts_what_the_issue_counted():
    """The counts the roofline readers use, at the published widths and the
    chip's share: 4.963 B parameters held, 38.2 MB of state a sequence,
    4,096 B of KV a token."""
    with open(os.path.join(
            ROOT, "benchmarks/configs/granite-4.0-h-small.json")) as f:
        cfg = json.load(f)
    kinds = reference.layer_types(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5]
    assert len(kinds) == 10 and len(cfg["layer_types"]) == 40
    p = reference.matmul_params(cfg)
    assert abs(p["mamba"] / 1e6 - 102.24) < 0.01  # the mixer's two matrices
    assert p["attention"] == 41_943_040 and p["expert"] == 9_437_184
    assert p["shared"] == 18_874_368 and p["router"] == 4096 * 72
    assert abs(reference.held_parameters(cfg) / 1e9 - 4.963) < 0.001
    assert reference.state_bytes_per_slot(cfg) == 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert reference.kv_bytes_per_token(cfg) == 4096
    # One decode step of 64 rows at context 300: weights 9.9 GB, states
    # 2 x 64 x 38.2 MB, and little else: bound by memory.
    nbytes = reference.serve_min_bytes(cfg, 64, 0, 64 * 300, 0)
    assert 14.7e9 < nbytes < 15.0e9
    more = reference.serve_min_bytes(cfg, 64, 160, 64 * 300, 3)
    assert more - nbytes == 3 * 2 * reference.state_bytes_per_slot(cfg) + 160 * 4096
    flops = reference.serve_flops(cfg, 64, 64 * 300, 64)
    assert nbytes / 819e9 > flops / 197e12
    # Every held expert hit once by 320 pairs: 36 x 18.9 MB and the rows.
    assert reference.expert_min_bytes(cfg, 36, 320) == (
        36 * 2 * 9_437_184 + 320 * 2 * 2 * 4096)
    assert reference.expert_flops(cfg, 320) == 2 * 9_437_184 * 320
    # Whole, the model is what the issue counted: 32.2 B.
    whole = dict(cfg, num_hidden_layers=40, num_local_experts=72,
                 experts_held=[0, 72])
    assert abs(reference.held_parameters(whole) / 1e9 - 32.2) < 0.1
