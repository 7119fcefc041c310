"""Multi-head latent attention (``models/mla.py``) against the plain reference
(``benchmarks/reference/deepseek_v2.py``) at toy widths, seeded, float32.

* YaRN's numbers at the published sizes against hand-computed values;
* which form a call runs, by its arithmetic, and that the two forms give the
  same numbers;
* a prompt in pieces of every kind (exact, padded, one token at a time), then
  decode through latent pages, against the reference's one pass on logits;
* planted faults, each caught where it must show.
"""

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import deepseek_toy  # noqa: E402
from deepseek_toy import TOY, reference, tokens, toy_program  # noqa: E402
from distributed_pytorch_tpu.models import mla  # noqa: E402

PAGE = 4
PAGES_PER_SEQ = 8
NUM_PAGES = 12
SLOTS = 2
TABLE = [3, 7, 2, 9, 5, 0, 0, 0]  # pages of 4: positions 0..19
#: Logits of order 3; float32 sums in another order through 3 layers:
#: measured under 3e-6.
TOL = dict(rtol=2e-5, atol=2e-5)
PUBLISHED = dict(
    factor=40, original_max_position_embeddings=4096, beta_fast=32,
    beta_slow=1, mscale=0.707, mscale_all_dim=0.707)


# ------------------------------------------------------------------- YaRN


def test_yarn_correction_range_at_the_published_sizes():
    assert mla.yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)


def test_score_scale_at_the_published_sizes():
    g = 0.1 * 0.707 * math.log(40) + 1.0
    assert g == pytest.approx(1.26081, abs=1e-5)
    # 192 ** -0.5 = 0.0721688, g = 1.260804: 0.114721 (ISSUE 35 rounds g to
    # 1.26081 first and writes 0.114722).
    assert mla.score_scale(192, PUBLISHED) == pytest.approx(0.114722, abs=1e-6)
    assert mla.score_scale(192, None) == pytest.approx(192**-0.5)
    assert mla.rope_multiplier(PUBLISHED) == pytest.approx(1.0)


@pytest.mark.parametrize("i, want", [
    (0, 1.0),  # a fast pair keeps its frequency
    (10, 10000 ** (-10 / 32)),  # the last that does: m = 1
    # m = 1 - 6/13: f = 0.01, f / 40 = 0.00025
    (16, 0.01 * (7 / 13) + 0.00025 * (6 / 13)),
    (23, 10000 ** (-23 / 32) / 40),  # the first wholly interpolated
    (31, 10000 ** (-31 / 32) / 40),
])
def test_yarn_frequencies_against_hand_computed_values(i, want):
    freqs = np.asarray(mla.yarn_frequencies(64, 10000.0, PUBLISHED))
    assert freqs.shape == (32,)
    assert freqs[i] == pytest.approx(want, rel=2e-6)


def test_the_reference_computes_the_same_yarn():
    cfg = dict(TOY, qk_rope_head_dim=64, qk_nope_head_dim=128,
               rope_scaling=dict(PUBLISHED, type="yarn"))
    assert reference.yarn_range(cfg) == (10, 23)
    assert reference.score_scale(cfg) == pytest.approx(0.114722, abs=1e-6)
    np.testing.assert_allclose(
        np.asarray(reference.rope_frequencies(cfg)),
        np.asarray(mla.yarn_frequencies(64, 10000.0, PUBLISHED)), rtol=1e-6)


# ------------------------------------------------------ which form a call runs


@pytest.mark.parametrize("t_step, absorbed", [
    (1, True), (64, True), (128, True), (170, True), (171, False),
    (192, False), (512, False)])
def test_the_form_follows_the_arithmetic_at_the_published_sizes(
        t_step, absorbed):
    """By cached token: absorbed 2 t 16 (2 x 512 + 64), expanded 2 x 512 x 16
    x 256 + 2 t 16 x 320. Equal at t = 4,194,304 / 24,576 = 170.7."""
    assert mla.absorb(t_step, 16, 512, 128, 64, 128) is absorbed


# ----------------------------------------------------- pieces through pages


@pytest.fixture(scope="module")
def program():
    weights, model, params = toy_program()
    return weights, model.clone(
        decode=True, page_size=PAGE, num_pages=NUM_PAGES), params


def zero_cache(model, dtype=None):
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((SLOTS, 1), jnp.int32)
    )["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, dtype or s.dtype), abstract)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply(model, padded, params, cache, toks, table, start, valid):
    kw = {"valid_lens": valid} if padded else {}
    return model.apply(
        {"params": params, "cache": cache}, toks, block_tables=table,
        seq_lens=start, state_slots=jnp.asarray([1], jnp.int32),
        mutable=["cache", "routing"], **kw)


def run(model, params, cache, toks, start, width=None):
    """One ``[1, T]`` call of the decode-mode model on the fixed table, as
    the engine's programs make it; ``width`` pads the piece to a program of
    that width. Returns ``(the piece's own logits, cache)``."""
    n = len(toks)
    padded = list(toks) + [0] * ((width or n) - n)
    logits, updated = _apply(
        model, width is not None, params, cache,
        jnp.asarray([padded], jnp.int32), jnp.asarray([TABLE], jnp.int32),
        jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32))
    return np.asarray(logits[0, :n]), updated["cache"]


def through_pages(model, params, toks, pieces, cache=None):
    """``toks`` through the paged model in ``pieces`` ((length, width or
    None) pairs; what is left goes a token a call, as decode does): the
    logits of every position."""
    cache = zero_cache(model) if cache is None else cache
    out, start = [], 0
    for length, width in pieces:
        logits, cache = run(
            model, params, cache, toks[start : start + length], start, width)
        out.append(logits)
        start += length
    while start < len(toks):
        logits, cache = run(model, params, cache, toks[start : start + 1], start)
        out.append(logits)
        start += 1
    return np.concatenate(out), cache


SPLITS = {
    "one-piece": [(13, None)],
    "one-piece-padded": [(13, 16)],
    "a-token-a-call": [],
    "pages": [(4, None), (4, None), (4, None)],
    "ragged": [(5, None), (1, None), (6, None)],
    "ragged-padded": [(5, 8), (3, 8), (5, 8)],
    "long-then-short": [(11, 16), (2, 8)],
    "short-then-long": [(2, 8), (11, 16)],
    "inside-a-page": [(3, 4), (2, 4), (3, 4), (5, 8)],
}


@pytest.mark.parametrize("name", sorted(SPLITS))
def test_pieces_then_decode_through_pages_match_the_reference(program, name):
    """Every split of a 13-token prompt into pieces, padded ones included,
    then 5 tokens decoded through the latent pages: the logits of every
    position against the reference's one expanded pass."""
    weights, model, params = program
    toks = tokens(18, seed=3)
    got, _ = through_pages(model, params, toks, SPLITS[name])
    want = np.asarray(reference.logits_at(TOY, weights, toks, range(18)))
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("t_step", [1, 3, 8])
def test_absorbed_equals_expanded(program, monkeypatch, t_step):
    """The same piece over the same cached context in both forms."""
    _, model, params = program
    toks = tokens(9 + t_step, seed=5)
    _, cache = through_pages(model, params, toks[:9], [(9, None)])
    out = {}
    for form in (True, False):
        monkeypatch.setattr(mla, "absorb", lambda *a, form=form: form)
        _apply.clear_cache()
        out[form], _ = run(model, params, cache, toks[9:], 9)
    _apply.clear_cache()
    np.testing.assert_allclose(out[True], out[False], **TOL)
    assert np.abs(out[True] - out[False]).max() > 0  # two programs, not one


def test_pages_past_the_rows_length_are_invisible(program):
    """Property: what lies past a row's length is dead. Poisoning the null
    page and the row's later pages with huge finite garbage changes nothing
    (the toy's whole table is one block of the gather, so here the mask
    hides it; on a table of several blocks the trip count does)."""
    _, model, params = program
    toks = tokens(10, seed=7)
    _, cache = through_pages(model, params, toks[:6], [(6, None)])

    def poison(pool):
        return pool.at[jnp.asarray([0, 2, 9, 5])].set(1e4)

    clean, _ = run(model, params, cache, toks[6:8], 6)
    dirty, _ = run(
        model, params, jax.tree_util.tree_map(poison, cache), toks[6:8], 6)
    np.testing.assert_array_equal(dirty, clean)


def test_the_gather_stops_at_the_longest_rows_length():
    """A table of several blocks: the loop's trip count comes from the rows'
    lengths, so a block past them is never gathered, NaN or not."""
    rng = np.random.default_rng(0)
    page, pages_per_seq, rank, dr, h = 4, 8, 12, 4, 2
    pool = jnp.asarray(rng.standard_normal((9, page, 16)), jnp.float32)
    pool = pool.at[5:].set(jnp.nan)  # pages 5..8: positions 16..31
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, 2, h, rank)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((1, 2, h, dr)), jnp.float32)
    positions = jnp.asarray([[9, 10]], jnp.int32)
    was = mla.ATTEND_BLOCK_TOKENS
    try:
        mla.ATTEND_BLOCK_TOKENS = 8  # two pages a block, four blocks
        out = mla._attend_blocks(
            q, q_pe, pool, tables, positions, jnp.asarray(11), scale=0.3,
            rank=rank, dr=dr, w_kvb=None, dn=0)
    finally:
        mla.ATTEND_BLOCK_TOKENS = was
    assert np.isfinite(np.asarray(out)).all()
    keys = np.asarray(pool[1:4]).reshape(12, 16)
    s = (np.einsum("thr,kr->htk", np.asarray(q[0]), keys[:, :rank])
         + np.einsum("thd,kd->htk", np.asarray(q_pe[0]), keys[:, rank:])) * 0.3
    s = np.where(np.arange(12)[None, None] <= np.asarray(positions[0])[None, :, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("htk,kr->thr", p / p.sum(-1, keepdims=True), keys[:, :rank])
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------ planted faults


def _reference_logits(weights, toks):
    return np.asarray(reference.logits_at(TOY, weights, toks, range(len(toks))))


def _caught(program, toks=None, pieces=((7, 8),), cache=None):
    """How far the paged model's logits lie from the reference's, with
    whatever fault is planted at the moment."""
    weights, model, params = program
    toks = toks or tokens(12, seed=11)
    _apply.clear_cache()
    got, _ = through_pages(model, params, toks, list(pieces), cache)
    _apply.clear_cache()
    return np.abs(got - _reference_logits(weights, toks)).max()


def test_no_fault_reads_under_the_tolerance(program):
    assert _caught(program) < 2e-5


def test_fault_mscale_squared_left_out(program, monkeypatch):
    monkeypatch.setattr(
        mla, "score_scale", lambda qk, yarn: qk**-0.5)
    assert _caught(program) > 1e-3


def test_fault_k_pe_not_rotated(program, monkeypatch):
    rotate = mla.rotate

    def queries_only(x, *args):
        return rotate(x, *args) if x.ndim == 4 else x  # k_pe has no head axis

    monkeypatch.setattr(mla, "rotate", queries_only)
    assert _caught(program) > 1e-3


def test_fault_the_latents_norm_left_out(program, monkeypatch):
    import flax.linen as nn

    class NoNorm(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x

    monkeypatch.setattr(mla, "latent_norm", lambda eps: NoNorm(name="kv_norm"))
    assert _caught(program) > 1e-3


def test_fault_the_value_read_past_c(program, monkeypatch):
    """The value is the row's FIRST ``r`` numbers: read from its end, ``k_pe``
    and the padding lanes leak into it."""
    monkeypatch.setattr(
        mla, "value_of", lambda latent, rank: latent[..., -rank:])
    assert _caught(program) > 1e-3


def test_fault_padding_written_to_the_rows_own_pages(program, monkeypatch):
    """A padded piece leaves the pool as the exact-length piece leaves it;
    with the padding's mask gone it does not."""
    _, model, params = program
    toks = tokens(6, seed=13)

    def pools(width):
        _apply.clear_cache()
        _, cache = run(model, params, zero_cache(model), toks, 0, width)
        return [np.asarray(leaf)[1:] for leaf in jax.tree_util.tree_leaves(cache)]

    for exact, padded in zip(pools(None), pools(8)):
        np.testing.assert_allclose(padded, exact, rtol=1e-6, atol=1e-6)
    monkeypatch.setattr(
        mla, "token_mask", lambda valid, t: jnp.ones((len(valid), t), bool))
    differs = [np.abs(p - e).max() for e, p in zip(pools(None), pools(8))]
    _apply.clear_cache()
    assert max(differs) > 1e-3


def test_fault_a_bf16_latent_under_a_float32_configuration(program):
    _, model, _ = program
    assert _caught(
        program, cache=zero_cache(model, jnp.bfloat16)) > 1e-3


# ------------------------------------------------------------------ refusals


def test_a_contiguous_decode_cache_is_refused():
    _, model, params = toy_program()
    with pytest.raises(ValueError, match="served through pages"):
        model.clone(decode=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_latent_layers_need_their_sizes():
    _, model, _ = toy_program()
    with pytest.raises(ValueError, match="kv_lora_rank"):
        model.clone(kv_lora_rank=0).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_the_pool_is_padded_to_whole_lanes():
    layer = mla.LatentAttention(
        16, 2048, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128)
    assert (layer.latent_width, layer.pool_width) == (576, 640)
    assert deepseek_toy.TOY["kv_lora_rank"] + 4 == 16  # the toy's: 16 -> 128
