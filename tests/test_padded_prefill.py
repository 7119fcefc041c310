"""A prefill piece padded to its program's width leaves what the same tokens
leave through a program of their exact length.

The engine runs a piece of a prompt in the program of the next width and
hands the model the piece's valid length (``valid_lens``); each layer kind
has to leave the padding out of everything it keeps or counts:

* attention: a padded position's K/V goes to the null page;
* S6 (``models/mamba.py``) and Mamba-2 (``models/mamba2.py``): ``delta`` is
  zero there, and the conv's tail ends at the valid length;
* routed experts (``models/moe.py``): a padded token reaches no expert and
  is in no ``routing`` count.

Everything is float32 and compared tightly: what differs between the two
programs is the shape of their matmuls and nothing else. Each fault a layer
could have is planted once and has to show.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import granite_toy  # noqa: E402
import hybrid_toy  # noqa: E402
from distributed_pytorch_tpu.models import mamba, transformer  # noqa: E402
from distributed_pytorch_tpu.models.mamba import STATE_KEYS  # noqa: E402
from distributed_pytorch_tpu.models.transformer import TransformerLM  # noqa: E402

WIDTH = 8
PAGE = 4
PAGES_PER_SEQ = 8
NUM_PAGES = 12
SLOTS = 2
#: Tokens a request already holds when the piece arrives: not a whole page,
#: so the piece starts inside one.
BEFORE = 5
#: float32 sums in another order (a ``[1, 8, d]`` product against a ``[1, v,
#: d]`` one): measured under 4e-7 on values of order 1.
TOL = dict(rtol=2e-5, atol=2e-6)


def attention_toy():
    model = TransformerLM(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2, dtype=jnp.float32,
    )
    params = model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def programs():
    """kind -> (decode-mode model, params): attention alone; S6 layers with
    attention; Mamba-2 layers with attention and routed experts."""
    return {
        kind: (
            model.clone(decode=True, page_size=PAGE, num_pages=NUM_PAGES),
            params,
        )
        for kind, (model, params) in (
            ("attention", attention_toy()),
            ("s6", hybrid_toy.toy_program()[1:]),
            ("mamba2_routed", granite_toy.toy_program()[1:]),
        )
    }


def zero_cache(model):
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((SLOTS, 1), jnp.int32)
    )["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), abstract)


def run(model, params, cache, tokens, start, valid=None, slot=1):
    """One ``[1, T]`` call of the decode-mode model on ``slot``'s pages and
    state, as the engine's prefill and decode programs make it (jitted: a
    shape compiles once, where eager flax would compile every operation of
    it). Returns ``(logits, cache, routing counts or None)``."""
    table = np.zeros((1, PAGES_PER_SEQ), np.int32)
    table[0, :4] = [3, 7, 2, 9]  # pages of 4: positions 0..15
    logits, updated = _apply(
        model, valid is not None, params, cache,
        jnp.asarray([tokens], jnp.int32), jnp.asarray(table),
        jnp.asarray([start], jnp.int32), jnp.asarray([slot], jnp.int32),
        jnp.asarray([valid or 0], jnp.int32),
    )
    counts = None
    if "routing" in updated:
        counts = np.stack([
            np.asarray(layer["experts"]["counts"][0])
            for _, layer in sorted(updated["routing"].items())
        ])
    return logits, updated["cache"], counts


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply(model, padded, params, cache, tokens, table, start, slot, valid):
    kw = {}
    if getattr(model, "recurrent_layers", 0) or model.routed_layers:
        kw["state_slots"] = slot
    if padded:
        kw["valid_lens"] = valid
    return model.apply(
        {"params": params, "cache": cache}, tokens, block_tables=table,
        seq_lens=start, mutable=["cache", "routing"], **kw,
    )


def leaves(cache):
    """path -> array of what a later call can read: the states whole, the
    page pools without the null page."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        leaf = np.asarray(leaf)
        out[name] = leaf if path[-1].key in STATE_KEYS else leaf[1:]
    return out


def both_ways(model, params, valid, seed=0):
    """The same ``valid`` tokens after ``BEFORE`` earlier ones, through the
    padded program and through the exact-length one, each followed by one
    decode step. Returns two dicts of everything a piece leaves behind."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, 128, size=BEFORE + WIDTH + 1).tolist()
    _, base, _ = run(model, params, zero_cache(model), toks[:BEFORE], 0)
    piece = toks[BEFORE : BEFORE + valid]
    nxt = toks[BEFORE + valid]
    out = []
    for tokens, kw in (
        (piece + [0] * (WIDTH - valid), dict(valid=valid)),
        (piece, {}),
    ):
        _, cache, counts = run(model, params, base, tokens, BEFORE, **kw)
        logits, _, _ = run(model, params, cache, [nxt], BEFORE + valid)
        left = leaves(cache)
        left["next_logits"] = np.asarray(logits[0, -1])
        if counts is not None:
            left["routing"] = counts
        out.append(left)
    return out


def gaps(padded, exact):
    """name -> the largest difference, relative to the tolerance (> 1: the
    two programs disagree there)."""
    out = {}
    for name, want in exact.items():
        got = padded[name]
        assert got.shape == want.shape, name
        if name == "routing":
            out[name] = float(np.abs(got - want).max())
        else:
            bound = TOL["atol"] + TOL["rtol"] * np.abs(want)
            out[name] = float((np.abs(got - want) / bound).max())
    return out


@pytest.mark.parametrize("valid", range(1, WIDTH + 1))
@pytest.mark.parametrize("kind", ["attention", "s6", "mamba2_routed"])
def test_a_padded_piece_leaves_what_the_exact_program_leaves(
    programs, kind, valid
):
    model, params = programs[kind]
    padded, exact = both_ways(model, params, valid, seed=valid)
    if kind == "mamba2_routed":
        # the counts are of the piece's own tokens: top 3 of 8 each
        assert (padded["routing"].sum(axis=-1) == 3 * valid).all()
        assert (padded["routing"] == exact["routing"]).all()
    if kind != "attention":
        assert any(k.endswith("scan_state") for k in padded)
        assert any(k.endswith("conv_state") for k in padded)
    worst = gaps(padded, exact)
    assert max(worst.values()) <= 1.0, worst


def test_the_padding_writes_the_null_page_alone(programs):
    """Pages the piece's own tokens do not reach keep their bytes: the
    padding's K/V is on page 0, not on the row's next pages."""
    model, params = programs["attention"]
    cache = jax.tree_util.tree_map(
        lambda x: jnp.full(x.shape, 7.0, x.dtype), zero_cache(model))
    _, after, _ = run(model, params, cache, [5, 6, 7, 0, 0, 0, 0, 0], 0, valid=3)
    for leaf in jax.tree_util.tree_leaves(after):
        leaf = np.asarray(leaf)
        assert (leaf[3, :3] != 7.0).all()  # positions 0..2, on page 3
        assert (leaf[3, 3:] == 7.0).all()
        assert (leaf[[1, 2, 4, 5, 6, 7, 8, 9, 10, 11]] == 7.0).all()
        assert (leaf[0] != 7.0).any()


class TestPlantedFaults:
    """Each way a layer could let the padding in, planted, has to show in
    what the piece leaves; ``valid`` 3 of 8 leaves five padded tokens."""

    @pytest.fixture(autouse=True)
    def retraced(self):
        """``_apply`` keeps its traces: one made before the fault was
        planted, or with it, must not serve the other side."""
        _apply.clear_cache()
        yield
        _apply.clear_cache()

    def test_delta_not_masked_moves_the_scan_state(self, programs, monkeypatch):
        monkeypatch.setattr(
            mamba, "token_mask",
            lambda valid_lens, t: jnp.ones((valid_lens.shape[0], t), bool))
        for kind in ("s6", "mamba2_routed"):
            worst = gaps(*both_ways(*programs[kind], 3))
            scan = max(v for k, v in worst.items() if k.endswith("scan_state"))
            assert scan > 100, (kind, worst)
            assert worst["next_logits"] > 1, (kind, worst)

    def test_conv_tail_taken_at_the_width(self, programs, monkeypatch):
        tail = mamba.conv_tail
        monkeypatch.setattr(
            mamba, "conv_tail",
            lambda padded, taps, valid_lens=None: tail(padded, taps))
        for kind in ("s6", "mamba2_routed"):
            worst = gaps(*both_ways(*programs[kind], 3))
            conv = max(v for k, v in worst.items() if k.endswith("conv_state"))
            assert conv > 100, (kind, worst)
            # ... and nothing else: the scan state is still the exact one
            assert max(v for k, v in worst.items()
                       if k.endswith("scan_state")) <= 1.0, (kind, worst)
            assert worst["next_logits"] > 1, (kind, worst)

    def test_a_padded_token_counted_by_the_router(self, programs, monkeypatch):
        monkeypatch.setattr(
            transformer, "live_tokens",
            lambda state_slots, valid_lens, t_step: (
                None if state_slots is None else state_slots >= 0))
        padded, exact = both_ways(*programs["mamba2_routed"], 3)
        assert (padded["routing"].sum(axis=-1) == 3 * WIDTH).all()
        assert gaps(padded, exact)["routing"] >= 1

    def test_padding_written_to_the_rows_own_pages(self, programs, monkeypatch):
        """Left out of ``_paged_decode_step``, the mask lets the padding's
        K/V onto the pages after the piece, where the exact program wrote
        nothing. (A read masks them, so only the pages show it.)"""
        monkeypatch.setattr(
            transformer, "token_mask",
            lambda valid_lens, t: jnp.ones((valid_lens.shape[0], t), bool))
        worst = gaps(*both_ways(*programs["attention"], 3))
        assert max(v for k, v in worst.items()
                   if k.endswith("cached_key")) > 100, worst
        assert worst["next_logits"] <= 1.0, worst
