"""Learned sparse attention over latent pages, windowed latent attention and
sigmoid routing (``models/mla.py``, ``models/moe.py``,
``ops/paged_attention.py``, ``serving/engine.py``) against the plain reference
(``benchmarks/reference/dots3_note.py``) at toy widths, seeded, float32.

* the exact top-k, the index-score kernel, the sparse decode and the windowed
  latent kernel, each against its plain form;
* a prompt in pieces, then decode through the three kinds of pool, against
  the reference's one pass on LOGITS, with planted faults that must show;
* the model through ``InferenceEngine``: two pools a full layer and pools of
  three widths under one allocator, table, trie and copy-on-write; the
  counters; what is refused;
* the third gating rule, and the share tied to the model.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from dots3_toy import (  # noqa: E402
    LOGIT_TOL, SEED, TOY, reference, share, tokens, toy_program,
)
from distributed_pytorch_tpu.models import mla, moe  # noqa: E402
from distributed_pytorch_tpu.ops import paged_attention as pa  # noqa: E402

# ------------------------------------------------------------------- the ops


def scores_with_ties(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(5, 300)).astype(np.float32)
    x[0, :50] = 1.5  # ties at the top
    x[1, 100:] = -np.inf  # fewer finite scores than k
    x[2] = np.where(rng.random(300) < 0.5, 0.0, x[2])  # ties in the middle
    x[3] = -np.abs(x[3])  # all negative
    return x


@pytest.mark.parametrize("k", [1, 7, 64, 299, 300, 400])
def test_the_top_k_is_exact_and_breaks_ties_as_lax_top_k_does(k):
    x = scores_with_ties()
    mask = np.asarray(pa.top_k_mask(jnp.asarray(x), k))
    for r, row in enumerate(x):
        finite = int(np.isfinite(row).sum())
        _, where = jax.lax.top_k(jnp.asarray(row), min(k, 300))
        want = np.zeros(300, bool)
        want[np.asarray(where)[: min(k, finite)]] = True
        assert (mask[r] == want).all(), (k, r)
    if k <= 300:
        held_to_flatnonzero(mask, k)


@functools.cache
def masks_by_hand():
    """name -> (mask [rows, N], k): what a top-k's mask can hold (never more
    than ``k`` true a row), by hand and at random."""
    rng = np.random.default_rng(5)

    def drawn(n, counts):
        mask = np.zeros((len(counts), n), bool)
        for row, count in zip(mask, counts):
            row[rng.choice(n, count, replace=False)] = True
        return mask

    def at(n, *rows):
        mask = np.zeros((len(rows), n), bool)
        for row, where in zip(mask, rows):
            row[list(where)] = True
        return mask

    return {
        # (i) no true position, one, exactly k, fewer than k
        "none-one-k-fewer": (drawn(1000, [0, 1, 64, 37]), 64),
        # (ii) all in one block of 128, all in the last block, lanes 0 and 127
        "one-block": (at(1024, range(384, 512, 3), range(1000, 1024),
                         [0, 127, 128, 255, 896, 1023]), 48),
        # (iii) N not a multiple of 128, N under 128, k = 1, k = N
        "n-300": (drawn(300, [0, 9, 20, 299]), 299),
        "n-300-k-n": (drawn(300, [300, 0, 150]), 300),
        "n-48": (drawn(48, [0, 1, 8, 5]), 8),
        "n-48-k-n": (drawn(48, [48, 47, 1]), 48),
        "k-1": (at(700, [], [0], [127], [128], [699]), 1),
        # the kernel's blocks over several tiles of its lanes
        "tiles": (drawn(40000, [1024, 1000, 0, 513, 512]), 1024),
    }


def held_to_flatnonzero(mask, k, **kw):
    where, real = (np.asarray(a) for a in pa.selected_positions(
        jnp.asarray(mask), k, **kw))
    assert where.dtype == np.int32 and where.shape == (len(mask), k)
    assert real.dtype == bool and real.shape == where.shape
    for r, row in enumerate(mask):
        true = np.flatnonzero(row)
        assert real[r].sum() == len(true) and real[r][:len(true)].all(), r
        assert (where[r][real[r]] == true).all(), r  # EQUAL, not close
        assert (where[r][~real[r]] == 0).all(), r
    return where, real


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
@pytest.mark.parametrize("case", sorted(masks_by_hand()))
def test_the_selected_positions_are_the_masks_true_positions(case, kernel):
    mask, k = masks_by_hand()[case]
    held_to_flatnonzero(mask, k, kernel=kernel)


def test_the_selected_positions_at_the_cells_shape():
    """``[32, 49664]`` with 2,048 true a row (one row short of them), through
    the CPU's form and through the kernel interpreted: the same integers."""
    rng = np.random.default_rng(6)
    mask = np.zeros((32, 49664), bool)
    for r, row in enumerate(mask):
        true = rng.choice(16430 + 1000 * r, 2048 - (r == 7), replace=False)
        row[true] = True
    by_xla = held_to_flatnonzero(mask, 2048, kernel="xla")
    by_kernel = held_to_flatnonzero(mask, 2048, kernel="interpret")
    for a, b in zip(by_xla, by_kernel):
        assert (a == b).all()


def paged_rows(seed, width, lens, page=4, pages_per_seq=12, num_pages=64):
    """A pool of ``width`` and tables for rows at positions ``lens`` (a row
    at -1 is out of the dispatch)."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(num_pages, page, width)), jnp.float32)
    tables = np.zeros((len(lens), pages_per_seq), np.int32)
    order = rng.permutation(np.arange(1, num_pages))
    used = 0
    for r, pos in enumerate(lens):
        if pos < 0:
            continue
        n = pos // page + 1
        tables[r, :n] = order[used:used + n]
        used += n
    return pool, jnp.asarray(tables), jnp.asarray(np.maximum(lens, 0), jnp.int32)


@pytest.mark.parametrize("block_pages", [2, 4, 32])
def test_the_index_kernel_scores_what_the_gather_path_scores(
        monkeypatch, block_pages):
    monkeypatch.setattr(pa, "INDEX_BLOCK_PAGES", block_pages)
    lens = [0, 17, 47, -1, 31]
    pool, tables, positions = paged_rows(1, 128, lens)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(len(lens), 6, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(lens), 6)), jnp.float32)
    want = np.asarray(pa.paged_index_scores(
        q, w, pool, tables, positions, kernel="xla"))
    got = np.asarray(pa.paged_index_scores(
        q, w, pool, tables, positions, kernel="interpret"))
    assert got.shape == want.shape == (len(lens), 48)
    for r, pos in enumerate(lens):
        if pos < 0:
            assert np.isneginf(got[r]).all()  # out of the dispatch
            continue
        assert np.abs(got[r, :pos + 1] - want[r, :pos + 1]).max() < 1e-4
        assert np.isneginf(got[r, pos + 1:]).all()
        assert np.isneginf(want[r, pos + 1:]).all()


def test_the_sparse_decode_attends_over_the_listed_positions_alone():
    lens = [5, 17, 47, 31]
    pool, tables, positions = paged_rows(3, 256, lens)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(len(lens), 1, 4, 256)), jnp.float32)
    scores = jnp.asarray(np.where(
        np.arange(48)[None, :] <= np.asarray(lens)[:, None],
        rng.normal(size=(len(lens), 48)), -np.inf), jnp.float32)
    where, real = pa.selected_positions(pa.top_k_mask(scores, 8), 8)
    assert np.asarray(real).sum(axis=1).tolist() == [6, 8, 8, 8]
    out = {
        kernel: np.asarray(pa.sparse_latent_attention(
            q, pool, tables, where, real, v_width=128, kernel=kernel))
        for kernel in ("xla", "interpret")}
    assert np.abs(out["xla"] - out["interpret"]).max() < 1e-5
    # By hand, row 2: a softmax over its eight positions' keys.
    keys = np.asarray(pool)[
        np.asarray(tables)[2, np.asarray(where)[2] // 4],
        np.asarray(where)[2] % 4]
    logits = np.asarray(q)[2, 0] @ keys.T * 256 ** -0.5
    p = np.exp(logits - logits.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ keys[:, :128]
    assert np.abs(out["interpret"][2, 0] - want).max() < 1e-5


@pytest.mark.parametrize("window", [1, 5, 9, 16, 40])
def test_the_windowed_kernel_reads_the_window_and_nothing_before_it(window):
    lens = [0, 17, 47, -1, 31, 8]
    pool, tables, positions = paged_rows(5, 256, lens)
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(len(lens), 1, 4, 256)), jnp.float32)
    run = functools.partial(
        pa.paged_latent_attention, q, v_width=128, window=window)
    want = np.asarray(run(pool, tables, positions, kernel="xla"))
    got = np.asarray(run(pool, tables, positions, kernel="interpret"))
    live = [r for r, pos in enumerate(lens) if pos >= 0]
    assert np.abs(got[live] - want[live]).max() < 1e-5
    # The pages behind the window are never read: poison them.
    poisoned = np.asarray(pool).copy()
    for r in live:
        first = max(lens[r] - window + 1, 0) // 4
        poisoned[np.asarray(tables)[r, :first]] = np.nan
    again = np.asarray(run(
        jnp.asarray(poisoned), tables, positions, kernel="interpret"))
    assert np.abs(again[live] - want[live]).max() < 1e-5
    full = np.asarray(pa.paged_latent_attention(
        q, pool, tables, positions, v_width=128, kernel="xla"))
    if window < 40:
        assert np.abs(full[2] - want[2]).max() > 1e-3


def test_window_tables_and_counts():
    assert pa.window_pages(513, 16) == 33 and pa.window_pages(9, 4) == 3
    read = pa.window_tokens_read(np.array([0, 511, 512, 527, 40000]), 513, 16)
    assert read.tolist() == [16, 512, 528, 528, 528]
    tables = jnp.arange(1, 41, dtype=jnp.int32).reshape(2, 20)
    got, lens, lo = pa.window_tables(tables, jnp.asarray([3, 50]), 4, 9)
    assert np.asarray(got).tolist() == [[1, 2, 3], [31, 32, 33]]
    assert np.asarray(lens).tolist() == [3, 10]
    assert np.asarray(lo).tolist() == [0, 2]


# ---------------------------------------------- the model against the reference

PAGE, NUM_PAGES, SLOTS = 4, 12, 2
TABLE = [3, 7, 2, 9, 5, 10, 1, 4]  # pages of 4: positions 0..31


@pytest.fixture(scope="module")
def program():
    weights, model, params = toy_program()
    return weights, model, params


def paged(model, kernel=""):
    return model.clone(decode=True, page_size=PAGE, num_pages=NUM_PAGES,
                       paged_kernel=kernel)


def zero_cache(model):
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((SLOTS, 1), jnp.int32)
    )["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), abstract)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply(model, padded, params, cache, toks, start, valid):
    kw = {"valid_lens": valid} if padded else {}
    return model.apply(
        {"params": params, "cache": cache}, toks,
        block_tables=jnp.asarray([TABLE], jnp.int32), seq_lens=start,
        state_slots=jnp.asarray([1], jnp.int32),
        mutable=["cache", "routing", "selection"], **kw)


def through_pages(model, params, toks, pieces):
    """``toks`` through the paged model in ``pieces`` ((length, width) pairs;
    what is left goes a token a call, as decode does): every position's
    logits."""
    cache = zero_cache(model)
    out, start = [], 0
    calls = list(pieces) + [(1, None)] * len(toks)
    for length, width in calls:
        if start >= len(toks):
            break
        piece = toks[start:start + length]
        n = len(piece)
        padded = list(piece) + [0] * ((width or n) - n)
        logits, updated = _apply(
            model, width is not None, params, cache,
            jnp.asarray([padded], jnp.int32), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32))
        cache = updated["cache"]
        out.append(np.asarray(logits[0, :n]))
        start += n
    return np.concatenate(out)


def reference_logits(weights, toks, cfg=TOY):
    return np.asarray(reference.logits_at(cfg, weights, toks, range(len(toks))))


def caught(program, kernel="", pieces=((7, 8), (6, 8)), n=26, cfg=TOY):
    """How far the paged model's logits lie from the reference's, with
    whatever fault is planted at the moment."""
    weights, model, params = program
    toks = tokens(n, seed=11)
    _apply.clear_cache()  # a planted fault is read when a call is traced
    got = through_pages(paged(model, kernel), params, toks, pieces)
    _apply.clear_cache()
    return np.abs(got - reference_logits(weights, toks, cfg)).max()


SPLITS = {
    "one-piece": [(26, None)],
    "one-piece-padded": [(26, 32)],
    "a-token-a-call": [],
    "pages": [(4, None)] * 5,
    "ragged-padded": [(5, 8), (3, 8), (5, 8), (9, 16)],
    "long-then-short": [(19, 24), (2, 8)],
}


@pytest.mark.parametrize("name, kernel", [
    (name, kernel) for name in sorted(SPLITS) for kernel in ("", "xla")
] + [("a-token-a-call", "interpret"), ("ragged-padded", "interpret")])
def test_pieces_then_decode_through_the_pools_match_the_reference(
        program, name, kernel):
    """Positions pass the window (9) and the selection (7) early, so every
    piece and every decode step meets both."""
    assert caught(program, kernel, SPLITS[name]) < LOGIT_TOL


def test_the_plain_forward_matches_the_reference(program):
    weights, model, params = program
    toks = tokens(30, seed=3)
    got = np.asarray(model.apply({"params": params}, jnp.asarray([toks]))[0])
    assert np.abs(got - reference_logits(weights, toks)).max() < LOGIT_TOL


def planted(monkeypatch, **replaced):
    """``build_program``'s layers built with other sizes (the fault)."""
    layer = mla.LatentAttention

    def faulty(*args, **kw):
        for key, change in replaced.items():
            if kw.get(key):
                kw[key] = change(kw[key])
        return layer(*args, **kw)

    monkeypatch.setattr(mla, "LatentAttention", faulty)


@pytest.mark.parametrize("fault, kernel", [
    (dict(window=lambda w: w + 1), ""), (dict(window=lambda w: w - 1), ""),
    (dict(window=lambda w: w + 1), "interpret"),
    (dict(index_top_k=lambda k: k // 2), ""),
    (dict(index_top_k=lambda k: k // 2), "interpret"),
    (dict(gate=lambda g: False), ""), (dict(lora_rescale=lambda r: False), ""),
])
def test_fault_in_the_layers_sizes_is_caught(
        program, monkeypatch, fault, kernel):
    """A window off by one either way, a selection of k / 2, the gate left
    out, the rescale left out."""
    planted(monkeypatch, **fault)
    assert caught(program, kernel) > 1e-3


def index_pages_gap(program):
    """``|pages - reference| / |reference|`` of the first full layer's
    index-key pages after a prompt's prefill in two pieces."""
    weights, model, params = program
    toks = tokens(26, seed=11)
    decode = paged(model)
    cache = zero_cache(decode)
    for start, piece in ((0, toks[:16]), (16, toks[16:])):
        _, updated = decode.apply(
            {"params": params, "cache": cache}, jnp.asarray([piece], jnp.int32),
            block_tables=jnp.asarray([TABLE], jnp.int32),
            seq_lens=jnp.asarray([start], jnp.int32),
            state_slots=jnp.asarray([1], jnp.int32),
            mutable=["cache", "routing"])
        cache = updated["cache"]
    pool = np.asarray(cache["block_0"]["mla"]["cached_index"])
    got = pool[TABLE].reshape(-1, pool.shape[-1])[:26, :TOY["index_head_dim"]]
    want = np.asarray(reference.probe_at(
        TOY, weights, toks, [25])["index_keys"][0])
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_fault_index_keys_in_8_bits_is_caught_in_the_pages(
        program, monkeypatch):
    """``benchmarks/control_sparse_latent_moe.py``'s fault. The toy's scores
    lie too far apart for 8 bits to move its selection, so the logits do not
    show it; the pages do (the cell's ``index_gap``)."""
    assert index_pages_gap(program) < 1e-5
    index_row = mla.index_row

    def rounded_row(k_idx, width):
        scale = jnp.max(jnp.abs(k_idx), axis=-1, keepdims=True) / 127
        return index_row(jnp.round(k_idx / scale) * scale, width)

    monkeypatch.setattr(mla, "index_row", rounded_row)
    assert index_pages_gap(program) > 1e-3


def test_fault_the_indexer_reading_x_where_it_reads_c_q_is_caught(
        program, monkeypatch):
    """Planted in the reference (the program's kernel of another height
    refuses ``x``): an indexer fed the layer's input where the equations feed
    it the query's latent selects other positions."""
    per_token = reference.per_token

    def faulty(y, w, a, eps, einsum):
        out = per_token(y, w, a, eps, einsum)
        if a["topk"]:
            out["index_in"] = y[:, :a["rq"]]
        return out

    monkeypatch.setattr(reference, "per_token", faulty)
    reference._programs.cache_clear()
    try:
        assert caught(program) > 1e-3
    finally:
        monkeypatch.undo()
        reference._programs.cache_clear()


@pytest.mark.parametrize("fault", ["bias_in_the_gates", "not_renormalised"])
def test_fault_in_the_gating_rule_is_caught(program, monkeypatch, fault):
    route = moe.route

    def faulty(scores, top_k, gating=moe.GATINGS[0], bias=None):
        if gating != "sigmoid_biased":
            return route(scores, top_k, gating, bias)
        s = jax.nn.sigmoid(scores)
        _, experts = jax.lax.top_k(s + bias, top_k)
        if fault == "bias_in_the_gates":
            s = s + bias
        chosen = jnp.take_along_axis(s, experts, axis=-1)
        if fault == "not_renormalised":
            return chosen, experts
        return chosen / jnp.sum(chosen, axis=-1, keepdims=True), experts

    monkeypatch.setattr(moe, "route", faulty)
    assert caught(program) > 1e-3


def test_the_third_gating_rule_by_hand():
    scores = jnp.asarray([[0.0, 2.0, -1.0, 1.0], [3.0, 0.0, 0.5, -2.0]])
    bias = jnp.asarray([0.0, -1.0, 0.9, 0.0])
    gates, experts = moe.route(scores, 2, "sigmoid_biased", bias)
    s = 1 / (1 + np.exp(-np.asarray(scores)))
    # Row 0: s + b = [.5, -.12, 1.17, .73]: experts 2 and 3, though expert 1
    # scores highest; row 1: [.95, -.5, 1.52, .12]: 2, then 0. Gates from s
    # alone.
    assert np.asarray(experts).tolist() == [[2, 3], [2, 0]]
    want = np.stack([s[0, [2, 3]] / s[0, [2, 3]].sum(),
                     s[1, [2, 0]] / s[1, [2, 0]].sum()])
    assert np.abs(np.asarray(gates) - want).max() < 1e-6
    plain, chosen = moe.route(scores, 2, "sigmoid_biased")
    assert np.asarray(chosen).tolist() == [[1, 3], [0, 2]]
    with pytest.raises(ValueError, match="unknown gating"):
        moe.route(scores, 2, "sigmoid")


def test_the_eight_shares_and_the_shared_expert_counted_once_are_the_whole_layer():
    """What the eight chips of the deployment compute of one expert layer
    under the sigmoid rule: each its one expert's part (the toy's eighth),
    all of them the shared expert alike. The eight parts and ONE shared
    output add up to the uncut reference's feed-forward."""
    from distributed_pytorch_tpu.models.transformer import MLPBlock

    weights = reference.make_weights(TOY, SEED)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights["layers"][1].items()}
    d, f, e = TOY["hidden_size"], TOY["moe_intermediate_size"], 8
    x = jnp.asarray(np.random.default_rng(7).normal(size=(24, d)), jnp.float32)
    whole, _ = reference.routed_experts(x, w, cfg=TOY, einsum=jnp.einsum)
    whole = np.asarray(whole) + np.asarray(reference.gated_mlp(
        x, w["ws_gate"], w["ws_up"], w["ws_down"], jnp.einsum))
    shared = np.asarray(MLPBlock(f, d, kind="gated_silu", use_bias=False).apply(
        {"params": {"gate": {"kernel": w["ws_gate"]}, "up": {"kernel": w["ws_up"]},
                    "down": {"kernel": w["ws_down"]}}}, x))
    parts = []
    for lo in range(e):
        layer = moe.RoutedExperts(
            n_experts=e, top_k=TOY["num_experts_per_tok"], d_ff=f, d_model=d,
            held=(lo, lo + 1), gating="sigmoid_biased")
        got = np.asarray(layer.apply(
            {"params": {"router_kernel": w["router"],
                        "router_bias": w["router_b"],
                        "in_kernel": w["we_in"][lo:lo + 1],
                        "out_kernel": w["we_out"][lo:lo + 1]}},
            x[None], mutable=["routing"])[0][0])
        cfg = share((lo, lo + 1))
        mine, _ = reference.routed_experts(
            x, dict(w, we_in=w["we_in"][lo:lo + 1], we_out=w["we_out"][lo:lo + 1]),
            cfg=cfg, einsum=jnp.einsum)
        assert np.abs(got - np.asarray(mine)).max() < 1e-5
        parts.append(got)
    assert np.abs(sum(parts) + shared - whole).max() < 1e-5
    assert sum(np.abs(p).max() > 1e-3 for p in parts) == e


def test_a_variant_without_its_sizes_is_refused():
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        layer_types=("latent_window",))
    with pytest.raises(ValueError, match="latent_variants"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_an_indexer_needs_the_querys_latent():
    layer = mla.LatentAttention(
        n_heads=2, d_model=16, kv_lora_rank=8, qk_nope_head_dim=4,
        qk_rope_head_dim=4, v_head_dim=4, index_heads=2, index_dim=8,
        index_top_k=4)
    with pytest.raises(ValueError, match="q_lora_rank"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))


def test_a_window_in_a_kv_layer_is_still_refused_by_name():
    from distributed_pytorch_tpu.models.transformer import Attention

    layer = Attention(2, 16, window=4, decode=True, page_size=4, num_pages=4)
    with pytest.raises(ValueError, match="K/V .non-latent. layer"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, 16)))


def test_the_counts_of_the_new_kernels_at_the_published_sizes():
    import json

    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "configs", "dots3-note-prev.json")) as f:
        cfg = json.load(f)
    assert reference.cache_bytes_per_token(cfg) == {
        "full_latent": 1152, "index_key": 256, "sliding_latent": 2176,
        "total": 3 * 1408 + 3 * 2176}
    p = reference.matmul_params(cfg)
    assert round(p["full"] / 1e6, 2) == 144.05
    assert round(p["sliding"] / 1e6, 2) == 90.83
    assert round(reference.held_parameters(cfg) / 1e6) == 5011
    assert reference.index_scores_flops(cfg, 1) == 2 * 64 * 128
    assert reference.index_scores_min_bytes(cfg, 1) == 256
    assert reference.sparse_decode_min_bytes(cfg, 2048) == 2048 * 1152
    assert reference.sparse_decode_flops(cfg, 1) == 2 * 128 * 1088
    assert reference.window_decode_min_bytes(cfg, 513) == 513 * 2176
    assert reference.pool_tokens(cfg) == 294912
