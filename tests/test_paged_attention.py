"""Paged-attention kernel + int8 KV pages: the ISSUE-19 contract.

Op level: the Pallas flash-decode kernel (run in interpret mode on the
CPU rig) must match the pure-XLA reference within float tolerance, a
decode step with the kernel off IS the reference bitwise (that is what
makes `paged_kernel="xla"` a no-op toggle), a chunk of several queries
is the blockwise walk, equal to the reference within float tolerance
and blind to every page past the blocks its rows hold (PR 47), NULL-page
(page 0) garbage must never survive the visibility mask, and the int8
path must dequantize to the same numbers the int8 reference computes.

Engine level: greedy tokens across the full toggle matrix (kernel
on/off x prefix_cache x overlap x speculative x mesh (1,1)/(1,8)) must
be identical to the kernel-off baseline on the fp path; the int8 path
is bounded by a perplexity tolerance instead (quantization legitimately
moves logits). Elastic snapshots carry a KV fingerprint and refuse
int8<->fp restores exactly like the mesh-geometry refusal.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.ops import flash_autotune as fa
from distributed_pytorch_tpu.obs import Tracer
from distributed_pytorch_tpu.ops import paged_attention as pa
from distributed_pytorch_tpu.ops.paged_attention import (
    NULL_PAGE,
    _paged_walk,
    chunk_keys_walked,
    kv_tokens_walked,
    paged_attention,
    paged_attention_reference,
    resolve_kernel,
)
from distributed_pytorch_tpu.ops.quant import quantize_int8
from distributed_pytorch_tpu.serving import kv_cache
from distributed_pytorch_tpu.serving import (
    EngineSnapshot,
    InferenceEngine,
    SamplingParams,
    drain_engine,
    make_serving_mesh,
    restore_engine,
)

# ----------------------------------------------------------------- op level


def make_problem(seed=0, s=3, h=4, kv_heads=2, d=8, page=4, pages_per_seq=4,
                 dtype=jnp.float32):
    """Mixed-liveness decode batch: row 0 mid-sequence, row 1 one token
    short of full, row 2 inactive (all-NULL table, len 0)."""
    rng = np.random.default_rng(seed)
    num_pages = 8
    q = jnp.asarray(rng.standard_normal((s, 1, h, d)), dtype)
    pool = (num_pages, page, kv_heads, d)
    k_pool = jnp.asarray(rng.standard_normal(pool), dtype)
    v_pool = jnp.asarray(rng.standard_normal(pool), dtype)
    bt = jnp.asarray([[3, 5, 0, 0], [1, 2, 4, 6], [0, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([6, 15, 0], jnp.int32)
    return q, k_pool, v_pool, bt[:s], lens[:s]


def quantize_pool(pool):
    qt = quantize_int8(pool, (3,))
    return qt.q, jnp.squeeze(qt.scale, -1)


def assert_rows_match(out, ref, bt, tol=2e-6):
    """The kernel against the reference: live rows within ``tol``, rows out
    of the group (a table that starts at the null page) exactly zero."""
    out, ref = np.asarray(out), np.asarray(ref)
    assert out.shape == ref.shape
    live = np.asarray(bt)[:, 0] != NULL_PAGE
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert (out[~live] == 0).all()


class TestPagedAttentionOp:
    @pytest.mark.parametrize("npb", [1, 2, 4])
    def test_kernel_matches_reference_fp(self, npb):
        q, kp, vp, bt, lens = make_problem()
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        out = paged_attention(
            q, kp, vp, bt, lens, kernel="interpret", pages_per_block=npb
        )
        assert_rows_match(out, ref, bt)

    def test_xla_mode_is_reference_bitwise(self):
        q, kp, vp, bt, lens = make_problem()
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        out = paged_attention(q, kp, vp, bt, lens, kernel="xla")
        assert (np.asarray(out) == np.asarray(ref)).all()

    def test_null_page_garbage_never_survives(self):
        """Property: page 0 contents are invisible. Poisoning the NULL
        page with huge finite garbage changes NOTHING for live rows, in
        both the reference and the kernel."""
        q, kp, vp, bt, lens = make_problem()
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        out = paged_attention(q, kp, vp, bt, lens, kernel="interpret")
        poison_k = kp.at[0].set(1e4)
        poison_v = vp.at[0].set(-1e4)
        ref_p = paged_attention_reference(q, poison_k, poison_v, bt, lens)
        out_p = paged_attention(
            q, poison_k, poison_v, bt, lens, kernel="interpret"
        )
        live = slice(0, 2)  # row 2 is inactive; only live rows must hold
        assert (np.asarray(ref_p)[live] == np.asarray(ref)[live]).all()
        assert (np.asarray(out_p)[live] == np.asarray(out)[live]).all()
        # Inactive rows still produce FINITE (discarded) output: the
        # kernel's are zeros.
        assert (np.asarray(out_p)[2] == 0).all()
        assert np.isfinite(np.asarray(ref_p)).all()

    def test_padded_table_tail_is_masked(self):
        """Rows whose table is wider than their length read their padded
        NULL entries as masked positions: growing the table with NULL
        pages never changes the output."""
        q, kp, vp, bt, lens = make_problem()
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        wide_bt = jnp.concatenate(
            [bt, jnp.zeros((bt.shape[0], 2), jnp.int32)], axis=1
        )
        ref_w = paged_attention_reference(q, kp, vp, wide_bt, lens)
        out_w = paged_attention(q, kp, vp, wide_bt, lens, kernel="interpret")
        np.testing.assert_allclose(ref_w, ref, atol=0, rtol=0)
        assert_rows_match(out_w, ref, wide_bt)

    def test_int8_kernel_matches_int8_reference(self):
        q, kp, vp, bt, lens = make_problem()
        k8, ks = quantize_pool(kp)
        v8, vs = quantize_pool(vp)
        ref = paged_attention_reference(
            q, k8, v8, bt, lens, k_scale=ks, v_scale=vs
        )
        out = paged_attention(
            q, k8, v8, bt, lens, k_scale=ks, v_scale=vs, kernel="interpret"
        )
        assert_rows_match(out, ref, bt)
        # And the quantized result is close to (not equal to) the fp one.
        fp = paged_attention_reference(q, kp, vp, bt, lens)
        err = np.abs(np.asarray(ref) - np.asarray(fp)).max()
        assert 0 < err < 0.1

    def test_grouped_query_mapping(self):
        """GQA group mapping: with Hkv=2, H=8, each KV head serves 4 query
        heads; a per-kv-head perturbation must move exactly its group."""
        q, kp, vp, bt, lens = make_problem(h=8, kv_heads=2)
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        out = paged_attention(q, kp, vp, bt, lens, kernel="interpret")
        assert_rows_match(out, ref, bt)
        bumped = paged_attention_reference(
            q, kp, vp.at[:, :, 0, :].add(1.0), bt, lens
        )
        delta = np.abs(np.asarray(bumped) - np.asarray(ref))
        # Query heads 0..3 read kv head 0 (moved); 4..7 read kv head 1.
        assert delta[0, :, :4, :].max() > 0
        assert delta[0, :, 4:, :].max() == 0

    @pytest.mark.parametrize("kernel", ["interpret", "xla"])
    def test_chunks_are_the_walk_and_the_reference_to_rounding(self, kernel):
        """A chunk (t_step 2) is the blockwise walk whatever ``kernel`` says:
        one result for both modes, the reference's within float tolerance.
        A single-token step with the kernel off stays the reference's bits."""
        q, kp, vp, bt, lens = make_problem()
        q2 = jnp.concatenate([q, q], axis=1)  # t_step = 2 (prefill chunk)
        ref = np.asarray(paged_attention_reference(q2, kp, vp, bt, lens))
        out = np.asarray(paged_attention(q2, kp, vp, bt, lens, kernel=kernel))
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)
        walk = _paged_walk(
            q2, kp, vp, bt, lens, bp=pa.walk_block_pages(bt.shape[1], 4))
        assert (out == np.asarray(walk)).all()
        one = paged_attention(q, kp, vp, bt, lens, kernel="xla")
        assert (np.asarray(one) == np.asarray(
            paged_attention_reference(q, kp, vp, bt, lens))).all()

    def test_resolve_kernel_validates(self):
        assert resolve_kernel("xla") == "xla"
        assert resolve_kernel("interpret") == "interpret"
        assert resolve_kernel(True) in ("pallas", "xla")
        assert resolve_kernel("auto") == resolve_kernel(None)
        with pytest.raises(ValueError, match="kernel"):
            resolve_kernel("cuda")

    def test_scale_pairing_validated(self):
        q, kp, vp, bt, lens = make_problem()
        k8, ks = quantize_pool(kp)
        with pytest.raises(ValueError, match="scale"):
            paged_attention(q, k8, vp, bt, lens, k_scale=ks, kernel="xla")

    def test_mesh_shard_map_parity(self):
        """The kernel under shard_map over the 'model' axis (the
        KV_POOL_SPEC head split) matches the unsharded reference on a
        (1,8) mesh, fp and int8."""
        q, kp, vp, bt, lens = make_problem(h=8, kv_heads=8)
        mesh = make_serving_mesh(1, 8)
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        out = paged_attention(
            q, kp, vp, bt, lens, kernel="interpret", mesh=mesh
        )
        assert_rows_match(out, ref, bt)
        k8, ks = quantize_pool(kp)
        v8, vs = quantize_pool(vp)
        ref8 = paged_attention_reference(
            q, k8, v8, bt, lens, k_scale=ks, v_scale=vs
        )
        out8 = paged_attention(
            q, k8, v8, bt, lens, k_scale=ks, v_scale=vs,
            kernel="interpret", mesh=mesh,
        )
        assert_rows_match(out8, ref8, bt)

    def test_jit_composes(self):
        q, kp, vp, bt, lens = make_problem()
        fn = jax.jit(lambda *a: paged_attention(*a, kernel="interpret"))
        out = fn(q, kp, vp, bt, lens)
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        assert_rows_match(out, ref, bt)

    def test_null_page_is_the_pools(self):
        assert NULL_PAGE == kv_cache.NULL_PAGE


# ------------------------------------- the kernel walks only a row's own KV

PAGE, WIDTH, NPB = 4, 8, 2  # a block of 8 tokens, 4 blocks a table
BLOCK = NPB * PAGE
# A row's decode position, or None for a row out of the group: the first
# key alone, the last of a block, the first of the next, one past it, one
# whole page, the table's last position; absent rows first, between live
# rows, twice in a row and last, so every hand-over of the prefetch runs.
EDGES = [
    None, 0, BLOCK - 1, None, BLOCK, BLOCK + 1, None, None, PAGE - 1,
    WIDTH * PAGE - 1, None,
]


def ragged_problem(positions, *, h=4, kv_heads=2, d=8, page=PAGE,
                   width=WIDTH, spare=3, seed=0, dtype=jnp.float32):
    """A decode batch with rows at ``positions``: each live row owns just
    the pages its ``pos + 1`` keys need, the rest of its table is the null
    page, and ``spare`` pages belong to no row. Every page holds random
    numbers, the null page and the spare ones too."""
    rng = np.random.default_rng(seed)
    owned = [0 if p is None else p // page + 1 for p in positions]
    num_pages = 1 + sum(owned) + spare
    ids = iter(rng.permutation(np.arange(1, num_pages)))
    bt = np.zeros((len(positions), width), np.int32)
    for row, n in enumerate(owned):
        bt[row, :n] = [next(ids) for _ in range(n)]
    lens = np.asarray([p or 0 for p in positions], np.int32)
    pool = (num_pages, page, kv_heads, d)
    q = jnp.asarray(rng.standard_normal((len(positions), 1, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal(pool), dtype)
    vp = jnp.asarray(rng.standard_normal(pool), dtype)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


def through(variant, npb=NPB):
    """``(kernel, reference)`` over ``(q, kp, vp, bt, lens)``: float pages,
    int8 pages, the (1, 2) mesh, or under ``jit``."""
    def scales(kp, vp):
        (k8, ks), (v8, vs) = quantize_pool(kp), quantize_pool(vp)
        return (k8, v8), dict(k_scale=ks, v_scale=vs)

    def run(fn, q, kp, vp, bt, lens, **kw):
        if variant == "int8":
            (kp, vp), sc = scales(kp, vp)
            kw.update(sc)
        return fn(q, kp, vp, bt, lens, **kw)

    kw = dict(kernel="interpret", pages_per_block=npb)
    if variant == "mesh":
        kw["mesh"] = make_serving_mesh(1, 2)
    kernel = functools.partial(run, paged_attention, **kw)
    if variant == "jit":
        kernel = jax.jit(kernel)
    return kernel, functools.partial(run, paged_attention_reference)


VARIANTS = ["fp", "int8", "mesh", "jit"]


def in_float32(reference, q, kp, vp, bt, lens, **kw):
    """The reference's float32 arithmetic of a problem's own numbers (bf16
    pools: what their kernel result rounds)."""
    q, kp, vp = (x.astype(jnp.float32) for x in (q, kp, vp))
    return reference(q, kp, vp, bt, lens, **kw)


class TestKernelWalksOwnKV:
    @pytest.mark.parametrize("npb", [1, NPB, WIDTH])
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_ragged_rows_match_reference(self, variant, npb):
        """Blocks of one page, of two, and one block a table, over rows
        that end on every edge of a block."""
        problem = ragged_problem(EDGES)
        kernel, reference = through(variant, npb)
        assert_rows_match(kernel(*problem), reference(*problem), problem[3])

    @pytest.mark.parametrize("npb", [1, NPB, WIDTH])
    def test_ragged_rows_of_bf16_pages(self, npb):
        """bf16 pools: the products' operands are bf16 (a bf16 x bf16 product
        is exact in float32, the softmax weights are rounded to bf16 before
        the weighted sum, as the reference's are), the statistics float32:
        the float32 arithmetic of the same bf16 numbers to the output's
        rounding; absent rows exactly zero."""
        problem = ragged_problem(EDGES, dtype=jnp.bfloat16)
        kernel, reference = through("fp", npb)
        out = kernel(*problem)
        assert out.dtype == jnp.bfloat16
        assert_rows_match(
            out.astype(jnp.float32), in_float32(reference, *problem),
            problem[3], tol=2e-2)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_absent_rows_are_zero_and_move_no_live_row(self, variant):
        positions = [5, BLOCK, 2 * BLOCK + 3, 0, WIDTH * PAGE - 1]
        q, kp, vp, bt, lens = ragged_problem(positions)
        kernel, _ = through(variant)
        full = np.asarray(kernel(q, kp, vp, bt, lens))
        for absent in ([0], [1, 2], [4], [0, 2, 4]):
            keep = np.ones(len(positions), bool)
            keep[absent] = False
            out = np.asarray(kernel(
                q, kp, vp, jnp.where(keep[:, None], bt, NULL_PAGE),
                jnp.where(keep, lens, 0),
            ))
            assert (out[~keep] == 0).all(), absent
            assert (out[keep] == full[keep]).all(), absent

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_garbage_past_a_rows_keys_changes_nothing(self, variant):
        """Large finite numbers in every slot a row owns past ``pos``, in
        every page no row owns and in the null page: live rows bit for
        bit."""
        positions = [1, BLOCK - 1, BLOCK, None, 2 * BLOCK + 2]
        q, kp, vp, bt, lens = ragged_problem(positions)
        dead = np.ones(kp.shape[:2], bool)  # [page id, slot in page]
        for row, pos in enumerate(positions):
            for key in range(0 if pos is None else pos + 1):
                dead[int(bt[row, key // PAGE]), key % PAGE] = False
        assert dead[NULL_PAGE].all() and dead.sum() > 4 * PAGE
        mask = jnp.asarray(dead)[:, :, None, None]
        kernel, reference = through(variant)
        clean = np.asarray(kernel(q, kp, vp, bt, lens))
        ref = np.asarray(reference(q, kp, vp, bt, lens))
        kp, vp = jnp.where(mask, 1e4, kp), jnp.where(mask, -1e4, vp)
        live = [p is not None for p in positions]
        assert (np.asarray(kernel(q, kp, vp, bt, lens))[live] == clean[live]).all()
        assert (np.asarray(reference(q, kp, vp, bt, lens))[live] == ref[live]).all()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("kv_heads, group, width, sm_scale", [
        (2, 12, 256, None),  # StarCoder2-3B: 24 query heads, 4,096 tokens a row
        (1, 20, 128, None),  # Jamba2-3B's attention layers: 2,048 tokens a row
        # granite-4.0-h-small's: 32 query heads on 8, scores scaled by 1/128
        (8, 4, 128, 1 / 128),
        (8, 4, 128, None),
        (8, 8, 64, None),  # K-EXAONE's: 64 query heads on 8
        (32, 1, 64, None),  # Olmo-Hybrid's: 30 on 30 held as 32 on 32
        (3, 2, 64, None),  # a KV head count that is no power of two
    ])
    def test_the_cells_head_groupings_and_table_widths(
            self, kv_heads, group, width, sm_scale, dtype):
        """Every cell's grouping (a score column is a (key, KV head): a
        query head keeps its own KV head's), float32 pools to summation order
        and bf16 pools to the output's rounding of the float32 arithmetic of
        the same numbers."""
        problem = ragged_problem(
            [130, None, 0, 127], h=kv_heads * group, kv_heads=kv_heads,
            d=128, page=16, width=width, seed=3, dtype=jnp.dtype(dtype),
        )
        kernel, reference = through("fp", npb=8)
        kw = {} if sm_scale is None else {"sm_scale": sm_scale}
        assert_rows_match(
            kernel(*problem, **kw).astype(jnp.float32),
            in_float32(reference, *problem, **kw), problem[3],
            tol=1e-5 if dtype == "float32" else 2e-2,
        )

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_the_score_scale_reaches_every_path(self, variant):
        """A caller's ``sm_scale`` moves the kernel and the gather path
        alike, and away from the default's result; ``head_dim ** -0.5``
        handed over explicitly IS the default."""
        problem = ragged_problem([5, BLOCK, None, 2 * BLOCK + 3])
        kernel, reference = through("fp" if variant == "jit" else variant)
        kernel = functools.partial(kernel, sm_scale=0.05)  # static, as in a model
        if variant == "jit":
            kernel = jax.jit(kernel)
        d = problem[0].shape[-1]
        default = np.asarray(reference(*problem))
        scaled = np.asarray(reference(*problem, sm_scale=0.05))
        assert np.abs(scaled - default).max() > 1e-2
        assert_rows_match(kernel(*problem), scaled, problem[3])
        assert np.array_equal(
            np.asarray(reference(*problem, sm_scale=d**-0.5)), default)

    def test_tokens_walked(self):
        pos = np.asarray([0, BLOCK - 1, BLOCK, 3 * BLOCK + 1])
        assert kv_tokens_walked(pos, BLOCK).tolist() == [
            BLOCK, BLOCK, 2 * BLOCK, 4 * BLOCK,
        ]


# ------------------------------ a chunk walks only the blocks its rows hold
#
# ``paged_attention`` at ``t_step > 1`` (a prefill piece, a speculative
# round's verification): ``_paged_walk``, a block of pages at a time with an
# online softmax over the blocks that hold a key some query sees, against the
# dense read ``paged_attention_reference``.

WALK_PAGE, WALK_BLOCK = 4, 16  # a toy block of 4 pages


def chunk_problem(starts, t_step, *, h=4, kv_heads=2, d=8, width=18,
                  page=WALK_PAGE, owned=None, dtype=jnp.float32, seed=0):
    """A chunk of ``t_step`` queries a row, row ``r`` from position
    ``starts[r]`` on (``None``: a row on the null table): each row owns the
    pages its ``start + t_step`` keys need (``owned``: that many instead),
    the rest of its table is the null page, and every page holds random
    numbers. ``(q, kp, vp, bt, lens)``."""
    rng = np.random.default_rng(seed)
    need = [
        0 if st is None else owned or -(-(st + t_step) // page)
        for st in starts
    ]
    num_pages = 1 + sum(need) + 2
    ids = iter(rng.permutation(np.arange(1, num_pages)))
    bt = np.zeros((len(starts), width), np.int32)
    for row, n in enumerate(need):
        bt[row, :n] = [next(ids) for _ in range(n)]
    lens = np.asarray([st or 0 for st in starts], np.int32)
    pool = (num_pages, page, kv_heads, d)
    q = jnp.asarray(rng.standard_normal((len(starts), t_step, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal(pool), dtype)
    vp = jnp.asarray(rng.standard_normal(pool), dtype)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


@pytest.fixture
def toy_walk_block(monkeypatch):
    monkeypatch.setattr(pa, "WALK_BLOCK_TOKENS", WALK_BLOCK)


#: name -> (chunk_problem's arguments, paged_attention's, what is compared).
#: Tables of 18 pages of 4 are four and a half blocks of 16 tokens.
WALKS = {
    # StarCoder2's grouping; the piece ends inside its third block
    "gqa_12_to_1": (dict(starts=[30], t_step=8, h=24, kv_heads=2), {}),
    "a_piece_from_an_empty_row": (dict(starts=[0], t_step=8), {}),
    "a_piece_that_ends_on_a_blocks_edge": (dict(starts=[24], t_step=8), {}),
    "a_piece_up_to_the_tables_end": (dict(starts=[64], t_step=8), {}),
    # a speculative round's verification: rows of different lengths, the
    # trip count the longest row's, one of them on the null table
    "rows_of_different_lengths": (
        dict(starts=[3, 41, 17], t_step=4), {}),
    "a_row_on_the_null_table": (
        dict(starts=[20, None, 50], t_step=4), {}),
    "the_score_scale": (dict(starts=[21], t_step=8), dict(sm_scale=0.05)),
    # a table that is a whole number of blocks, and one of a single block
    "whole_blocks": (dict(starts=[30], t_step=8, width=16), {}),
    "one_block_a_table": (dict(starts=[5], t_step=8, width=4), {}),
    "bf16_pages": (
        dict(starts=[30], t_step=8, dtype=jnp.bfloat16), {}),
}


@pytest.mark.usefixtures("toy_walk_block")
class TestChunkWalk:
    @pytest.mark.parametrize("name", sorted(WALKS))
    def test_the_walk_matches_the_dense_read(self, name):
        problem, kw = WALKS[name]
        q, kp, vp, bt, lens = chunk_problem(**problem)
        ref = paged_attention_reference(q, kp, vp, bt, lens, **kw)
        out = paged_attention(q, kp, vp, bt, lens, kernel="interpret", **kw)
        assert out.shape == ref.shape and out.dtype == ref.dtype
        tol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)

    def test_int8_pages_are_dequantised_a_block_at_a_time(self):
        q, kp, vp, bt, lens = chunk_problem([30, 9], 8)
        (k8, ks), (v8, vs) = quantize_pool(kp), quantize_pool(vp)
        ref = paged_attention_reference(
            q, k8, v8, bt, lens, k_scale=ks, v_scale=vs)
        out = paged_attention(
            q, k8, v8, bt, lens, k_scale=ks, v_scale=vs, kernel="xla")
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_one_to_one_heads_with_the_pools_padding_heads(self):
        """Olmo's full layers: 6 KV heads held as 8, as the model widens
        them (zero K, V and query heads last): the real heads are the
        reference's, the padding heads' output is zero."""
        q, kp, vp, bt, lens = chunk_problem([30], 8, h=8, kv_heads=8)
        q, kp, vp = (x.at[..., 6:, :].set(0) for x in (q, kp, vp))
        ref = paged_attention_reference(q, kp, vp, bt, lens)
        out = np.asarray(paged_attention(q, kp, vp, bt, lens, kernel="xla"))
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        assert (out[:, :, 6:] == 0).all() and np.abs(out[:, :, :6]).min() > 0

    @pytest.mark.parametrize("valid", [1, 5, 8])
    def test_a_padded_piece(self, valid):
        """A piece of ``valid`` tokens padded to 8: the row's own queries are
        the reference's, and the padding costs no block: the row holds
        ``14 + valid`` keys, a block of 16 until ``valid`` passes 2."""
        q, kp, vp, bt, lens = chunk_problem([14], 8, owned=18)
        ref = np.asarray(paged_attention_reference(q, kp, vp, bt, lens))
        past = np.asarray(bt)[0, (2 if valid > 2 else 1) * 4:]
        kp, vp = kp.at[past].set(np.nan), vp.at[past].set(np.nan)
        out = np.asarray(paged_attention(
            q, kp, vp, bt, lens, valid_lens=jnp.asarray([valid], jnp.int32),
            kernel="xla"))
        np.testing.assert_allclose(
            out[:, :valid], ref[:, :valid], atol=1e-5, rtol=1e-5)
        assert np.isfinite(out).all()  # the padding: finite, and nobody's

    @pytest.mark.parametrize("start", [0, 20, 40])
    def test_the_dead_blocks_are_never_read(self, start):
        """The row owns its whole table (a long prompt's early piece), and
        every page past the blocks its ``start + 8`` keys reach holds NaN:
        the dense read would spread it (0 x NaN), the walk never gathers
        those pages and gives the reference's result on clean pages."""
        q, kp, vp, bt, lens = chunk_problem([start], 8, owned=18)
        ref = np.asarray(paged_attention_reference(q, kp, vp, bt, lens))
        live = chunk_keys_walked(start + 8, 18, WALK_PAGE) // WALK_PAGE
        assert live == 4 * -(-(start + 8) // WALK_BLOCK) < 18
        dead = np.asarray(bt)[0, live:]
        kp, vp = kp.at[dead].set(np.nan), vp.at[dead].set(np.nan)
        assert not np.isfinite(np.asarray(
            paged_attention_reference(q, kp, vp, bt, lens))).any()
        out = np.asarray(paged_attention(q, kp, vp, bt, lens, kernel="xla"))
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_one_program_serves_every_length(self):
        """The trip count is traced: one jitted program, a short row and a
        long one."""
        fn = jax.jit(lambda *a: paged_attention(*a, kernel="xla"))
        for start in (2, 60):
            problem = chunk_problem([start], 8, owned=18)
            np.testing.assert_allclose(
                fn(*problem), paged_attention_reference(*problem),
                atol=1e-5, rtol=1e-5)
        assert fn._cache_size() == 1

    def test_keys_walked_is_whole_blocks_of_the_table(self):
        """The rule the walk's trip count and the host's count share: at
        least one block, whole blocks, at most the table's (its last block
        counted whole: 18 pages are five blocks of 4)."""
        n_keys = np.asarray([0, 1, 16, 17, 64, 65, 72, 500])
        assert chunk_keys_walked(n_keys, 18, WALK_PAGE).tolist() == [
            16, 16, 16, 32, 64, 80, 80, 80]
        traced = jax.jit(lambda n: chunk_keys_walked(n, 18, WALK_PAGE))
        assert [int(traced(n)) for n in n_keys] == [
            16, 16, 16, 32, 64, 80, 80, 80]
        assert chunk_keys_walked(9, 2, WALK_PAGE) == 8  # a table of one block


def test_the_walk_at_its_shipped_block():
    """No toy block: 512 tokens a block over a table of 72 pages of 16 (two
    and a quarter blocks), 24 query heads on 2 as in StarCoder2's cells."""
    assert pa.WALK_BLOCK_TOKENS == 512
    q, kp, vp, bt, lens = chunk_problem(
        [500], 64, h=24, kv_heads=2, d=16, page=16, width=72, owned=72)
    ref = np.asarray(paged_attention_reference(q, kp, vp, bt, lens))
    assert chunk_keys_walked(564, 72, 16) == 1024
    dead = np.asarray(bt)[0, 64:]
    out = np.asarray(paged_attention(
        q, kp.at[dead].set(np.nan), vp.at[dead].set(np.nan), bt, lens,
        kernel="xla"))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------- autotune family


@pytest.fixture
def _isolated_caches(tmp_path, monkeypatch):
    """Redirect every cache tier at empty temp state (same idiom as
    test_flash_autotune.py) so paged lookups hit the seeded table."""
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    monkeypatch.delenv("FLASH_BLOCKS_TABLE", raising=False)
    monkeypatch.delenv("FLASH_AUTOTUNE", raising=False)
    monkeypatch.setattr(fa, "_runtime_cache", {})
    fa._load_table_file.cache_clear()
    yield
    fa._load_table_file.cache_clear()


class TestPagedAutotune:
    def test_candidates_are_bounded_powers_of_two(self):
        cands = fa.paged_candidates(64, 16)
        assert cands[0] == 1
        for c in cands:
            assert c & (c - 1) == 0
            assert c * 16 <= 4096
        assert fa.paged_candidates(1, 8) == [1]

    def test_seeded_cpu_entry_no_sweep(self, _isolated_caches):
        """CI never autotunes: the shipped PAGED_DEFAULT_TABLE entry for
        'cpu' answers lookups directly."""
        npb = fa.lookup_paged(256, 16, 64, device_kind="cpu")
        assert npb == fa.PAGED_DEFAULT_TABLE["cpu"]
        # And nothing was swept or persisted to disk.
        assert fa._load_disk_cache() == {}

    def test_family_key_disjoint_from_flash(self):
        pk = fa._paged_key("cpu", 2048, 16, 64, "float32")
        flash = fa._key("cpu", 2048, 64, "float32", False)
        assert pk != flash
        assert fa.PAGED_FAMILY in pk[3] and "p16" in pk[3]

    def test_lookup_clips_to_legal_candidates(self, _isolated_caches):
        # Table width 2 pages: the seeded npb must clip down to <= 2.
        npb = fa.lookup_paged(16, 8, 8, device_kind="tpu v5 lite")
        assert npb in fa.paged_candidates(2, 8)

    def test_table_file_tier_wins(self, _isolated_caches, tmp_path,
                                  monkeypatch):
        key = fa._paged_key("cpu", 256, 16, 64, "float32")
        path = tmp_path / "table.json"
        path.write_text(json.dumps({json.dumps(list(key)): [8, 128]}))
        monkeypatch.setenv("FLASH_BLOCKS_TABLE", str(path))
        assert fa.lookup_paged(256, 16, 64, device_kind="cpu") == 8

    def test_autotune_paged_persists_winner(self, _isolated_caches):
        npb = fa.autotune_paged(16, 4, 8, slots=2, kv_heads=2, steps=1)
        assert npb in fa.paged_candidates(4, 4)
        # Cached: a second call returns without sweeping (runtime tier).
        assert fa.lookup_paged(16, 4, 8) == npb
        disk = fa._load_disk_cache()
        key = fa._paged_key(fa._device_kind(), 16, 4, 8, "float32")
        assert disk[key] == (npb, npb * 4)


# -------------------------------------------------- engine parity matrix

MESH_LM = dict(
    vocab_size=64, d_model=32, n_layers=2, n_heads=8, d_ff=64,
    dtype=jnp.float32,
)
PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [1, 2, 3, 9, 10]]
MAX_NEW = 5
ENGINE_KW = dict(
    max_slots=4, max_seq_len=32, page_size=8, token_budget=32,
    max_prefill_chunk=16,
)


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(**MESH_LM)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def draft_and_params():
    draft = TransformerLM(
        vocab_size=64, d_model=16, n_layers=1, n_heads=8, d_ff=32,
        dtype=jnp.float32,
    )
    dparams = draft.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return draft, dparams


def run_engine(model, params, *, mesh=None, prefix=True, overlap=True,
               spec=None, **extra):
    kw = dict(ENGINE_KW)
    if spec is not None:
        draft, dparams = spec
        kw.update(draft_model=draft, draft_params=dparams, gamma=3)
    eng = InferenceEngine(
        model, params, mesh=mesh, prefix_cache=prefix, overlap=overlap,
        **kw, **extra,
    )
    ids = [
        eng.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
        for p in PROMPTS
    ]
    eng.run()
    out = [eng.poll(i).generated for i in ids]
    eng.close()
    return out, eng


@pytest.fixture(scope="module")
def baseline_greedy(model_and_params):
    out, _ = run_engine(*model_and_params)
    return out


class TestEngineKernelParity:
    """fp path: kernel on/off must be token-identical everywhere. On the
    CPU rig paged_kernel=True resolves to the XLA reference (bitwise by
    the op tests above); "interpret" runs the actual kernel math."""

    @pytest.mark.parametrize("kernel", [True, "xla", "interpret"])
    @pytest.mark.parametrize("prefix", [True, False])
    def test_kernel_matrix_unsharded(self, model_and_params,
                                     baseline_greedy, kernel, prefix):
        out, eng = run_engine(
            *model_and_params, prefix=prefix, paged_kernel=kernel
        )
        assert out == baseline_greedy
        assert eng.paged_kernel in ("auto", "xla", "interpret")

    @pytest.mark.parametrize("overlap", [True, False])
    def test_kernel_overlap_toggle(self, model_and_params,
                                   baseline_greedy, overlap):
        out, _ = run_engine(
            *model_and_params, overlap=overlap, paged_kernel=True
        )
        assert out == baseline_greedy

    def test_kernel_speculative(self, model_and_params, draft_and_params,
                                baseline_greedy):
        out, _ = run_engine(
            *model_and_params, spec=draft_and_params, paged_kernel=True
        )
        assert out == baseline_greedy

    @pytest.mark.parametrize("shape", [(1, 1), (1, 8)])
    def test_kernel_mesh(self, model_and_params, baseline_greedy, shape):
        out, eng = run_engine(
            *model_and_params, mesh=make_serving_mesh(*shape),
            paged_kernel=True,
        )
        assert out == baseline_greedy
        assert eng._sharded_programs >= 2  # decode + the one prefill width

    @pytest.mark.parametrize("shape", [(1, 1), (1, 8)])
    def test_kernel_interpret_mesh(self, model_and_params,
                                   baseline_greedy, shape):
        out, _ = run_engine(
            *model_and_params, mesh=make_serving_mesh(*shape),
            paged_kernel="interpret",
        )
        assert out == baseline_greedy

    def test_paged_program_name(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(
            model, params, xla_ledger=True, paged_kernel=True, **ENGINE_KW
        )
        rid = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
        eng.run()
        assert eng.poll(rid).finished
        names = {r.name for r in eng.xla.programs.values()}
        eng.close()
        assert any(n.startswith("decode_step_paged") for n in names)
        assert not any(n == "decode_step" for n in names)

    @pytest.mark.parametrize("kernel, block", [("interpret", 16), ("xla", 0)])
    def test_step_slices_count_kv_tokens_fetched_and_visible(
            self, model_and_params, kernel, block):
        """Prompts of 15 and 3 tokens decode side by side from positions
        14 and 2: a step's slice carries the keys its decode rows could see
        (``pos + 1``) and the ones read for them, whole blocks of 2 pages
        of 8 under the kernel (the seeded "cpu" entry), every slot's whole
        table on the gather path."""
        model, params = model_and_params
        tracer = Tracer()
        eng = InferenceEngine(
            model, params, tracer=tracer, overlap=False,
            paged_kernel=kernel, **ENGINE_KW,
        )
        assert eng.reads.blocks.get("kv", 0) * eng.page_size == block
        for prompt in (list(range(1, 16)), [1, 2, 3]):
            eng.submit(prompt, SamplingParams(max_new_tokens=4))
        eng.run()
        eng.close()
        steps = [
            e["args"] for e in tracer.events
            if e.get("ph") == "X" and e["name"] == "step"
        ]
        counted = [
            (a["decode_rows"], a["decode_kv_tokens_visible"],
             a["decode_kv_tokens_fetched"])
            for a in steps if "decode_kv_tokens_visible" in a
        ]
        assert all(
            "decode_kv_tokens_fetched" not in a
            for a in steps if not a["decode_rows"]
        )
        whole = ENGINE_KW["max_slots"] * ENGINE_KW["max_seq_len"]
        # positions (14, 2), (15, 3), (16, 4), (17, 5): the long row
        # crosses into its second block of 16 at position 16
        assert counted == [
            (2, 15 + 3, 16 + 16 if block else whole),
            (2, 16 + 4, 16 + 16 if block else whole),
            (2, 17 + 5, 32 + 16 if block else whole),
            (2, 18 + 6, 32 + 16 if block else whole),
        ]

    @pytest.mark.parametrize("kernel", ["interpret", False])
    def test_prefill_slices_count_keys_walked_and_the_tables(
            self, model_and_params, kernel, monkeypatch):
        """Prompts of 21 and 3 tokens over tables of 32 tokens, a block of 8
        (one page). A prompt's last token is its first decode step's: the 20
        go as pieces of 16 and 4, which walk 2 and 3 blocks; the 2 walk one.
        ``prefill.chunk`` slices carry both sides, the ``step`` slices and
        ``stats()`` their sums."""
        monkeypatch.setattr(pa, "WALK_BLOCK_TOKENS", 8)
        model, params = model_and_params
        tracer = Tracer()
        eng = InferenceEngine(
            model, params, tracer=tracer, overlap=False, prefix_cache=False,
            paged_kernel=kernel, **ENGINE_KW,
        )
        for prompt in (list(range(1, 22)), [1, 2, 3]):
            eng.submit(prompt, SamplingParams(max_new_tokens=2))
        eng.run()
        eng.close()
        slices = lambda name: [  # noqa: E731
            e["args"] for e in tracer.events
            if e.get("ph") == "X" and e["name"] == name
        ]
        pieces = sorted(
            (a["start"], a["tokens"], a["keys_walked"], a["keys_table"])
            for a in slices("prefill.chunk")
        )
        assert pieces == [(0, 2, 8, 32), (0, 16, 16, 32), (16, 4, 24, 32)]
        stats = eng.stats()
        assert stats["prefill_keys_walked"] == 48
        assert stats["prefill_keys_table"] == 96
        steps = slices("step")
        for name in ("prefill_keys_walked", "prefill_keys_table"):
            assert sum(a.get(name, 0) for a in steps) == stats[name]
        assert all(
            "prefill_keys_walked" not in a
            for a in steps if not a["prefill_programs"]
        )

    def test_bad_kernel_mode_fails_at_init(self, model_and_params):
        model, params = model_and_params
        with pytest.raises(ValueError, match="kernel"):
            InferenceEngine(
                model, params, paged_kernel="cuda", **ENGINE_KW
            )


# ------------------------------------------------------------ int8 path


class TestInt8KV:
    def test_cache_layout(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(
            model, params, kv_quant="int8", **ENGINE_KW
        )
        leaves = jax.tree_util.tree_leaves(eng.pools["target"])
        dtypes = sorted({str(x.dtype) for x in leaves})
        assert dtypes == ["float32", "int8"]
        for x in leaves:
            assert x.ndim in (3, 4)  # scale pools ride alongside
        assert eng.kv_fingerprint == "int8"
        eng.close()

    def test_rejects_unknown_quant(self, model_and_params):
        model, params = model_and_params
        with pytest.raises(ValueError, match="kv_quant"):
            InferenceEngine(model, params, kv_quant="int4", **ENGINE_KW)

    @pytest.mark.parametrize("kernel", [False, True])
    def test_int8_perplexity_tolerance(self, model_and_params, kernel):
        """Teacher-forced decode through the paged cache: the int8 path's
        per-token NLL over a fixed stream must stay within 2% of the fp
        path's (greedy tokens may legitimately differ under quantization;
        the distribution must not move materially)."""
        model, params = model_and_params
        toks = np.asarray(
            np.random.default_rng(7).integers(1, 64, (2, 12))
        )

        def mean_nll(kv_quant):
            m = model.clone(
                decode=True, page_size=4, num_pages=17, kv_quant=kv_quant,
                paged_kernel="interpret" if kernel else "",
            )
            cache = m.init(
                jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32)
            )["cache"]
            bt = jnp.asarray(
                [[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32
            )
            nll = []
            for t in range(toks.shape[1] - 1):
                lens = jnp.full((2,), t, jnp.int32)
                logits, mut = m.apply(
                    {"params": params, "cache": cache},
                    jnp.asarray(toks[:, t:t + 1]),
                    block_tables=bt, seq_lens=lens, mutable=["cache"],
                )
                cache = mut["cache"]
                logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
                nll.append(
                    -np.asarray(logp)[np.arange(2), toks[:, t + 1]].mean()
                )
            return float(np.mean(nll))

        fp = mean_nll("")
        q8 = mean_nll("int8")
        assert abs(q8 - fp) / fp < 0.02, (fp, q8)

    def test_int8_halves_page_bytes(self, model_and_params):
        model, params = model_and_params
        fp = InferenceEngine(model, params, **ENGINE_KW)
        q8 = InferenceEngine(model, params, kv_quant="int8", **ENGINE_KW)
        bytes_fp = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(fp.pools["target"])
        )
        bytes_q8 = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(q8.pools["target"])
        )
        fp.close()
        q8.close()
        # fp32 pools: int8 payload = 1/4, f32 scales add 1/D = 1/8.
        d = MESH_LM["d_model"] // MESH_LM["n_heads"]
        assert bytes_q8 * 8 == bytes_fp * (2 + 8 // d * 1)

    def test_int8_engine_runs_all_toggles(self, model_and_params,
                                          draft_and_params):
        """int8 output is engine-path-invariant: kernel modes, prefix,
        speculative, and mesh all agree with the int8 gather baseline."""
        base, _ = run_engine(*model_and_params, kv_quant="int8")
        for extra in (
            dict(paged_kernel=True),
            dict(paged_kernel="interpret"),
            dict(prefix=False),
            dict(spec=draft_and_params),
            dict(mesh=make_serving_mesh(1, 8), paged_kernel=True),
        ):
            out, _ = run_engine(*model_and_params, kv_quant="int8", **extra)
            assert out == base, extra


# --------------------------------------------------- elastic fingerprint


class TestKvFingerprint:
    def _snap(self, model, params, **ekw):
        eng = InferenceEngine(model, params, **ENGINE_KW, **ekw)
        eng.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=8))
        eng.step()
        snap = drain_engine(eng)
        eng.close()
        return snap

    def test_snapshot_carries_kv_fingerprint(self, model_and_params):
        model, params = model_and_params
        assert self._snap(model, params).kv == "fp"
        assert self._snap(model, params, kv_quant="int8").kv == "int8"

    def test_restore_refuses_kv_mismatch(self, model_and_params):
        model, params = model_and_params
        snap = self._snap(model, params, kv_quant="int8")
        fp_engine = InferenceEngine(model, params, **ENGINE_KW)
        with pytest.raises(ValueError, match="int8"):
            restore_engine(fp_engine, snap)
        fp_engine.close()

    def test_restore_matching_int8_round_trips(self, model_and_params):
        model, params = model_and_params
        snap = self._snap(model, params, kv_quant="int8")
        target = InferenceEngine(
            model, params, kv_quant="int8", **ENGINE_KW
        )
        ids = restore_engine(target, snap)
        target.run()
        assert all(target.poll(i).finished for i in ids)
        target.close()

    def test_old_snapshots_decode_as_fp(self, model_and_params):
        """Wire backcompat: snapshots written before the kv field decode
        with kv='fp' (mirrors the mesh-field default)."""
        model, params = model_and_params
        snap = self._snap(model, params)
        doc = json.loads(snap.to_json())
        del doc["kv"]
        old = EngineSnapshot.from_json(json.dumps(doc))
        assert old.kv == "fp"
        assert dataclasses.replace(old, kv=snap.kv) == snap


# ------------------------------------------------------------- latent pool
#
# The second kind of page (``models/mla.py``): ONE pool ``[num_pages, page,
# W]`` with no head axis; 16 query heads read the same cached vector, whose
# first ``v_width`` numbers are also the value (QK width W, V width
# ``v_width``).


def make_latent_problem(seed=0, h=16, w=20, page=4, pages_per_seq=6,
                        dtype=jnp.float32):
    """Ragged rows: mid-page, one token, a whole table but one, out of the
    group (an all-null table), and a row that shares its first pages with
    row 0 (a shared document)."""
    from distributed_pytorch_tpu.ops.paged_attention import (
        paged_latent_attention,
    )

    rng = np.random.default_rng(seed)
    bt = jnp.asarray([
        [3, 5, 9, 0, 0, 0], [7, 0, 0, 0, 0, 0], [1, 2, 4, 6, 8, 10],
        [0, 0, 0, 0, 0, 0], [3, 5, 11, 12, 0, 0]], jnp.int32)
    lens = jnp.asarray([9, 0, 22, 0, 13], jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, 1, h, w)), dtype)
    pool = jnp.asarray(rng.standard_normal((13, page, w)), dtype)
    return paged_latent_attention, q, pool, bt, lens


def dense_latent(q, pool, bt, lens, v_width, sm_scale):
    """The same numbers written out a row at a time in NumPy."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    out = np.zeros(q.shape[:3] + (v_width,))
    for b in range(q.shape[0]):
        keys = pool[np.asarray(bt[b])].reshape(-1, pool.shape[-1])
        keys = keys[: int(lens[b]) + 1]
        s = np.einsum("hw,kw->hk", q[b, 0], keys) * sm_scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b, 0] = (p / p.sum(-1, keepdims=True)) @ keys[:, :v_width]
    return out


class TestLatentPagedAttention:
    V = 16  # of a latent of 20: QK and V widths differ

    def test_reference_is_the_dense_computation(self):
        _, q, pool, bt, lens = make_latent_problem()
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V, sm_scale=0.3)
        assert ref.shape == (5, 1, 16, self.V)
        want = dense_latent(q, pool, bt, lens, self.V, 0.3)
        np.testing.assert_allclose(np.asarray(ref), want, atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("npb", [1, 2, 4, 6])
    def test_kernel_matches_reference(self, npb):
        """Group 16 on one latent, ragged rows, every block size: live rows
        to float32 rounding, the row out of the group exactly zero."""
        attend, q, pool, bt, lens = make_latent_problem()
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V, sm_scale=0.3)
        out = attend(q, pool, bt, lens, v_width=self.V, kernel="interpret",
                     pages_per_block=npb, sm_scale=0.3)
        assert_rows_match(out, ref, bt)

    def test_default_scale_is_the_widths_root(self):
        attend, q, pool, bt, lens = make_latent_problem(seed=2)
        out = attend(q, pool, bt, lens, v_width=self.V, kernel="interpret")
        want = dense_latent(q, pool, bt, lens, self.V, 20**-0.5)
        live = np.asarray(bt)[:, 0] != NULL_PAGE
        np.testing.assert_allclose(
            np.asarray(out)[live], want[live], atol=2e-6, rtol=2e-6)

    def test_xla_mode_and_chunks_are_the_reference_bitwise(self):
        attend, q, pool, bt, lens = make_latent_problem(seed=3)
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V)
        out = attend(q, pool, bt, lens, v_width=self.V, kernel="xla")
        assert (np.asarray(out) == np.asarray(ref)).all()
        q2 = jnp.concatenate([q, q], axis=1)  # t_step 2: never the kernel
        ref2 = paged_attention_reference(
            q2, pool, None, bt, lens, v_width=self.V)
        out2 = attend(q2, pool, bt, lens, v_width=self.V, kernel="interpret")
        assert (np.asarray(out2) == np.asarray(ref2)).all()

    def test_null_page_and_unowned_pages_never_survive(self):
        """Poisoning the null page and every page no row owns with NaN
        changes nothing for live rows in the kernel (it never fetches them);
        with huge finite garbage nothing in the reference either."""
        attend, q, pool, bt, lens = make_latent_problem(seed=4)
        kw = dict(v_width=self.V, sm_scale=0.3)
        out = attend(q, pool, bt, lens, kernel="interpret", **kw)
        ref = paged_attention_reference(q, pool, None, bt, lens, **kw)
        # Row 2 holds 23 of its table's 24 positions: its last page's last
        # row is past its length; pages 10 (row 2's last) stays.
        unowned = jnp.asarray([0])
        out_p = attend(q, pool.at[unowned].set(jnp.nan), bt, lens,
                       kernel="interpret", **kw)
        ref_p = paged_attention_reference(
            q, pool.at[unowned].set(1e4), None, bt, lens, **kw)
        live = np.asarray(bt)[:, 0] != NULL_PAGE
        assert (np.asarray(out_p)[live] == np.asarray(out)[live]).all()
        assert (np.asarray(ref_p)[live] == np.asarray(ref)[live]).all()
        assert (np.asarray(out_p)[~live] == 0).all()

    def test_rows_that_share_pages_read_the_same_latent(self):
        """Rows 0 and 4 share their first two pages (a document): each
        attends to them under its own length."""
        attend, q, pool, bt, lens = make_latent_problem(seed=5)
        q = q.at[4].set(q[0])
        out = attend(q, pool, bt, lens.at[4].set(7).at[0].set(7),
                     v_width=self.V, kernel="interpret", pages_per_block=2)
        np.testing.assert_allclose(
            np.asarray(out[4]), np.asarray(out[0]), atol=1e-6, rtol=1e-6)

    def test_bf16_pool_runs_in_its_own_type(self):
        attend, q, pool, bt, lens = make_latent_problem(
            seed=6, dtype=jnp.bfloat16)
        out = attend(q, pool, bt, lens, v_width=self.V, kernel="interpret",
                     sm_scale=0.3)
        assert out.dtype == jnp.bfloat16
        want = dense_latent(q, pool, bt, lens, self.V, 0.3)
        live = np.asarray(bt)[:, 0] != NULL_PAGE
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[live], want[live], atol=0.06, rtol=0.06)

    @pytest.mark.parametrize("bad, message", [
        (dict(v_width=21), "v_width"), (dict(v_width=0), "v_width")])
    def test_a_value_wider_than_the_latent_is_refused(self, bad, message):
        attend, q, pool, bt, lens = make_latent_problem()
        with pytest.raises(ValueError, match=message):
            attend(q, pool, bt, lens, kernel="xla", **bad)

    def test_a_pool_with_a_head_axis_is_refused(self):
        attend, q, pool, bt, lens = make_latent_problem()
        with pytest.raises(ValueError, match="latent pool"):
            attend(q, pool[:, :, None], bt, lens, v_width=self.V)

    def test_block_size_is_looked_up_under_the_pools_width(self, monkeypatch):
        from distributed_pytorch_tpu.ops import paged_attention as pa

        seen = []
        real = pa.block_pages

        def spy(*args):
            seen.append(args)
            return real(*args)

        monkeypatch.setattr(pa, "block_pages", spy)
        attend, q, pool, bt, lens = make_latent_problem(seed=8)
        attend(q, pool, bt, lens, v_width=self.V, kernel="interpret")
        assert seen and seen[0][:3] == (6, 4, 20)

    def test_the_shipped_block_is_by_kind_and_width(self):
        """A (kind, head size) entry of the shipped table goes before its
        kind's: the latent pool's 640 lanes have a block of their own."""
        kind = "TPU v5 lite"
        assert fa.lookup_paged_with_tier(
            16384, 16, 640, "bfloat16", device_kind=kind
        ) == (fa.PAGED_DEFAULT_TABLE[("tpu v5 lite", 640)], "shipped_table")
        assert fa.lookup_paged_with_tier(
            4096, 16, 128, "bfloat16", device_kind=kind
        ) == (fa.PAGED_DEFAULT_TABLE["tpu v5 lite"], "shipped_table")


# ------------------------------------------------- rows that share their pages
#
# The latent kernel serves rows whose tables begin with the same physical
# pages as a group (``shared_prefix_groups``): the shared pages are copied
# once, at the group's first row, and every member walks only what follows.


def shared_latent_problem(rows, *, page=4, pages_per_seq=16, seed=0, h=16,
                          w=20, copied=()):
    """A dispatch built row by row: ``rows[i]`` is ``None`` (a row out of
    the dispatch) or ``(document, shared_pages, pos)``: the row's table
    begins with the first ``shared_pages`` pages of that document and goes
    on with pages of its own up to the one that holds ``pos``. Physical
    pages are dealt in a shuffled order. ``copied`` names ``(row, other,
    index)``: ``row``'s page at ``index`` gets the contents of ``other``'s
    there (a page copied on write: equal numbers under another number)."""
    rng = np.random.default_rng(seed)
    needed = sum(r[2] // page + 1 for r in rows if r) + 1
    deal = iter(1 + rng.permutation(needed + 64))
    documents = {}
    tables = np.zeros((len(rows), pages_per_seq), np.int32)
    lens = np.zeros((len(rows),), np.int32)
    for i, row in enumerate(rows):
        if row is None:
            continue
        doc, shared, pos = row
        pages = documents.setdefault(doc, [])
        while len(pages) < shared:
            pages.append(next(deal))
        held = pos // page + 1
        assert shared < held <= pages_per_seq
        tables[i, :shared] = pages[:shared]
        tables[i, shared:held] = [next(deal) for _ in range(held - shared)]
        lens[i] = pos
    pool = rng.standard_normal((int(tables.max()) + 1, page, w))
    for row, other, index in copied:
        pool[tables[row, index]] = pool[tables[other, index]]
    q = rng.standard_normal((len(rows), 1, h, w))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(pool, jnp.float32),
            jnp.asarray(tables), jnp.asarray(lens))


def doc_rows(*positions, document=0, shared=6):
    return [None if p is None else (document, shared, p) for p in positions]


SHARING = {
    # name: (rows, pages a block, the (leader, shared) it must come to)
    "one-row-alone": (doc_rows(27), 2, ([0], [0])),
    "a-pair": (doc_rows(27, 33), 2, ([0, 0], [6, 6])),
    "three": (doc_rows(25, 30, 41), 2, ([0, 0, 0], [6] * 3)),
    "five-is-four-and-one": (
        doc_rows(25, 26, 27, 28, 29), 2, ([0, 0, 0, 0, 4], [6] * 4 + [0])),
    "nine-is-four-four-one": (
        doc_rows(*range(25, 34)), 2,
        ([0] * 4 + [4] * 4 + [8], [6] * 8 + [0])),
    "shared-length-no-multiple-of-the-block": (
        doc_rows(24, 37, shared=5), 4, ([0, 0], [5, 5])),
    "shared-length-under-an-eighth-more": (
        doc_rows(40, 45, shared=9), 8, ([0, 0], [9, 9])),
    "tails-of-one-token-to-several-blocks": (
        doc_rows(24, 25, 47, 63), 2, ([0] * 4, [6] * 4)),
    "an-absent-row-between-members": (
        doc_rows(27, None, 30, None, 29), 2,
        ([0, 1, 0, 3, 0], [6, 0, 6, 0, 6])),
    "two-documents-interleaved": (
        doc_rows(27, None, 30) + doc_rows(22, 41, document=1, shared=4)
        + doc_rows(35), 2,
        ([0, 1, 0, 3, 3, 0], [6, 0, 6, 4, 4, 6])),
    "a-leader-behind-its-members": (
        # The first row stands in the document's fourth page: the group
        # shares the three whole pages below its position.
        [(0, 3, 13), (0, 6, 30), (0, 6, 33)], 2, ([0] * 3, [3] * 3)),
    "a-member-that-parts-early": (
        [(0, 6, 30), (0, 3, 20), (0, 6, 33)], 2, ([0] * 3, [3] * 3)),
    "under-a-block-stays-alone": (
        doc_rows(9, 14, shared=1), 2, ([0, 1], [0, 0])),
    "no-sharing": (
        [(0, 0, 27), (1, 0, 3), None, (2, 0, 0), (3, 0, 63), (4, 0, 32)], 2,
        ([0, 1, 2, 3, 4, 5], [0] * 6)),
}


class TestLatentRowsThatShare:
    V = 16

    def attend(self, q, pool, bt, lens, npb, **kw):
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_latent_attention,
        )

        return paged_latent_attention(
            q, pool, bt, lens, v_width=self.V, kernel="interpret",
            pages_per_block=npb, sm_scale=0.3, **kw)

    @pytest.mark.parametrize("name", sorted(SHARING))
    def test_grouped_rows_match_the_reference(self, name):
        """Every row of every grouping attends to exactly its own keys: the
        kernel against the gather path, and the grouping it worked out
        against the one written down."""
        from distributed_pytorch_tpu.ops.paged_attention import (
            shared_prefix_groups,
        )

        rows, npb, (leader, shared) = SHARING[name]
        q, pool, bt, lens = shared_latent_problem(rows, seed=len(name))
        got = shared_prefix_groups(
            np.asarray(bt), np.asarray(lens), pool.shape[1], npb)
        assert [list(map(int, g)) for g in got] == [leader, shared]
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V, sm_scale=0.3)
        assert_rows_match(self.attend(q, pool, bt, lens, npb), ref, bt)

    @pytest.mark.parametrize("npb", [1, 3, 4, 16])
    def test_every_block_size_serves_the_same_groups(self, npb):
        rows = doc_rows(27, None, 30) + doc_rows(
            22, 41, document=1, shared=4) + doc_rows(35)
        q, pool, bt, lens = shared_latent_problem(rows, seed=npb)
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V, sm_scale=0.3)
        assert_rows_match(self.attend(q, pool, bt, lens, npb), ref, bt)

    def test_a_page_copied_on_write_is_not_shared(self):
        """Rows 0 and 1 hold the document's five whole pages and, at index
        5, a copy each of its partial page: equal contents under two
        physical numbers. The tables part there, so the group shares five
        pages, and both rows read their own copy."""
        from distributed_pytorch_tpu.ops.paged_attention import (
            shared_prefix_groups,
        )

        rows = doc_rows(25, 29, shared=5)
        q, pool, bt, lens = shared_latent_problem(
            rows, seed=11, copied=[(1, 0, 5)])
        assert (np.asarray(pool[bt[0, 5]]) == np.asarray(pool[bt[1, 5]])).all()
        assert bt[0, 5] != bt[1, 5]
        leader, shared = shared_prefix_groups(
            np.asarray(bt), np.asarray(lens), 4, 2)
        assert list(shared) == [5, 5] and list(leader) == [0, 0]
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V, sm_scale=0.3)
        assert_rows_match(self.attend(q, pool, bt, lens, 2), ref, bt)
        # The copy is read where it stands: poisoning row 1's changes row 1.
        poisoned = pool.at[bt[1, 5]].set(7.0)
        out = self.attend(q, poisoned, bt, lens, 2)
        ref_p = paged_attention_reference(
            q, poisoned, None, bt, lens, v_width=self.V, sm_scale=0.3)
        assert_rows_match(out, ref_p, bt)
        assert (np.asarray(out[0]) == np.asarray(
            self.attend(q, pool, bt, lens, 2)[0])).all()

    @pytest.mark.parametrize("npb", [2, 4])
    def test_a_dispatch_with_no_sharing_gives_what_the_parent_kernel_gave(
            self, npb):
        """Every group a single row: the walk PR 35's kernel made (kept in
        ``parent_latent_kernel.py``), bar the width of each row's last
        block."""
        from parent_latent_kernel import parent_latent_attention

        rows, _, _ = SHARING["no-sharing"]
        q, pool, bt, lens = shared_latent_problem(rows, seed=npb)
        want = parent_latent_attention(
            q, pool, bt, lens, v_width=self.V, pages_per_block=npb,
            sm_scale=0.3)
        assert_rows_match(self.attend(q, pool, bt, lens, npb), want, bt)

    def test_groups_told_are_groups_worked_out(self):
        """A caller that names the groups (the decode program, once for its
        layers) and one that says nothing get the same bits; groups told
        wrongly as rows alone still attend to the right keys."""
        from distributed_pytorch_tpu.ops.paged_attention import (
            shared_prefix_groups,
        )

        rows, npb, _ = SHARING["two-documents-interleaved"]
        q, pool, bt, lens = shared_latent_problem(rows, seed=3)
        out = self.attend(q, pool, bt, lens, npb)
        groups = shared_prefix_groups(bt, lens, pool.shape[1], npb)
        assert isinstance(groups[0], jax.Array)
        told = self.attend(q, pool, bt, lens, npb, row_groups=groups)
        assert (np.asarray(told) == np.asarray(out)).all()
        alone = (jnp.arange(len(rows), dtype=jnp.int32),
                 jnp.zeros((len(rows),), jnp.int32))
        ref = paged_attention_reference(
            q, pool, None, bt, lens, v_width=self.V, sm_scale=0.3)
        assert_rows_match(
            self.attend(q, pool, bt, lens, npb, row_groups=alone), ref, bt)

    def test_traced_and_numpy_groupings_agree(self):
        from distributed_pytorch_tpu.ops.paged_attention import (
            shared_prefix_groups,
        )

        for name, (rows, npb, want) in SHARING.items():
            _, pool, bt, lens = shared_latent_problem(rows, seed=1)
            traced = jax.jit(
                lambda t, p: shared_prefix_groups(t, p, 4, npb))(bt, lens)
            assert [list(map(int, g)) for g in traced] == list(want), name

    def test_bf16_pool_groups_in_its_own_type(self):
        rows = doc_rows(27, 30, 41)
        q, pool, bt, lens = shared_latent_problem(rows, seed=5)
        q, pool = q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)
        out = self.attend(q, pool, bt, lens, 2)
        assert out.dtype == jnp.bfloat16
        want = dense_latent(q, pool, bt, lens, self.V, 0.3)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), want, atol=0.06, rtol=0.06)


GROUPINGS = {
    # name: (tables, positions, min_pages, max_rows) -> (leader, shared,
    # tokens the kernel fetches at 2 pages a block of 4 tokens)
    "a-pair-shares-its-whole-pages": (
        [[3, 5, 9, 2], [3, 5, 9, 7]], [13, 15], 2, 4,
        # 3 shared pages once (a block of 2 and one of 1), a page a row
        ([0, 0], [3, 3], (3 + 1 + 1) * 4)),
    "tables-part-where-the-contents-do": (
        [[3, 5, 9, 2], [3, 5, 8, 7]], [13, 15], 2, 4,
        ([0, 0], [2, 2], (2 + 2 + 2) * 4)),
    "no-page-at-or-past-a-members-position": (
        # row 1 stands in the page both hold at index 2: not shared
        [[3, 5, 9, 2], [3, 5, 9, 0]], [13, 10], 2, 4,
        ([0, 0], [2, 2], (2 + 2 + 1) * 4)),
    "the-null-page-starts-no-group": (
        [[0, 0, 0, 0], [3, 5, 9, 2], [0, 0, 0, 0], [3, 5, 9, 7]],
        [0, 13, 0, 15], 2, 4,
        ([0, 1, 2, 1], [0, 3, 0, 3], (1 + 1 + 3 + 1 + 1) * 4)),
    "under-min-pages-rows-stay-alone": (
        [[3, 5, 9, 2], [3, 5, 8, 7]], [13, 15], 3, 4,
        ([0, 1], [0, 0], (4 + 4) * 4)),
    "a-group-wider-than-max-rows-is-split": (
        [[3, 5, 9, 10 + i] for i in range(5)], [13] * 5, 2, 2,
        # two shared walks of 3 pages, four rows' own page, a row alone
        ([0, 0, 2, 2, 4], [3, 3, 3, 3, 0], (3 + 3 + 1 * 4 + 4) * 4)),
    "two-documents": (
        [[3, 5, 2, 0], [4, 6, 7, 0], [3, 5, 8, 0], [4, 6, 9, 0]],
        [9, 9, 10, 11], 2, 4,
        ([0, 1, 0, 1], [2, 2, 2, 2], (2 + 2 + 1 * 4) * 4)),
}


@pytest.mark.parametrize("name", sorted(GROUPINGS))
def test_the_grouping_rule_on_handmade_tables(name):
    """``shared_prefix_groups`` alone, and ``latent_tokens_fetched`` of what
    it returns: that IS the engine's ``decode_kv_tokens_fetched``."""
    from distributed_pytorch_tpu.ops.paged_attention import (
        latent_tokens_fetched,
        shared_prefix_groups,
    )

    tables, positions, min_pages, max_rows, want = GROUPINGS[name]
    tables = np.asarray(tables, np.int32)
    positions = np.asarray(positions, np.int32)
    leader, shared = shared_prefix_groups(
        tables, positions, 4, min_pages, max_rows)
    assert leader.dtype == shared.dtype == np.int32
    assert (list(leader), list(shared)) == want[:2]
    assert latent_tokens_fetched(
        positions, leader, shared, 4, 2, tables.shape[1]) == want[2]


@pytest.mark.parametrize("pages, npb, walked", [
    (0, 128, 0), (1, 128, 16), (16, 128, 16), (17, 128, 32), (33, 128, 64),
    (65, 128, 128), (128, 128, 128), (129, 128, 144), (557, 128, 576),
    (5, 2, 5), (3, 1, 3), (7, 6, 7), (8, 6, 9),
])
def test_pages_a_walk_copies(pages, npb, walked):
    from distributed_pytorch_tpu.ops.paged_attention import pages_walked

    assert int(pages_walked(np.asarray(pages), npb)) == walked


# ------------------------------------------- index keys of rows that share pages
#
# The index kernel scores a group's shared index keys once, at the group's
# first row, against the members' queries stacked (``paged_index_scores``,
# grouped by the same ``shared_prefix_groups``): whole blocks of its own, so
# what is left of ``shared`` under a block is each member's own walk.


def shared_index_problem(rows, npb, monkeypatch, **kw):
    """``shared_latent_problem``'s dispatch as an index kernel's: ``q [S, H,
    D]``, ``w [S, H]``, the index-key pool, the tables and the positions,
    with the kernel's block set to ``npb`` pages."""
    from distributed_pytorch_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "INDEX_BLOCK_PAGES", npb)
    q, pool, bt, lens = shared_latent_problem(rows, h=6, **kw)
    w = np.random.default_rng(len(rows)).standard_normal(q.shape[::2])
    return q[:, 0], jnp.asarray(w, jnp.float32), pool, bt, lens


def singletons(n):
    return jnp.arange(n, dtype=jnp.int32), jnp.zeros((n,), jnp.int32)


def assert_index_scores(got, q, w, pool, bt, lens):
    """The reference's scores at every ``s <= pos``, ``-inf`` past it and
    everywhere in a row out of the dispatch."""
    from distributed_pytorch_tpu.ops.paged_attention import (
        index_scores_reference,
    )

    got = np.asarray(got)
    want = np.asarray(index_scores_reference(q, w, pool, bt, lens))
    assert got.shape == want.shape
    for r, pos in enumerate(np.asarray(lens)):
        if bt[r, 0] == 0:
            assert np.isneginf(got[r]).all()
            continue
        np.testing.assert_allclose(
            got[r, :pos + 1], want[r, :pos + 1], atol=1e-4, rtol=1e-5)
        assert np.isneginf(got[r, pos + 1:]).all()


class TestIndexRowsThatShare:
    @pytest.mark.parametrize("name", sorted(SHARING))
    def test_grouped_rows_score_what_the_reference_scores(
            self, name, monkeypatch):
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_index_scores,
        )

        rows, npb, _ = SHARING[name]
        q, w, pool, bt, lens = shared_index_problem(
            rows, npb, monkeypatch, seed=len(name))
        got = paged_index_scores(q, w, pool, bt, lens, kernel="interpret")
        assert_index_scores(got, q, w, pool, bt, lens)

    @pytest.mark.parametrize("name", sorted(SHARING))
    def test_grouped_scores_are_the_bits_of_rows_served_alone(
            self, name, monkeypatch):
        """The exact top-k downstream turns a changed score into a changed
        selection: a group's stacked product has to give every member the
        bits its own walk gives."""
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_index_scores,
            shared_prefix_groups,
        )

        rows, npb, (leader, shared) = SHARING[name]
        q, w, pool, bt, lens = shared_index_problem(
            rows, npb, monkeypatch, seed=len(name))
        groups = shared_prefix_groups(bt, lens, pool.shape[1], npb)
        assert [list(map(int, g)) for g in groups] == [leader, shared]
        told = paged_index_scores(
            q, w, pool, bt, lens, kernel="interpret", row_groups=groups)
        alone = paged_index_scores(
            q, w, pool, bt, lens, kernel="interpret",
            row_groups=singletons(len(rows)))
        assert np.array_equal(np.asarray(told), np.asarray(alone))
        worked_out = paged_index_scores(
            q, w, pool, bt, lens, kernel="interpret")
        assert np.array_equal(np.asarray(worked_out), np.asarray(told))

    def test_a_page_copied_on_write_is_scored_where_it_stands(
            self, monkeypatch):
        """``TestLatentRowsThatShare``'s copied page: the group shares the
        five pages before it (two whole blocks of two; the fifth is each
        row's own), and each row scores its own copy."""
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_index_scores,
        )

        q, w, pool, bt, lens = shared_index_problem(
            doc_rows(25, 29, shared=5), 2, monkeypatch, seed=11,
            copied=[(1, 0, 5)])
        assert bt[0, 5] != bt[1, 5]
        got = paged_index_scores(q, w, pool, bt, lens, kernel="interpret")
        assert_index_scores(got, q, w, pool, bt, lens)
        poisoned = pool.at[bt[1, 5]].set(7.0)
        out = paged_index_scores(
            q, w, poisoned, bt, lens, kernel="interpret")
        assert_index_scores(out, q, w, poisoned, bt, lens)
        assert np.array_equal(np.asarray(out[0]), np.asarray(got[0]))
        assert not np.array_equal(np.asarray(out[1]), np.asarray(got[1]))

    @pytest.mark.parametrize("npb", [3, 32])
    def test_a_table_of_no_whole_blocks(self, npb, monkeypatch):
        """Blocks of 3 pages leave the table's 16 a last block of one page;
        at the kernel's own 32 the table is narrower than a block and is ONE
        block of its 16 pages, so nobody shares a whole one."""
        from distributed_pytorch_tpu.ops import paged_attention as pa

        rows, _, _ = SHARING["two-documents-interleaved"]
        q, w, pool, bt, lens = shared_index_problem(
            rows, npb, monkeypatch, seed=npb)
        assert pa.index_block_pages(bt.shape[1]) == min(npb, 16)
        got = pa.paged_index_scores(q, w, pool, bt, lens, kernel="interpret")
        assert_index_scores(got, q, w, pool, bt, lens)
        alone = pa.paged_index_scores(
            q, w, pool, bt, lens, kernel="interpret",
            row_groups=singletons(len(rows)))
        assert np.array_equal(np.asarray(got), np.asarray(alone))

    def test_bf16_keys_score_the_same_bits_grouped_and_alone(
            self, monkeypatch):
        rows, npb, _ = SHARING["five-is-four-and-one"]
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_index_scores,
        )

        q, w, pool, bt, lens = shared_index_problem(
            rows, npb, monkeypatch, seed=7)
        q, pool = q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)
        told = paged_index_scores(q, w, pool, bt, lens, kernel="interpret")
        assert told.dtype == jnp.float32
        alone = paged_index_scores(
            q, w, pool, bt, lens, kernel="interpret",
            row_groups=singletons(len(rows)))
        assert np.array_equal(np.asarray(told), np.asarray(alone))


INDEX_COPIES = {
    # SHARING's name: blocks the walks copy, counted by hand: a row alone or
    # a leader ``pos // block tokens + 1``, a member that less the group's
    # shared whole blocks
    "one-row-alone": 4,
    "a-pair": 4 + (5 - 3),
    "three": 4 + (4 - 3) + (6 - 3),
    "five-is-four-and-one": 4 + 3 * (4 - 3) + 4,
    "shared-length-no-multiple-of-the-block": 2 + (3 - 1),
    "shared-length-under-an-eighth-more": 2 + (2 - 1),
    "an-absent-row-between-members": 4 + (4 - 3) + (4 - 3),
    "a-leader-behind-its-members": 2 + (4 - 1) + (5 - 1),
    "under-a-block-stays-alone": 2 + 2,
    "no-sharing": 4 + 1 + 1 + 8 + 5,
}


@pytest.mark.parametrize("name", sorted(INDEX_COPIES))
def test_index_keys_the_kernel_copies(name):
    """``index_tokens_fetched`` and ``index_rows_grouped`` of a dispatch's
    live rows: the engine's ``decode_index_tokens_fetched`` and, in a model
    with sparse layers, its ``decode_rows_grouped``."""
    from distributed_pytorch_tpu.ops.paged_attention import (
        index_rows_grouped,
        index_tokens_fetched,
        shared_prefix_groups,
    )

    rows, npb, (_, shared) = SHARING[name]
    _, pool, bt, lens = shared_latent_problem(rows)
    live = np.asarray(bt)[:, 0] != 0
    tables, positions = np.asarray(bt)[live], np.asarray(lens)[live]
    page = pool.shape[1]
    groups = shared_prefix_groups(tables, positions, page, npb)
    assert index_tokens_fetched(
        positions, *groups, page, npb, tables.shape[1]
    ) == INDEX_COPIES[name] * npb * page
    assert index_rows_grouped(groups[1], npb) == sum(
        s >= npb for s in shared)


# ------------------------------------------------ neighbouring pages as ONE copy
#
# The latent and index kernels copy a turn of their copy loop whose pages are
# neighbours in the pool as ONE copy (``is_run``). The same bytes land in the
# same places: whatever physical pages a table names, the result is the same
# bits, and the host counts the copies by the kernels' own rule.

RUN_PAGE = 4
#: name: a row is ``None`` or (the document's pages it shares, pages of its
#: own, tokens into its last page). Rows 0 and 1 ask of one document.
RUN_ROWS = {
    "base": [(36, 3, 1), (36, 5, 2), None, (0, 21, 3)],
    # Rows 0 and 3 stand in the LAST page of a turn of every kernel.
    "ends": [(36, 4, 1), (36, 5, 2), None, (0, 24, 1)],
}


def _no_neighbours(phys):
    """Physical pages dealt so that no two neighbours stay neighbours, among
    pages that ``run_problem`` deals to nobody."""
    return 300 + (np.asarray(phys) * 7) % 199


def _runs(doc, own):
    return doc, own


def _permuted(doc, own):
    return _no_neighbours(doc), {r: _no_neighbours(p) for r, p in own.items()}


def _broken(doc, own):
    """The document jumps at index 20, an edge of every kernel's turns (it
    costs nothing), and two pages change places in the MIDDLE of turns: the
    document's at index 5 and row 3's at index 9."""
    doc, own = doc.copy(), {r: p.copy() for r, p in own.items()}
    doc[20:] += 40
    doc[5], own[3][9] = own[3][9], doc[5]
    return doc, own


def _scattered_tails(doc, own):
    return doc, {
        r: _no_neighbours(p) if r in (0, 1) else p for r, p in own.items()
    }


#: name: (rows, layout) and, by hand, ``(copies, pages in runs)`` of the
#: latent kernel (16 pages a block: turns of 2), of its windowed call (a
#: window of 61 tokens: 16 pages from the window's first) and of the index
#: kernel (blocks of 4 pages, a block a turn). Worked out in
#: ``test_runs_go_as_one_copy``'s docstring.
RUN_TABLES = {
    "all-runs": ("base", _runs, (41, 62), (29, 38), (27, 60)),
    "no-two-neighbours": ("base", _permuted, (72, 0), (48, 0), (72, 0)),
    "broken-in-the-middle-of-a-turn": (
        "base", _broken, (51, 42), (35, 26), (63, 12)),
    "a-run-ends-at-the-last-live-page": (
        "ends", _runs, (38, 68), (27, 42), (21, 68)),
    "a-shared-walk-then-scattered-tails": (
        "base", _scattered_tails, (44, 56), (29, 38), (30, 56)),
}


def run_problem(rows, layout, *, h=4, w=20, pages_per_seq=64):
    """``(q, pool, tables, lens)``: the rows' LOGICAL pages (a document's, a
    row's own) always hold the same numbers; ``layout`` says under which
    physical pages. In ``_runs`` the document's pages are neighbours, and so
    are each row's own."""
    rng = np.random.default_rng(0)
    document = 10 + np.arange(36)
    own = {
        r: 100 + 50 * r + np.arange(row[1])
        for r, row in enumerate(rows) if row
    }
    content = {"doc": rng.standard_normal((36, RUN_PAGE, w))}
    for r, pages in own.items():
        content[r] = rng.standard_normal((len(pages), RUN_PAGE, w))
    document, own = layout(document, own)
    pool = np.zeros((512, RUN_PAGE, w))
    pool[document] = content["doc"]
    tables = np.zeros((len(rows), pages_per_seq), np.int32)
    lens = np.zeros((len(rows),), np.int32)
    for r, row in enumerate(rows):
        if row is None:
            continue
        shared, mine, into = row
        pool[own[r]] = content[r]
        tables[r, :shared] = document[:shared]
        tables[r, shared:shared + mine] = own[r]
        lens[r] = (shared + mine - 1) * RUN_PAGE + into
    q = rng.standard_normal((len(rows), 1, h, w))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(pool, jnp.float32),
            jnp.asarray(tables), jnp.asarray(lens))


def _run_kernel(kind, q, pool, bt, lens, **kw):
    """``(result, the gather path's (the index kernel: its head weights),
    (copies, pages in runs) by the host)`` of one of the kernels that copy by
    runs on a dispatch; the host's count is held to the loop's."""
    from page_copy_loops import index_copies_by_loop, latent_copies_by_loop

    from distributed_pytorch_tpu.ops import paged_attention as pa

    live = np.asarray(bt)[:, 0] != 0
    tables, positions = np.asarray(bt)[live], np.asarray(lens)[live]
    alone = np.arange(len(tables), dtype=np.int32), np.zeros(
        len(tables), np.int32)
    if kind == "index":
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            q.shape[::2]), jnp.float32)
        out = pa.paged_index_scores(
            q[:, 0], w, pool, bt, lens, kernel="interpret", **kw)
        groups = pa.shared_prefix_groups(tables, positions, RUN_PAGE, 4)
        copies = pa.index_copies_started(
            tables, positions, *groups, RUN_PAGE, 4)
        assert copies == index_copies_by_loop(
            tables, positions, *groups, RUN_PAGE, 4)
        return out, w, copies
    window = {"window": 61} if kind == "latent-windowed" else {}
    out = pa.paged_latent_attention(
        q, pool, bt, lens, v_width=16, kernel="interpret", sm_scale=0.3,
        pages_per_block=16, **window, **kw)
    ref = paged_attention_reference(
        q, pool, None, bt, lens, v_width=16, sm_scale=0.3, **window)
    if window:
        tables, positions, _ = pa.window_tables(tables, positions, RUN_PAGE, 61)
        groups = alone
    else:
        groups = pa.shared_prefix_groups(tables, positions, RUN_PAGE, 16)
    copies = pa.latent_copies_started(tables, positions, *groups, RUN_PAGE, 16)
    assert copies == latent_copies_by_loop(
        tables, positions, *groups, RUN_PAGE, 16)
    return out, ref, copies


@pytest.mark.parametrize("name", sorted(RUN_TABLES))
@pytest.mark.parametrize("kind", ["latent", "latent-windowed", "index"])
def test_runs_go_as_one_copy(kind, name, monkeypatch):
    """Every table gives, bit for bit, what the SAME logical pages under
    physical pages of which no two are neighbours give (there every copy is a
    page's), and for the latent kernel what PR 35's page-by-page kernel gives
    rows served alone; and the host counts the copies the kernel starts.

    By hand, on ``base``: rows 0 and 1 hold a document's 36 pages and 3 and
    5 of their own, row 3 holds 21. *Latent* (blocks of 16 pages in turns of
    2; a walk's last block at the width that holds it, clamped to the walk's
    last page; a block's LEADING turns that are runs go as one copy each, the
    rest a copy a page): the shared walk 16 + 16 + 4 pages = 18 turns; row
    0's own 3 pages at a width of 4 = turns (36, 37), (38, 38); row 1's 5 at
    8 = (36, 37), (38, 39), (40, 40), (40, 40); row 3's 16 + 5 at 8 = 8 + 4
    turns of which the last two repeat page 20: 36 turns. All runs: 18 + (1 +
    2) + (2 + 2 + 2) + (10 + 2 + 2) = 41 copies, 31 runs. Broken: the
    document's turn (4, 5) is none, so its first block is 2 runs and 6 turns
    of 2 copies, and row 3's turn (8, 9) leaves its first block 4 runs and 4
    turns: (2 + 12 + 8 + 2) + 3 + 6 + (4 + 8 + 6) = 51, 21 runs; the jump at
    index 20 is a turn's edge and costs nothing. Tails scattered: rows 0 and
    1 a copy a page: 18 + 4 + 8 + 14. *Windowed* (every row alone, ONE block
    of 16 pages from the page that holds ``pos - 60``): row 0 pages 23-38,
    row 1 25-40, row 3 5-20: 8 turns each; the turn (35, 36) crosses from the
    document into a row's own pages and ends the block's leading runs: 6 +
    2 x 2, 5 + 3 x 2 and 8 copies, scattered tails or not. Broken: row 3's
    turn (9, 10) is its third: 2 + 6 x 2. *Index* (a block of 4 pages a turn;
    the LEADING blocks of a row's table that are runs go as one copy each):
    row 0 leads and walks blocks 0-9 (the last 36, 37, 38, 38), row 1 blocks
    9 and 10 (40 four times), row 3 blocks 0-5 (the last 20 four times): 18
    blocks, all runs 9 + 4, 1 + 4, 5 + 4 copies. Broken: the document's
    block 1 and row 3's block 2 are none, so rows 0 and 1 have ONE leading
    run and row 3 two: (1 + 36) + 8 + (2 + 16). Tails scattered: row 1's
    block 9 is none: 13 + 8 + 9. On ``ends`` rows 0 and 3 hold 40 and 24
    pages: latent 18 + 2 + 6 + 12; windowed row 0 pages 24-39 (8 runs: the
    document ends at a turn's edge), row 1 as before, row 3 8-23; index 10 +
    (1 + 4) + 6."""
    from distributed_pytorch_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "INDEX_BLOCK_PAGES", 4)
    rows, layout, *counted = RUN_TABLES[name]
    q, pool, bt, lens = run_problem(RUN_ROWS[rows], layout)
    out, ref, copies = _run_kernel(kind, q, pool, bt, lens)
    want = dict(zip(("latent", "latent-windowed", "index"), counted))[kind]
    assert copies == want
    q_p, pool_p, bt_p, _ = run_problem(RUN_ROWS[rows], _permuted)
    apart, _, none = _run_kernel(kind, q_p, pool_p, bt_p, lens)
    assert none[1] == 0
    assert np.array_equal(np.asarray(out), np.asarray(apart))
    if kind == "index":
        assert_index_scores(out, q[:, 0], ref, pool, bt, lens)
    else:
        assert_rows_match(out, ref, bt)
    if kind == "latent":
        from parent_latent_kernel import parent_latent_attention

        alone = _run_kernel(
            kind, q, pool, bt, lens, row_groups=singletons(len(bt)))[0]
        parent = parent_latent_attention(
            q, pool, bt, lens, v_width=16, pages_per_block=16, sm_scale=0.3)
        assert_rows_match(alone, parent, bt)


@pytest.mark.parametrize("seed", range(4))
def test_the_hosts_count_of_copies_is_the_loops(seed):
    """``latent_copies_started`` and ``index_copies_started`` (arrays)
    against the kernels' copy loops walked a page at a time, on dispatches of
    rows that share documents laid out partly in runs: at the benchmark's
    block (128 pages: turns of 16) and the index kernel's (32)."""
    from page_copy_loops import index_copies_by_loop, latent_copies_by_loop

    from distributed_pytorch_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(seed)
    page, width, slots = 16, 1024, 12
    tables = np.zeros((slots, width), np.int32)
    positions = np.zeros((slots,), np.int32)
    nxt = 1
    for d in range(slots // 3):
        whole = int(rng.integers(130, 600))
        document = nxt + np.arange(whole)
        nxt += whole
        if d % 2:  # a document that was prefilled among others: short runs
            cuts = np.sort(rng.choice(whole, 12, replace=False))
            document = np.concatenate(
                [part[::-1] if i % 3 == 0 else part
                 for i, part in enumerate(np.split(document, cuts))])
        for r in range(3 * d, 3 * d + 3):
            own = int(rng.integers(1, 40))
            tables[r, :whole] = document
            tables[r, whole:whole + own] = nxt + rng.permutation(own) * (
                1 if r % 2 else 0) + (0 if r % 2 else np.arange(own))
            nxt += own
            positions[r] = (whole + own) * page - int(rng.integers(1, page))
    for npb, started, loop in (
        (128, pa.latent_copies_started, latent_copies_by_loop),
        (32, pa.index_copies_started, index_copies_by_loop),
    ):
        groups = pa.shared_prefix_groups(tables, positions, page, npb)
        assert (groups[1] > 0).any()
        got = started(tables, positions, *groups, page, npb)
        assert got == loop(tables, positions, *groups, page, npb)
        assert 0 < got[1] and got[0] > got[1] // npb
