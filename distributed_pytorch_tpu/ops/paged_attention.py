"""Pallas paged-attention (flash-decode) over the serving engine's KV pools.

The serving decode hot path reads the paged KV cache — a global per-layer
pool ``[num_pages, page_size, Hkv, D]`` addressed through per-sequence block
tables — and until this module existed it did so via a plain-XLA gather
(now :func:`paged_attention_reference`): materialize every row's
``[pages_per_seq * page_size, Hkv, D]`` logical view in HBM, then attend.
``obs/roofline.py`` classifies that program bandwidth-bound; the gather
writes and re-reads the whole working set once per generated token.

This kernel fuses the block-table indirection into the attention loop, and
walks only the KV a row has:

* grid ``(slots,)``, run in order. The block table and the rows' positions
  ride as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``); the
  pools stay in HBM (``memory_space=pl.ANY``) and are never gathered, copied
  or re-laid-out as a whole.
* per row, a ``fori_loop`` over its ``pos // block_tokens + 1`` KV blocks,
  a trip count read from the prefetched positions: a row of 300 tokens in a
  table of 4,096 walks two blocks of 256, not sixteen. Each block is
  ``pages_per_block`` PHYSICAL pages, each copied whole by
  ``pltpu.make_async_copy`` (one contiguous ``[page * Hkv, D]`` slab, the
  rows the pool stores it as) into one of two VMEM buffers a pool; the next
  block's copies, or the next live row's first block's, are started before
  the current block is computed.
* what is never fetched: a page the row does not own. A logical page past
  the row's last live one clamps to that one (its key positions lie past
  ``pos`` and die in the mask), so the padded tail of a table, the null
  page it points at, and every other row's pages stay untouched.
* rows out of the dispatch group do nothing: a row whose table STARTS at
  the null page (the engine stages a zeroed table for it) starts no copy,
  does no arithmetic, and its output is zeros. A length of 0 is no such
  sign: a live one-token prompt decodes at position 0 and sees its own key.
  The model discards those rows' outputs either way, and their K/V writes
  still land in the null page.
* a block is computed on its page tiles AS STORED (PR 48): a buffer's
  ``[block tokens * Hkv, D]`` rows, in the pool's (token, KV head) order and
  dtype, are the operand of both products as they were copied: no float32
  copy, no reshape into heads, no transpose. ONE product ``[H, D] x rows^T``
  scores all the query heads against every row (M = ``H``), and ONE product
  ``[H, columns] x rows`` is the weighted sum; bf16 operands (exact in
  float32), float32 accumulation. It does ``Hkv`` times the products and
  ``exp``s of a layout a KV head, which the chip has to spare beside the
  block's copy at 1 to 32 KV heads (PERF.md section 6, PR 48).
* online softmax (FlashAttention-style running max / denominator / output
  accumulator in fp32 VMEM scratch, persisting across a row's blocks) over
  the block's columns, with grouped-query head mapping in the mask: query
  head ``h`` keeps the columns of kv head ``h // group`` (``col % Hkv``), the
  reference's grouping.
* masking is positional, exactly as the reference: key position ``kpos``
  (``col // Hkv``) is visible iff ``kpos <= pos`` (the row's current absolute
  position), which also kills the dead tail of a row's last block. The
  columns' keys and heads are the same for every block: built once a row.
* int8 KV pages: with ``k_scale``/``v_scale`` (``[num_pages, page_size,
  Hkv]`` float32, quantized on page write by the model) the kernel copies
  int8 pages, a quarter of the fp32 page bytes. A ``[page, Hkv]`` scale
  page has no lane-aligned slice a manual copy could take, so the rows'
  scales are gathered through the table outside the kernel (one float a
  key and kv head, a 1/D-th of a gathered view) and, being one number a
  column, are applied to the block's scores and weights instead of its tiles
  (whose int8 numbers are exact in the queries' dtype): the same product in
  float32, in another order.

``paged_attention_reference`` is the pure-XLA fallback of a decode step: the
dense read (each row's whole table gathered, a one-shot softmax), what the
engine did before the kernel and what every other path is tested against.
Mode resolution ("auto") uses the kernel on TPU and the reference elsewhere;
``kernel="interpret"`` runs the Pallas kernel through the interpreter — the
CPU test rig's way of exercising the real kernel code path.

A CHUNK of queries (``T_step > 1``: a prefill piece, a speculative round's
verification) is neither: :func:`_paged_walk`, plain XLA, a ``fori_loop``
over blocks of :data:`WALK_BLOCK_TOKENS` keys with an online softmax whose
trip count is the blocks the longest row HOLDS (:func:`chunk_keys_walked`),
so a piece at position 300 of a 4,096-key table scores 1,024 keys a query
and not 4,096. :func:`paged_attention` is the one read of K/V pages at every
``T_step``; ``models/transformer.py`` holds no gather of its own.

GSPMD cannot partition a ``pallas_call``, so under a sharded jit pass
``mesh`` (as :class:`models.transformer.Attention` does): the kernel then
runs per-shard under ``shard_map`` with the KV-head dim split over the
``model`` axis — the same placement ``serving/mesh.py:KV_POOL_SPEC`` gives
the pools, so no collective is added beyond what the weight split implies.

The latent and the index kernel (below) copy a RUN of pages as one DMA. A
turn of their copy loops names a few table entries (16 pages of the latent
kernel's block of 128, the index kernel's whole block of 32); where those
entries are NEIGHBOURING pages of the pool, ``entry[i] == entry[0] + i``
(:func:`is_run`: one rule for both kernels and for the host's count of their
copies, :func:`latent_copies_started` / :func:`index_copies_started`), the
pages stand side by side in HBM and ONE copy of the whole stretch takes the
place of a copy a page: the same bytes into the same places of the same
buffer, so the result is the same bits whatever the tables name. A resident
document's pages, prefilled in one go off a free list that deals ascending
numbers, are neighbours from its first page on, so what a kernel is told
(:func:`latent_runs`, :func:`index_runs`: worked out from the tables before
the call, a scalar-prefetch operand) is how many LEADING turns of a block, or
blocks of a row's table, are runs, and it copies them in a loop of their own
before the loop that copies the rest a page at a time: a test a turn, in the
kernel's one instruction stream, cost a dispatch with no runs 5-10% (PERF.md
section 6, PR 44), two loops cost it nothing. A WAIT counts bytes on the
buffer's semaphore, not copies: one wait a turn serves a run's copy and a
copy a page alike. This kernel, ``_decode_kernel``, copies a page at a time:
its tables are a chat's, grown a page at a time.

Block sizing (``pages_per_block``) comes from the ``ops/flash_autotune``
harness' ``paged_decode`` family: measured winners on real hardware, a
seeded table entry for CPU/interpret so CI never autotunes. A block is also
the unit of waste: a row walks whole blocks, so it reads up to one block of
keys it cannot see (:func:`kv_tokens_walked`; ``serving/decode_reads.py``
counts both sides as ``decode_kv_tokens_fetched`` / ``_visible``, at the block
each call looks up: :func:`kv_block_pages`, :func:`latent_block_pages`,
:func:`index_block_pages`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu.ops.attention import NEG_INF
from distributed_pytorch_tpu.utils.platform import on_tpu

#: Accepted ``kernel=`` modes: "auto" resolves per backend, "pallas" forces
#: the compiled kernel, "interpret" runs the kernel through the Pallas
#: interpreter (CPU tests), "xla" forces the reference fallback.
KERNEL_MODES = ("auto", "pallas", "interpret", "xla")

#: The pool's reserved physical page (``serving/kv_cache.py`` ``NULL_PAGE``):
#: padded table tails point at it, and a row whose table STARTS with it is
#: out of the dispatch group.
NULL_PAGE = 0


def resolve_kernel(kernel) -> str:
    """Settle a ``kernel=`` toggle to a concrete mode.

    ``"auto"``/``True`` pick the compiled kernel on TPU and the XLA
    reference everywhere else (the interpreter is orders of magnitude
    slower than dense XLA — it is a correctness tool, never an implicit
    fallback)."""
    if kernel is True or kernel in (None, "auto"):
        return "pallas" if on_tpu() else "xla"
    mode = str(kernel)
    if mode not in ("pallas", "interpret", "xla"):
        raise ValueError(
            f"unknown paged-attention kernel mode {kernel!r} "
            f"(expected one of {KERNEL_MODES})"
        )
    return mode


def paged_attention_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    sm_scale: Optional[float] = None,
    v_width: Optional[int] = None,
    window: int = 0,
) -> jnp.ndarray:
    """The XLA gather path, the DENSE read (gather each row's pages into its
    contiguous logical view, positional visibility mask, grouped GQA
    einsums, f32 softmax over the table's width): a decode step with the
    kernel off, and the anchor the kernel and the chunk walk are tested
    against.

    ``q`` [S, T_step, H, D] is post-RoPE; ``seq_lens`` [S] is each row's
    token count BEFORE the step (= the absolute position of its first new
    token). With ``k_scale``/``v_scale`` the pools are int8 and dequantize
    at the gather, mirroring the contiguous quantized-cache idiom.
    ``sm_scale`` multiplies the scores; ``None`` is ``D ** -0.5``.

    ``v_pool=None`` is the latent case (``models/mla.py``): ``k_pool`` is
    ONE pool ``[num_pages, page, W]`` with no head axis, ``q`` is ``[S,
    T_step, H, W]``, and a token's value is the first ``v_width`` numbers of
    its key; the result is ``[S, T_step, H, v_width]``. ``window`` (the
    latent case only) keeps a query at ``t`` to the keys ``(t - window, t]``;
    0 is none."""
    s, t_step, h, d = q.shape
    page = k_pool.shape[1]
    pages_per_seq = block_tables.shape[1]
    kv_len = pages_per_seq * page

    positions = seq_lens.astype(jnp.int32)[:, None] + jnp.arange(
        t_step, dtype=jnp.int32
    )
    if v_pool is None:
        # A latent pool [num_pages, page, W]: ONE cached vector a token that
        # every query head reads, whose first ``v_width`` numbers are also
        # the value (QK width W, V width ``v_width``).
        if k_scale is not None:
            raise ValueError("a latent pool has no per-head scales")
        keys = k_pool[block_tables].reshape(s, kv_len, d)
        scale = d**-0.5 if sm_scale is None else sm_scale
        k_abs = jnp.arange(kv_len)[None, None, :]
        visible = k_abs <= positions[:, :, None]  # [S, T_step, K]
        if window:
            visible &= k_abs > positions[:, :, None] - window
        logits = jnp.einsum("bqhd,bkd->bhqk", q, keys) * scale
        logits = jnp.where(visible[:, None], logits, NEG_INF)
        weights = jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
        return jnp.einsum("bhqk,bkd->bqhd", weights, keys[..., :v_width])
    kv_heads = k_pool.shape[2]
    keys = k_pool[block_tables].reshape(s, kv_len, kv_heads, d)
    values = v_pool[block_tables].reshape(s, kv_len, kv_heads, d)
    if k_scale is not None:
        ks = k_scale[block_tables].reshape(s, kv_len, kv_heads)
        vs = v_scale[block_tables].reshape(s, kv_len, kv_heads)
        keys = keys.astype(q.dtype) * ks[..., None].astype(q.dtype)
        values = values.astype(q.dtype) * vs[..., None].astype(q.dtype)
    scale = d**-0.5 if sm_scale is None else sm_scale
    k_abs = jnp.arange(kv_len)[None, None, :]
    visible = k_abs <= positions[:, :, None]  # [S, T_step, K]
    group = h // kv_heads
    qg = q.reshape(s, t_step, kv_heads, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys) * scale
    logits = jnp.where(visible[:, None, None], logits, NEG_INF)
    weights = jax.nn.softmax(
        logits.astype(jnp.float32), axis=-1
    ).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, values)
    return out.reshape(s, t_step, h, d)


#: How ``_decode_kernel`` computes a block, as ``engine.stats()`` names it
#: (``kv_decode_block_form``): on the page tiles AS STORED. The sweep of PR 48
#: (PERF.md section 6) timed it against the tile relaid a KV head (operands
#: float32, bf16 after a float32 relayout, bf16 throughout) at every cell's
#: geometry, 1 to 32 KV heads: it won or tied at all of them, so it is the
#: one form.
KV_BLOCK_FORM = "stored"

#: The key of a score column that is not its query head's own: past any
#: position.
_NO_KEY = 2**30


def _decode_kernel(
    bt_ref, lens_ref, *refs, npb, group, sm_scale, quantized, windowed=False
):
    """One slot (grid step) of the flash-decode kernel: walk the row's own
    KV blocks, and only those.

    ``refs`` unpacks to (when ``windowed``) a third scalar-prefetch operand,
    each row's first live key, then the row's queries, the K and V pools left
    in HBM, (when quantized) the
    row's K and V scales by block, the output block, then the scratch: two
    buffers of ``npb`` pages for each pool, one DMA semaphore a buffer, the
    buffer the row's first block was prefetched into (SMEM), and the three
    fp32 accumulators (running max ``m``, denominator ``l``, output
    ``acc``).

    ``windowed`` is a window layer's call (:func:`paged_window_attention`):
    the table is the row's SHORT one, from the page that holds its window's
    first key on, positions count from that page's first token, and a key
    before ``lo_ref[row]`` is masked like one past ``pos``. That first key
    stands in the table's first page, so every walked block still has a
    visible key.

    A block is computed on its tiles AS STORED: a buffer's ``[block tokens *
    Hkv, D]`` rows, (token, KV head) order, are the operand of both products
    as they were copied. ONE product scores ALL the query heads against every
    row, ``[H, D] x rows^T`` (M = ``H``), and a query head keeps the columns
    of its own KV head (``col % Hkv == head // group``) at visible positions
    (``col // Hkv``); the online softmax runs over the columns; ONE product
    ``[H, columns] x rows`` is the weighted sum (the other heads' columns
    weigh exactly 0). No float32 copy, reshape or transpose of a tile, at
    ``Hkv`` times the products and ``exp``s, which the MXU and the vector unit
    have to spare beside the block's copy at every geometry measured (PERF.md
    section 6, PR 48). The operands are the pool's dtype (bf16 x bf16 is
    exact in float32; int8 pages: the queries' dtype, exact too, their scales
    multiplying scores and weights outside the products; the softmax weights
    are rounded to it before the weighted sum, as the reference's are), the
    products accumulate in float32, and max, ``exp``, sums, correction and
    the accumulators are float32."""
    lo_ref = None
    if windowed:
        lo_ref, *refs = refs
    q_ref, *refs = refs
    k_hbm, v_hbm = refs[:2]
    ks_ref, vs_ref = refs[2:4] if quantized else (None, None)
    (o_ref, k_buf, v_buf, sems, first_buf, m_scr, l_scr, acc_scr) = refs[
        4 if quantized else 2 :
    ]

    b = pl.program_id(0)
    slots, pages_per_seq = bt_ref.shape
    h, d = q_ref.shape[1:]
    kv_heads = h // group
    page = k_buf.shape[2] // kv_heads
    bkv = npb * page
    cols = bkv * kv_heads  # a block's rows, and its scores' columns
    # int8 is exact in the queries' dtype.
    operand = q_ref.dtype if quantized else k_buf.dtype

    def is_live(row):
        # A row out of the dispatch group stages a zeroed block table: its
        # first entry is the null page.
        return bt_ref[row, 0] != NULL_PAGE

    def page_copies(phys, buf, n):
        return [
            pltpu.make_async_copy(pool.at[phys], dst.at[buf, n], sems.at[buf])
            for pool, dst in ((k_hbm, k_buf), (v_hbm, v_buf))
        ]

    # Both loops are unrolled on purpose: on the v5e a rolled page loop made
    # a call 15-20% slower (PERF.md section 6), and ``_paged_flash`` is traced
    # once a program, not once a layer.
    def start(row, blk, buf):
        """Start the K and V copies of ``row``'s block ``blk`` into buffer
        ``buf``. A logical page past the row's last live one clamps to that
        one: a page the row does not own is never fetched, and the
        duplicates' key positions lie past ``pos``."""
        last = jnp.minimum(lens_ref[row] // page, pages_per_seq - 1)
        for n in range(npb):
            phys = bt_ref[row, jnp.minimum(blk * npb + n, last)]
            for copy in page_copies(phys, buf, n):
                copy.start()

    def wait(buf):
        # A wait takes a copy's size and semaphore, not its source.
        for n in range(npb):
            for copy in page_copies(0, buf, n):
                copy.wait()

    @pl.when(jnp.logical_not(is_live(b)))
    def _absent():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(is_live(b))
    def _row():
        pos = lens_ref[b]  # the decode token's absolute position
        n_blocks = jnp.minimum(
            pos // bkv + 1, pl.cdiv(pages_per_seq, npb)
        )
        # The row before, if live, started this row's first block while it
        # computed its own last one; else this row starts it itself.
        prefetched = jnp.logical_and(b > 0, is_live(jnp.maximum(b - 1, 0)))
        buf0 = jnp.where(prefetched, first_buf[0], 0)

        @pl.when(jnp.logical_not(prefetched))
        def _first():
            start(b, 0, 0)

        next_row = jnp.minimum(b + 1, slots - 1)
        next_live = jnp.logical_and(b + 1 < slots, is_live(next_row))

        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        # The mask's constant part, once a row: the key a score column
        # stands for, counted from its block's first, where the column's KV
        # head is the query head's own; elsewhere a key past any position.
        col = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)
        own = jax.lax.rem(col, kv_heads) == jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (h, cols), 0), group
        )
        key = jnp.where(own, jax.lax.div(col, kv_heads), _NO_KEY)
        q = q_ref[0].astype(operand)  # [H, D]

        def block(j, carry):
            buf = (buf0 + j) % 2

            @pl.when(j + 1 < n_blocks)
            def _next_block():
                start(b, j + 1, 1 - buf)

            @pl.when(jnp.logical_and(j + 1 == n_blocks, next_live))
            def _next_row():
                start(next_row, 0, 1 - buf)
                first_buf[0] = 1 - buf

            wait(buf)

            def rows(pages):
                # [npb, page * Hkv, D] -> [bkv * Hkv, D]: the leading axes
                # merged, every row where it is.
                return pages[buf].reshape(cols, d).astype(operand)

            s_blk = jax.lax.dot_general(
                q, rows(k_buf), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, bkv * Hkv]
            if quantized:
                # A key's scale is one number a (position, kv head), a
                # column: it factors out of the contraction over D.
                s_blk = s_blk * ks_ref[0, j]
            s_blk = s_blk * sm_scale
            # Every walked block has key ``j * bkv`` visible, so the running
            # max stays finite and no exp(NEG_INF - NEG_INF) row can arise.
            visible = key <= pos - j * bkv
            if windowed:
                visible = jnp.logical_and(visible, key >= lo_ref[b] - j * bkv)
            s_blk = jnp.where(visible, s_blk, NEG_INF)
            m_prev = m_scr[:, :1]
            l_prev = l_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * vs_ref[0, j]
            pv = jax.lax.dot_general(
                p.astype(operand), rows(v_buf), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, D]
            acc_scr[:] = acc_scr[:] * correction + pv
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def block_pages(
    pages_per_seq: int, page: int, head_dim: int, dtype, pages_per_block=None
) -> int:
    """Pages in one KV block of the kernel for a decode shape: the autotune
    harness' ``paged_decode`` entry unless ``pages_per_block`` is given,
    held to the block table's width."""
    if pages_per_block is None:
        from distributed_pytorch_tpu.ops.flash_autotune import lookup_paged

        pages_per_block = lookup_paged(
            pages_per_seq * page, page, head_dim,
            dtype_name=jnp.dtype(dtype).name,
        )
    return max(1, min(int(pages_per_block), int(pages_per_seq)))


def kv_block_pages(
    pages_per_seq: int, pool, dtype, pages_per_block=None, *, short=False
) -> int:
    """The block of the K/V kernel's call (:func:`paged_attention`) over a
    table ``pages_per_seq`` wide, a pool ``[num_pages, page, Hkv, D]`` and
    queries of ``dtype`` (int8 pages are looked up as their queries are).
    The call and whoever counts what it reads ask here, with what the call is
    handed (``serving/decode_reads.py``). ``short`` is a window group's table
    (:func:`paged_window_attention`): looked up as the power of two that holds
    it and held to its own width, so that a row's 9 pages are ONE block where
    the device's block is 16 and not a block of 8 and one of 1."""
    width = 1 << (pages_per_seq - 1).bit_length() if short else pages_per_seq
    return min(pages_per_seq, block_pages(
        width, pool.shape[1], pool.shape[-1], dtype, pages_per_block
    ))


def latent_block_pages(pages_per_seq: int, pool, pages_per_block=None) -> int:
    """The block of the latent kernel's call (:func:`paged_latent_attention`)
    over a table ``pages_per_seq`` wide (a windowed call's:
    :func:`window_pages`) and a pool ``[num_pages, page, W]``: looked up under
    the pool's own width and dtype. As :func:`kv_block_pages`, for the call
    and for the count alike; the index kernel's is
    :func:`index_block_pages`."""
    return block_pages(
        pages_per_seq, pool.shape[1], pool.shape[-1], pool.dtype,
        pages_per_block,
    )


def kv_tokens_walked(positions, block_tokens: int):
    """Key positions the kernel fetches and computes on for decode rows at
    ``positions`` (a row at ``pos`` sees ``pos + 1`` keys): whole blocks of
    ``block_tokens``, up to the one that holds ``pos``."""
    return (positions // block_tokens + 1) * block_tokens


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "interpret", "sm_scale")
)
def _paged_flash(
    q3, k_pool, v_pool, block_tables, seq_lens, k_scale, v_scale,
    first_key=None, *, pages_per_block, interpret, sm_scale=None,
):
    """Build and invoke the pallas_call for ``q3`` [S, H, D] (T_step == 1).

    Jitted so that a model's layers, which all call it at one shape, share
    ONE trace and one lowering: the kernel's page copies are unrolled, and
    traced a layer at a time they cost a 30-layer decode program ~40 s of
    set-up in every process, compile cache or not. The kernel is named, so
    a device trace shows it as ``attention._paged_decode_step`` (the name it
    has had in every trace, then taken from the calling module's scope)
    whoever calls it; a window layer's call (``first_key [S]``: each row's
    first live key, counted like ``seq_lens`` from its short table's first
    token) is :data:`KV_WINDOW_KERNEL`, so a trace tells the two kinds apart.

    The grid is the slots, run in order (``"arbitrary"``: a row's last block
    starts the next row's first). The block table and the lengths are scalar
    prefetch operands. The pools stay in HBM (``pl.ANY``), each page seen as
    the ``[page * Hkv, D]`` rows it is stored as (a ``[page, Hkv, D]`` slice
    is refused by Mosaic where Hkv is no multiple of the dtype's sublane
    packing, one KV head in bf16 for one), and the kernel copies them page by
    page into two VMEM buffers of ``pages_per_block`` pages a pool.

    int8 pages: a scale page ``[page, Hkv]`` has no lane-aligned slice to
    copy, so the rows' scales are gathered through the table here, by block
    and as the scores' columns are (``[S, blocks, 1, block tokens * Hkv]``
    float32: a 1/D-th of a gathered view), and ride in as one block a row."""
    s, h, d = q3.shape
    num_pages, page, kv_heads = k_pool.shape[:3]
    pages_per_seq = block_tables.shape[1]
    group = h // kv_heads
    npb = int(pages_per_block)
    nblk = -(-pages_per_seq // npb)
    quantized = k_scale is not None
    windowed = first_key is not None
    bt = block_tables.astype(jnp.int32)
    prefetch = (bt, seq_lens.astype(jnp.int32))
    if windowed:
        prefetch += (first_key.astype(jnp.int32),)

    def row_spec(shape):
        return pl.BlockSpec(
            shape, lambda b, *_: (b,) + (0,) * (len(shape) - 1),
            memory_space=pltpu.VMEM,
        )

    padded = jnp.pad(bt, ((0, 0), (0, nblk * npb - pages_per_seq)))

    def by_block(scale):
        return scale[padded].reshape(s, nblk, 1, npb * page * kv_heads)

    operands = [
        pool.reshape(num_pages, page * kv_heads, d)
        for pool in (k_pool, v_pool)
    ]
    in_specs = [row_spec((1, h, d))] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    if quantized:
        operands += [by_block(k_scale), by_block(v_scale)]
        in_specs += [row_spec((1, nblk, 1, npb * page * kv_heads))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(s,),
        in_specs=in_specs,
        out_specs=row_spec((1, h, d)),
        scratch_shapes=[
            pltpu.VMEM((2, npb, page * kv_heads, d), k_pool.dtype),
            pltpu.VMEM((2, npb, page * kv_heads, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),  # buffer of the row's first block
            pltpu.VMEM((h, 128), jnp.float32),  # running max m
            pltpu.VMEM((h, 128), jnp.float32),  # denominator l
            pltpu.VMEM((h, d), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, npb=npb, group=group,
            sm_scale=d**-0.5 if sm_scale is None else sm_scale,
            quantized=quantized, **({"windowed": True} if windowed else {}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, d), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=KV_WINDOW_KERNEL if windowed else "attention._paged_decode_step",
    )(*prefetch, q3, *operands)


#: Key positions a block of the chunk walk (:func:`_paged_walk`) holds: the
#: best ONE size over the cells' four geometries and every width and start of
#: a piece (``tools/bench_prefill_attention.py``, PERF.md section 6, PR 47).
WALK_BLOCK_TOKENS = 512


def walk_block_pages(pages_per_seq: int, page: int) -> int:
    """Pages in a block of the chunk walk over a table ``pages_per_seq``
    wide: :data:`WALK_BLOCK_TOKENS`, held to the table (a table of at most
    one block is one trip)."""
    return max(1, min(WALK_BLOCK_TOKENS // page, pages_per_seq))


def _blocks_walked(n_keys, pages_per_seq: int, page: int, bp: int):
    """Blocks of ``bp`` pages that hold the first ``n_keys`` key positions of
    a table ``pages_per_seq`` wide: at least one, at most the table's.
    NumPy, an int or traced."""
    xp = jnp if isinstance(n_keys, jax.Array) else np
    return xp.clip(-(-n_keys // (bp * page)), 1, -(-pages_per_seq // bp))


def chunk_keys_walked(n_keys, pages_per_seq: int, page: int):
    """Key positions the chunk walk gathers and scores, a row, where its
    longest row holds ``n_keys`` keys once the chunk is written (``start +
    tokens`` of a prefill piece): whole blocks. The walk's trip count and the
    host's count of it (``serving/decode_reads.py``) are one rule,
    :func:`_blocks_walked` at :func:`walk_block_pages`."""
    bp = walk_block_pages(pages_per_seq, page)
    return _blocks_walked(n_keys, pages_per_seq, page, bp) * (bp * page)


@functools.partial(jax.jit, static_argnames=("bp", "sm_scale"))
def _paged_walk(
    q, k_pool, v_pool, block_tables, seq_lens, valid_lens=None, k_scale=None,
    v_scale=None, *, bp, sm_scale=None,
):
    """Attention of a CHUNK of queries ``q`` [S, T_step, H, D] (a prefill
    piece; a speculative round's verification) over the rows' K/V pages, a
    block of pages at a time with an online softmax, over the blocks that
    hold a key some query can see and no others: a piece that starts at 300
    in a table of 4,096 walks two blocks of 512, not eight. The trip count
    is traced (:func:`chunk_keys_walked` of the longest row), so one program
    serves every length; ``models/mla.py`` ``_attend_blocks`` is the same walk
    over latent pages. Jitted, as :func:`_paged_flash` is and for its reason:
    a model's layers all call it at one shape and share ONE trace and one
    lowering a program (30 layers x 8 prefill programs traced a layer at a
    time cost ``sc2-3b-completion`` 5.7 s of set-up; PERF.md section 6, PR
    47); ``bp``, the block in pages, is static and its caller's to look up.

    The reference's operands and precision: products of the pool's type,
    scores, softmax statistics and accumulator in float32; int8 pages are
    dequantised a block at a time. Key 0 is visible to every query, so the
    running max is finite from the first block on; a row shorter than the
    longest sees nothing in the blocks past its own and its state passes
    through them unchanged. The padding of a piece sees what the walked
    blocks hold below its position, and is its caller's to throw away."""
    s, t_step, h, d = q.shape
    page, kv_heads = k_pool.shape[1:3]
    pages_per_seq = block_tables.shape[1]
    bkv = bp * page
    tables = jnp.pad(
        block_tables, ((0, 0), (0, -(-pages_per_seq // bp) * bp - pages_per_seq))
    )
    lens = seq_lens.astype(jnp.int32)
    held = lens + (t_step if valid_lens is None else valid_lens)
    n_blocks = _blocks_walked(jnp.max(held), pages_per_seq, page, bp)
    positions = lens[:, None] + jnp.arange(t_step, dtype=jnp.int32)
    scale = d**-0.5 if sm_scale is None else sm_scale
    # Heads lead, as the products' results are laid out: transposed once,
    # outside the loop.
    qg = q.reshape(s, t_step, kv_heads, h // kv_heads, d).transpose(
        0, 2, 3, 1, 4
    )

    def block(j, carry):
        m_prev, l_prev, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        keys = k_pool[ids].reshape(s, bkv, kv_heads, d)
        values = v_pool[ids].reshape(s, bkv, kv_heads, d)
        if k_scale is not None:
            ks = k_scale[ids].reshape(s, bkv, kv_heads)
            vs = v_scale[ids].reshape(s, bkv, kv_heads)
            keys = keys.astype(q.dtype) * ks[..., None].astype(q.dtype)
            values = values.astype(q.dtype) * vs[..., None].astype(q.dtype)
        scores = jnp.einsum(
            "bhgqd,bkhd->bhgqk", qg, keys,
            preferred_element_type=jnp.float32,
        ) * scale
        k_abs = j * bkv + jnp.arange(bkv, dtype=jnp.int32)
        visible = k_abs[None, None, :] <= positions[:, :, None]  # [S, T, K]
        scores = jnp.where(visible[:, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(values.dtype), values,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * correction[..., None] + pv

    stats = (s, kv_heads, h // kv_heads, t_step)
    _, l_fin, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full(stats, NEG_INF, jnp.float32),
        jnp.zeros(stats, jnp.float32),
        jnp.zeros(stats + (d,), jnp.float32),
    ))
    out = (acc / l_fin[..., None]).astype(q.dtype)  # [S, Hkv, G, T, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(s, t_step, h, d)


def paged_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    valid_lens: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    kernel="auto",
    pages_per_block: Optional[int] = None,
    mesh=None,
    heads_axis: str = "model",
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Paged attention over ``q`` [S, T_step, H, D] against the page pools:
    the one read of K/V pages, at every ``T_step``.

    A single-token step (the batched decode step) dispatches per ``kernel``
    (see :func:`resolve_kernel`): the Pallas kernel, or with it off the XLA
    reference. A CHUNK (``T_step > 1``: a prefill piece, a speculative
    round's verification) is the blockwise walk, :func:`_paged_walk`,
    whatever ``kernel`` says; ``valid_lens`` [S] tells it how many of a padded
    piece's tokens are the rows' own (``None``: all), so that it walks no
    block for the padding's sake. ``pages_per_block`` defaults to the
    autotune harness' ``paged_decode`` family entry for this shape (the
    kernel's block; the walk's is :func:`walk_block_pages`). ``sm_scale``
    multiplies the scores on every path (``None``: ``D ** -0.5``).

    Under a sharded jit pass ``mesh``: the kernel runs per-shard via
    ``shard_map`` with Q heads and KV heads (and scale heads) split over
    ``heads_axis`` and everything else replicated — the exact placement the
    engine's pool/param shardings already use, so no extra collective. The
    walk is plain XLA, partitioned like any other op of the program."""
    s, t_step, h, d = q.shape
    kv_heads = k_pool.shape[2]
    if h % kv_heads:
        raise ValueError(
            f"query heads {h} not divisible by kv heads {kv_heads}"
        )
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    mode = resolve_kernel(kernel)
    if t_step != 1:
        return _paged_walk(
            q, k_pool, v_pool, block_tables, seq_lens, valid_lens, k_scale,
            v_scale, bp=walk_block_pages(block_tables.shape[1], k_pool.shape[1]),
            # A static argument: None keeps the default scale's one trace.
            **({} if sm_scale is None else {"sm_scale": float(sm_scale)}),
        )
    if mode == "xla":
        return paged_attention_reference(
            q, k_pool, v_pool, block_tables, seq_lens,
            k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale,
        )

    run = functools.partial(
        _paged_flash,
        pages_per_block=kv_block_pages(
            block_tables.shape[1], k_pool, q.dtype, pages_per_block
        ),
        interpret=(mode == "interpret"),
        # A static argument: None keeps the default block's one trace.
        **({} if sm_scale is None else {"sm_scale": float(sm_scale)}),
    )
    q3 = q.reshape(s, h, d)
    bt = block_tables.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)

    tp = 1 if mesh is None else dict(mesh.shape).get(heads_axis, 1)
    if tp <= 1:
        out3 = run(q3, k_pool, v_pool, bt, lens, k_scale, v_scale)
        return out3.reshape(s, 1, h, d)

    if kv_heads % tp or h % tp:
        raise ValueError(
            f"heads (H={h}, Hkv={kv_heads}) not divisible by mesh axis "
            f"{heads_axis!r} (size {tp})"
        )
    args = [q3, k_pool, v_pool, bt, lens]
    specs = [
        P(None, heads_axis, None),
        P(None, None, heads_axis, None),
        P(None, None, heads_axis, None),
        P(None, None),
        P(None),
    ]
    if k_scale is not None:
        args += [k_scale, v_scale]
        specs += [P(None, None, heads_axis), P(None, None, heads_axis)]

    def local(*a):
        ks, vs = (a[5], a[6]) if len(a) == 7 else (None, None)
        return run(a[0], a[1], a[2], a[3], a[4], ks, vs)

    out3 = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=P(None, heads_axis, None),
        check_vma=False,
    )(*args)
    return out3.reshape(s, 1, h, d)


# ------------------------------------------------------------- windowed K/V
#
# A K/V layer with a window (``models/transformer.py``'s ``"attention_window"``
# layers) is served on block tables of its GROUP's own (``serving/kv_cache.py``
# ``WindowTable``): the pages behind a window go back to the group's allocator,
# so what a call is handed is each row's SHORT table, from the page that holds
# its window's first key on: ``window_pages(window, page)`` entries for a decode
# row, :func:`window_group_pages` for a prefill piece of several queries.

KV_WINDOW_KERNEL = "attention._window_paged_decode_step"


def window_first_page(seq_lens, window: int, page: int):
    """The logical page that a short table's first entry stands for: the one
    that holds the first key ``max(pos - window + 1, 0)`` of the window of a
    row's FIRST new token at ``seq_lens``. NumPy or traced; the host stages
    its tables from it (``WindowTable.as_row``) and the layer counts its
    positions from it: one rule."""
    xp = np if isinstance(seq_lens, (np.ndarray, int, np.integer)) else jnp
    return xp.maximum(seq_lens - (window - 1), 0) // page


def window_group_pages(window: int, page: int, tokens: int = 1) -> int:
    """Pages a sequence holds in a window group while ``tokens`` new tokens
    in a row are written and read: from the first one's window's first key
    to the last one's own (:func:`window_pages` at one token: 9 at a window
    of 128 on pages of 16, 41 inside a piece of 512)."""
    return window_pages(window + tokens - 1, page)


def paged_window_attention_reference(
    q, k_pool, v_pool, block_tables, seq_lens, *, window: int,
    sm_scale: Optional[float] = None,
):
    """The XLA gather path over SHORT tables: :func:`paged_attention_reference`
    with key ``j`` of a row's gathered view standing at position
    ``window_first_page * page + j``, and a query at ``t`` kept to the keys
    ``(t - window, t]``. Any ``T_step``: a prefill piece reads its window's
    pages and its own through it."""
    s, t_step, h, d = q.shape
    page, kv_heads = k_pool.shape[1:3]
    kv_len = block_tables.shape[1] * page
    lens = seq_lens.astype(jnp.int32)
    positions = lens[:, None] + jnp.arange(t_step, dtype=jnp.int32)
    keys = k_pool[block_tables].reshape(s, kv_len, kv_heads, d)
    values = v_pool[block_tables].reshape(s, kv_len, kv_heads, d)
    scale = d**-0.5 if sm_scale is None else sm_scale
    k_abs = (window_first_page(lens, window, page) * page)[:, None, None] + (
        jnp.arange(kv_len)[None, None, :]
    )
    visible = (k_abs <= positions[:, :, None]) & (
        k_abs > positions[:, :, None] - window
    )
    group = h // kv_heads
    qg = q.reshape(s, t_step, kv_heads, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys) * scale
    logits = jnp.where(visible[:, None, None], logits, NEG_INF)
    weights = jax.nn.softmax(
        logits.astype(jnp.float32), axis=-1
    ).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, values)
    return out.reshape(s, t_step, h, d)


def paged_window_attention(
    q, k_pool, v_pool, block_tables, seq_lens, *, window: int,
    kernel="auto", pages_per_block: Optional[int] = None,
    sm_scale: Optional[float] = None,
):
    """Paged attention of ``q`` [S, T_step, H, D] over a window group's pools
    through its SHORT tables ``[S, width]`` (entry 0: the page
    :func:`window_first_page` names for ``seq_lens``). As
    :func:`paged_attention`: a single-token step dispatches per ``kernel`` to
    ``_decode_kernel``, named :data:`KV_WINDOW_KERNEL`, told each row's first
    live key beside its position; everything else takes the gather path.
    :func:`kv_block_pages` decides the block over the short table's width (a
    decode row's 9 pages at the published sizes are ONE block)."""
    s, t_step, h, d = q.shape
    page, kv_heads = k_pool.shape[1:3]
    if h % kv_heads:
        raise ValueError(
            f"query heads {h} not divisible by kv heads {kv_heads}"
        )
    mode = resolve_kernel(kernel)
    if mode == "xla" or t_step != 1:
        return paged_window_attention_reference(
            q, k_pool, v_pool, block_tables, seq_lens, window=window,
            sm_scale=sm_scale,
        )
    lens = seq_lens.astype(jnp.int32)
    base = window_first_page(lens, window, page) * page
    out3 = _paged_flash(
        q.reshape(s, h, d), k_pool, v_pool, block_tables.astype(jnp.int32),
        lens - base, None, None, jnp.maximum(lens - (window - 1), 0) - base,
        pages_per_block=kv_block_pages(
            block_tables.shape[1], k_pool, q.dtype, pages_per_block,
            short=True,
        ),
        interpret=(mode == "interpret"),
        **({} if sm_scale is None else {"sm_scale": float(sm_scale)}),
    )
    return out3.reshape(s, 1, h, d)


# --------------------------------------------------------------- latent pool
#
# The second kind of page (``models/mla.py``): ONE pool a layer of ``[num_pages,
# page, W]``, a token's ``[c | k_pe]`` with no head axis. Every query head
# reads the same cached vector, and its first ``v_width`` numbers are also the
# value: the block is copied once and used twice. And every ROW that holds the
# same physical pages (a document the prefix trie handed to several askers)
# reads the same vectors: rows whose tables begin alike are served as a group,
# the pages they share copied once for all of them.

#: Rows one shared walk serves: its products' M is this many rows' heads (4 x
#: 16 = 64 of the MXU's 128 rows at the published sizes, beside 5.2 MB of
#: page buffers in VMEM). A wider group is split.
GROUP_ROWS = 4

#: Page buffers of the latent kernel: the one a block is computed from and
#: the ones the blocks after it are copied into meanwhile.
LATENT_BUFFERS = 3


def block_widths(npb: int) -> tuple:
    """The sizes, in pages, at which the latent kernel copies and computes a
    block: the whole block of ``npb`` and its half, quarter and eighth. Only
    a walk's last block holds fewer than ``npb`` pages; it takes the smallest
    of these that holds what is left, so a tail of a few pages does not pay
    for a whole block."""
    return tuple(sorted({max(1, npb >> k) for k in range(4)}))


def _fitting(n, widths: tuple):
    """The smallest of ``widths`` that holds ``n`` pages (``n`` at most the
    largest), for an array or a traced scalar alike."""
    where = np.where if isinstance(n, (np.ndarray, np.generic)) else jnp.where
    out = widths[-1]
    for width in widths[-2::-1]:
        out = where(n <= width, width, out)
    return out


def pages_walked(n_pages, npb: int):
    """Pages the latent kernel copies for a walk over ``n_pages`` of a table:
    whole blocks of ``npb``, and what is left at the width that holds it
    (:func:`block_widths`)."""
    rest = n_pages % npb
    return n_pages - rest + (rest > 0) * _fitting(rest, block_widths(npb))


def shared_prefix_groups(
    tables, positions, page: int, min_pages: int, max_rows: int = GROUP_ROWS
):
    """Which decode rows the latent kernel serves together: ``(leader,
    shared)``, both ``[S]`` int32. Row ``r`` belongs to the group of row
    ``leader[r]`` (itself: a row served alone), and the group's rows hold the
    same physical page at each of their tables' first ``shared[r]`` logical
    indices: the kernel copies those once, at the leader's turn, and each
    member walks only what follows them. Decided from the staged ``tables [S,
    pages_per_seq]`` and ``positions [S]`` alone, NumPy or traced:

    * rows whose tables start at the same physical page are one class (a row
      out of the dispatch, whose table starts at the null page, is in none),
      cut in slot order into groups of at most ``max_rows``; a group's leader
      is its first row, so it runs before its members;
    * a member shares the pages on which its table agrees with the leader's,
      up to the first index where they part (a last page copied on write has
      equal contents under another number: not shared), and no page at or past
      the one that holds its ``pos``: every shared key lies below every
      member's position and the shared walk needs no causal mask. The group
      shares what all its members do;
    * a group of one, or one that shares fewer than ``min_pages`` (a block:
      not worth a walk of its own), stays rows served alone: ``leader[r] ==
      r``, ``shared[r] == 0``, the walk of a row in a dispatch with no
      sharing.

    The kernel's operand and the tracer's count of what was fetched
    (:func:`latent_tokens_fetched`) both come from here."""
    xp = np if isinstance(tables, np.ndarray) else jnp
    positions = xp.asarray(positions)
    slots, width = tables.shape
    rows = xp.arange(slots)
    live = tables[:, 0] != NULL_PAGE
    same = (
        (tables[:, :1] == tables[None, :, 0]) & live[:, None] & live[None, :]
    )
    rank = (same & (rows[None, :] < rows[:, None])).sum(axis=1)
    first = rank - rank % max_rows
    leader = xp.where(
        live, xp.argmax(same & (rank[None, :] == first[:, None]), axis=1),
        rows,
    )
    agree = tables == tables[leader]
    common = xp.where(agree.all(axis=1), width, xp.argmin(agree, axis=1))
    common = xp.minimum(common, xp.minimum(positions // page, width - 1))
    together = leader[:, None] == leader[None, :]
    shared = xp.where(together, common[None, :], width).min(axis=1)
    grouped = (together.sum(axis=1) > 1) & (shared >= min_pages)
    return (
        xp.where(grouped, leader, rows).astype(xp.int32),
        xp.where(grouped, shared, 0).astype(xp.int32),
    )


def latent_tokens_fetched(
    positions, leader, shared, page: int, npb: int, pages_per_seq: int
) -> int:
    """Key positions the latent kernel copies out of the pool for a decode
    dispatch grouped as :func:`shared_prefix_groups` says (NumPy): a group's
    shared pages once, at its leader's turn, and every row's own pages from
    there to the one that holds its ``pos``, each walk in whole blocks and a
    last one of its own width (:func:`pages_walked`)."""
    last = np.minimum(positions // page, pages_per_seq - 1)
    leads = (leader == np.arange(len(leader))) & (shared > 0)
    pages = pages_walked(last + 1 - shared, npb).sum() + pages_walked(
        shared[leads], npb
    ).sum()
    return int(pages) * page


def is_run(entries):
    """THE rule of what a RUN is, for the two kernels that copy pages by runs
    (:func:`_latent_decode_kernel`, :func:`_index_scores_kernel`: their
    operands :func:`latent_runs` and :func:`index_runs`) and for the host that
    counts their copies (:func:`latent_copies_started`,
    :func:`index_copies_started`). ``entries`` are the table entries of one
    turn of a copy loop, first to last (arrays of one shape: a turn an
    element). They are a run where they name NEIGHBOURING pages of the pool,
    ``entries[i] == entries[0] + i``: pages that stand side by side in HBM,
    which ONE copy of ``len(entries)`` pages moves. A turn of one page is no
    run: it is one copy as it is."""
    if len(entries) < 2:
        return False
    run = entries[1] == entries[0] + 1
    for i in range(2, len(entries)):
        run = run & (entries[i] == entries[0] + i)
    return run


def _turn_runs(entries, pages, turn: int):
    """Which turns of walks are runs: ``entries [S, turns * turn]`` are each
    row's table entries from its walk's first page on, ``pages [S]`` the
    pages its walk has. Turn ``t`` holds the walk's pages ``[t * turn, (t + 1)
    * turn)``; it is a run where all of them are the walk's own (a turn that
    reaches past the walk's last page repeats that page in the kernel: no
    run) and :func:`is_run`. ``[S, turns]`` bool, NumPy or traced."""
    xp = np if isinstance(entries, np.ndarray) else jnp
    slots = entries.shape[0]
    turns = entries.reshape(slots, -1, turn)
    whole = (xp.arange(turns.shape[1]) + 1) * turn <= pages[:, None]
    return is_run([turns[..., i] for i in range(turn)]) & whole


def _leading(runs):
    """How many of ``runs [..., turns]`` hold from the first on, up to the
    first that does not: int32, NumPy or traced."""
    xp = np if isinstance(runs, np.ndarray) else jnp
    return xp.cumprod(runs.astype(xp.int32), axis=-1).sum(axis=-1)


def _tables_from(tables, first, width: int):
    """Each row's table from index ``first[r]`` on, ``width`` entries (the
    null page past the table's end), NumPy or traced."""
    if isinstance(tables, np.ndarray):
        padded = np.pad(tables, ((0, 0), (0, width)))
        return padded[
            np.arange(len(tables))[:, None], first[:, None] + np.arange(width)
        ]
    padded = jnp.pad(tables, ((0, 0), (0, width)))
    # A slice a row, not a gather an entry.
    return jax.vmap(
        lambda row, at: jax.lax.dynamic_slice(row, (at,), (width,))
    )(padded, first)


def latent_turns(pages_per_seq: int, npb: int) -> tuple:
    """``(pages a turn, turns)`` of the latent kernel's copy loop at blocks
    of ``npb`` pages: a block is copied its widths' common divisor at a time,
    and the whole blocks that a table of ``pages_per_seq`` pages meets take
    this many turns."""
    chunk = math.gcd(*block_widths(npb))
    return chunk, -(-pages_per_seq // npb) * (npb // chunk)


def latent_runs(tables, positions, leader, shared, page: int, npb: int):
    """How many LEADING turns of each block of the latent kernel's walks go
    as ONE copy each, for a decode dispatch grouped as
    :func:`shared_prefix_groups` says: ``[2 S, blocks]`` int32, row ``r`` the
    blocks of ``r``'s SHARED walk (its table's first ``shared[r]`` pages; all
    0 but at a group's leader) and row ``S + r`` those of its OWN walk (from
    ``shared[r]`` to the page that holds its ``pos``). A block's turns are
    counted from its first up to the first that is no run
    (:func:`_turn_runs`): a resident document's blocks are runs from their
    first page on, up to the document's last pages, and the kernel copies a
    block as two loops, its leading runs and then the rest a copy a page, with
    no test a turn. Worked out once, from the tables alone, NumPy or traced:
    the kernel's operand and the host's count of its copies
    (:func:`latent_copies_started`) both come from here."""
    xp = np if isinstance(tables, np.ndarray) else jnp
    slots, width = tables.shape
    chunk, turns = latent_turns(width, npb)
    last = xp.minimum(positions // page, width - 1)
    leads = (leader == xp.arange(slots)) & (shared > 0)
    whole_blocks = xp.pad(tables, ((0, 0), (0, turns * chunk - width)))
    runs = xp.concatenate([
        _turn_runs(whole_blocks, xp.where(leads, shared, 0), chunk),
        _turn_runs(
            _tables_from(tables, shared, turns * chunk), last + 1 - shared,
            chunk,
        ),
    ])
    return _leading(runs.reshape(2 * slots, -1, npb // chunk))


def latent_copies_started(tables, positions, leader, shared, page: int,
                          npb: int):
    """``(copies, pages in runs)``: the copy descriptors the latent kernel
    starts for a decode dispatch's live rows (``tables [S, pages_per_seq]``,
    grouped as :func:`shared_prefix_groups` says; NumPy) and the pages among
    them that went as part of a run. The kernel's walks (a group's shared
    pages once, at its leader; every row's own) in the kernel's turns (a
    block's pages ``gcd(block_widths)`` at a time, a walk's last block at the
    width that holds it): a turn that :func:`latent_runs` counts is one
    copy, any other a copy a page."""
    slots, width = tables.shape
    chunk, _ = latent_turns(width, npb)
    last = np.minimum(positions // page, width - 1)
    leads = (leader == np.arange(slots)) & (shared > 0)
    pages = np.concatenate([last + 1 - shared, shared[leads]])
    turns = int(pages_walked(pages, npb).sum()) // chunk
    runs = int(latent_runs(tables, positions, leader, shared, page, npb).sum())
    return runs + (turns - runs) * chunk, runs * chunk


def _latent_decode_kernel(
    bt_ref, lens_ref, lead_ref, shared_ref, runs_ref, *refs, npb, v_width,
    sm_scale, windowed=False,
):
    """One slot (grid step) of the latent flash-decode kernel: the frame of
    :func:`_decode_kernel` (walk the row's own blocks and only those, the
    pool's pages copied into VMEM buffers ahead of the block that is being
    computed, online softmax in fp32 scratch) over ONE pool, for rows grouped
    as :func:`shared_prefix_groups` says (``lead_ref``, ``shared_ref``).

    A step is one or two WALKS over a stretch of the row's table, one block
    body for both. The row's own walk: from its ``shared`` pages on to the
    page that holds ``pos``, under the causal mask, ``q_ref[b]``'s ``[H, W]``
    absorbed query (``[q~ | q_pe]``) against a block's ``[keys, W]`` latent (a
    matmul whose M is ``H``), whose first ``v_width`` columns, already in
    VMEM, are the value. A row served alone shares nothing and starts from an
    empty softmax state: PR 35's row. Before it, at a group's LEADER (its
    first row) only, the shared walk: the group's ``shared`` leading pages,
    all visible to every member, copied once and scored against all the
    members' heads stacked (M = two rows' heads, or ``GROUP_ROWS``' where the
    group is wider); each member's running max, denominator and accumulator
    are kept in ``m_st / l_st / acc_st`` (a place a row), and the member's
    own walk, at its own turn, carries on from them: the same online softmax
    over the same keys, shared pages first.

    The copies run ahead of the arithmetic as a STREAM of blocks in the order
    they are computed (live rows in slot order; at a leader the shared walk,
    then the own one), ``stream`` (SMEM) holding where the copying stands:
    before a block is computed, the blocks after it are started into the
    buffers that are free (``buf``'s first size, less the one in use), so a
    short block's few copies do not leave the copy engine idle under the
    long block before it. Only a walk's last block is short: it is copied
    and computed at the narrowest of :func:`block_widths` that holds it
    (pages past its live ones repeat the last live one and die in the mask,
    so nothing unowned or stale is ever read), and only it needs a mask. The
    products take the pool's type as it is stored (bf16 on the chip: the
    MXU's own) and accumulate in float32.

    A block is copied a TURN of ``chunk`` pages at a time (the widths'
    common divisor: 16 of a block of 128). A turn whose pages are a RUN,
    neighbouring pages of the pool, goes as ONE copy of the stretch:
    ``runs_ref`` (:func:`latent_runs`) says how many of a block's LEADING
    turns are, and the block is two loops, those and then the rest a copy a
    page (a short block's clamped tail among them), with no test a turn.
    Either way the same bytes land in the same places, and the wait, which
    counts bytes on the buffer's semaphore and not copies, is one a turn.

    ``windowed`` (a sliding layer's call): a fifth scalar operand ``lo_ref``
    gives each row the first key position it sees, and every block is masked
    on both sides (the tables such a call is handed begin at the window's
    first live page, so that position lies in the row's first page)."""
    lo_ref, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    (q_ref, pool_hbm, o_ref, buf, sems, stream, members, qg_scr, mg_scr,
     lg_scr, accg_scr, m_st, l_st, acc_st) = refs
    b = pl.program_id(0)
    slots, pages_per_seq = bt_ref.shape
    h, w = q_ref.shape[1:]
    n_buf, _, page, _ = buf.shape
    group_rows = members.shape[0]
    widths = block_widths(npb)
    chunk = math.gcd(*widths)  # copies a turn of the rolled copy loop
    ROW, OWN, BLOCK, STARTED, DONE = range(5)  # ``stream``'s places

    def is_live(row):
        return bt_ref[row, 0] != NULL_PAGE

    def leads(row):
        return jnp.logical_and(lead_ref[row] == row, shared_ref[row] > 0)

    def own_pages(row):
        last = jnp.minimum(lens_ref[row] // page, pages_per_seq - 1)
        return last + 1 - shared_ref[row]

    def next_live(row):
        """The first live row at or after ``row``; ``slots`` if none."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < slots,
                jnp.logical_not(is_live(jnp.minimum(r, slots - 1))),
            ),
            lambda r: r + 1, row,
        )

    def opens_alone(row):
        """1 where ``row``'s step opens with its own walk, 0 where with a
        shared one (``row`` may be ``slots``: nothing follows)."""
        return 1 - leads(jnp.minimum(row, slots - 1)).astype(jnp.int32)

    def page_copy(phys, slot, n):
        return pltpu.make_async_copy(
            pool_hbm.at[phys], buf.at[slot, n], sems.at[slot]
        )

    def run_copy(first, slot, n):
        """ONE copy of the ``chunk`` neighbouring pages of the pool from
        ``first`` on, into the buffer's pages from ``n`` on: the bytes of
        ``chunk`` page copies in the same places."""
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(first, chunk)],
            buf.at[slot, pl.ds(n, chunk)], sems.at[slot],
        )

    # A pool of fewer pages than a turn holds no run (nor a slice to wait by).
    by_runs = 1 < chunk <= pool_hbm.shape[0]

    def turns(left):
        """Turns of the copy loop for a block of a walk with ``left`` pages
        to go: start and wait have to agree on it."""
        return _fitting(jnp.minimum(left, npb), widths) // chunk

    def start_next():
        """Start the copies of the stream's next block into the buffer that
        is its turn, and move the stream on. A page past the block's live
        ones clamps to the last of them, as in ``_decode_kernel``: a page
        the row does not own is never fetched."""
        row, own, j = stream[ROW], stream[OWN], stream[BLOCK]
        ahead = shared_ref[row]
        p0 = jnp.where(own == 1, ahead, 0) + j * npb
        left = jnp.where(own == 1, own_pages(row), ahead) - j * npb
        live = jnp.minimum(left, npb)
        slot = stream[STARTED] % n_buf

        def a_run(c, carry):
            run_copy(bt_ref[row, p0 + c * chunk], slot, c * chunk).start()
            return carry

        def turn(c, carry):
            for i in range(chunk):
                n = c * chunk + i
                phys = bt_ref[row, p0 + jnp.minimum(n, live - 1)]
                page_copy(phys, slot, n).start()
            return carry

        # The block's leading turns that are runs go as one copy each, the
        # rest a copy a page: two loops, no test a turn.
        runs = runs_ref[own * slots + row, j] if by_runs else 0
        jax.lax.fori_loop(0, runs, a_run, 0)
        jax.lax.fori_loop(runs, turns(left), turn, 0)
        stream[STARTED] = stream[STARTED] + 1

        @pl.when(left > npb)
        def _same_walk():
            stream[BLOCK] = j + 1

        @pl.when(jnp.logical_and(left <= npb, own == 0))
        def _own_walk():
            stream[OWN] = 1
            stream[BLOCK] = 0

        @pl.when(jnp.logical_and(left <= npb, own == 1))
        def _next_row():
            after = next_live(row + 1)
            stream[ROW] = after
            stream[OWN] = opens_alone(after)
            stream[BLOCK] = 0

    def wait(left, slot):
        # A wait counts BYTES on the buffer's semaphore, whatever copies
        # brought them: one wait a turn, for a run's copy or a copy a page.
        def turn(c, carry):
            if by_runs:
                run_copy(0, slot, c * chunk).wait()
            else:
                for i in range(chunk):
                    page_copy(0, slot, c * chunk + i).wait()
            return carry

        jax.lax.fori_loop(0, turns(left), turn, 0)

    def attend(slot, pages, q, key0, limit, m_ref, l_ref, acc_ref):
        """The block body: ``q [M, W]`` against the first ``pages`` pages of
        buffer ``slot``, whose first key stands at position ``key0``; keys
        past ``limit`` are masked (``None``: all are visible)."""
        k = buf[slot, 0:pages].reshape(pages * page, w)  # as stored
        s_blk = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [M, keys]
        if limit is not None:
            kpos = key0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, pages * page), 1
            )
            # Every walked block's first key is visible, so the running max
            # stays finite and no exp(NEG_INF - NEG_INF) row can arise.
            seen = kpos <= limit
            if windowed:
                # A window's first block holds the window's first key: it too
                # has a visible key.
                seen = jnp.logical_and(seen, kpos >= lo_ref[b])
            s_blk = jnp.where(seen, s_blk, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        # The value is the key's own first columns: no second copy.
        pv = jax.lax.dot_general(
            p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [M, v_width]
        acc_ref[:] = acc_ref[:] * correction + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def walk(p0, n_pages, q, limit, m_ref, l_ref, acc_ref):
        """Carry the softmax state in ``m_ref / l_ref / acc_ref`` over pages
        ``[p0, p0 + n_pages)`` of this row's table: the stream's next
        ``cdiv(n_pages, npb)`` blocks."""

        def block(j, carry):
            for _ in range(n_buf - 1):

                @pl.when(jnp.logical_and(
                    stream[STARTED] < stream[DONE] + n_buf,
                    stream[ROW] < slots))
                def _run_ahead():
                    start_next()

            slot = stream[DONE] % n_buf
            left = n_pages - j * npb
            wait(left, slot)
            key0 = (p0 + j * npb) * page

            @pl.when(left > npb)
            def _whole():
                attend(
                    slot, npb, q, key0, limit if windowed else None, m_ref,
                    l_ref, acc_ref,
                )

            fits = _fitting(left, widths)
            for pages in widths:

                @pl.when(jnp.logical_and(left <= npb, fits == pages))
                def _last(pages=pages):
                    attend(slot, pages, q, key0, limit, m_ref, l_ref, acc_ref)

            stream[DONE] = stream[DONE] + 1
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n_pages, npb), block, 0)

    def empty(m_ref, l_ref, acc_ref):
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(b == 0)
    def _open_the_stream():
        first = next_live(0)
        stream[ROW] = first
        stream[OWN] = opens_alone(first)
        stream[BLOCK] = 0
        stream[STARTED] = 0
        stream[DONE] = 0

        @pl.when(first < slots)
        def _first_block():
            start_next()

    @pl.when(jnp.logical_not(is_live(b)))
    def _absent():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(is_live(b))
    def _row():
        shared = shared_ref[b]

        @pl.when(leads(b))
        def _shared_walk():
            # The group's rows in slot order: this one, then the rows after
            # it that name it.
            def find(r, count):
                mine = lead_ref[r] == b

                @pl.when(mine)
                def _member():
                    members[count] = r

                return count + mine.astype(jnp.int32)

            count = jax.lax.fori_loop(b, slots, find, 0)
            for k in range(group_rows):
                # A place no member takes computes on this row's query again;
                # nobody reads what it gives.
                r = jnp.where(k < count, members[k], b)
                qg_scr[k * h : (k + 1) * h] = q_ref[r].astype(qg_scr.dtype)
            # A pair rides at M = 2 H; only a wider group pays for more.
            sizes = sorted({min(2, group_rows), group_rows})
            for lo, rows in zip([0] + sizes, sizes):
                m = rows * h
                state = (mg_scr.at[0:m], lg_scr.at[0:m], accg_scr.at[0:m])

                @pl.when(jnp.logical_and(lo < count, count <= rows))
                def _stacked(m=m, state=state):
                    empty(*state)
                    walk(0, shared, qg_scr[0:m], shared * page - 1, *state)

            for k in range(group_rows):

                @pl.when(k < count)
                def _hand_over(k=k):
                    r = members[k]
                    m_st[r] = mg_scr[k * h : (k + 1) * h]
                    l_st[r] = lg_scr[k * h : (k + 1) * h]
                    acc_st[r] = accg_scr[k * h : (k + 1) * h]

        @pl.when(shared == 0)
        def _alone():
            empty(m_st.at[b], l_st.at[b], acc_st.at[b])

        walk(
            shared, own_pages(b), q_ref[b].astype(buf.dtype), lens_ref[b],
            m_st.at[b], l_st.at[b], acc_st.at[b],
        )
        o_ref[0] = (acc_st[b] / l_st[b][:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("pages_per_block", "interpret", "sm_scale", "v_width"),
)
def _latent_flash(
    q3, pool, block_tables, seq_lens, leader, shared, runs, first_key=None,
    *, pages_per_block, interpret, sm_scale, v_width,
):
    """The latent kernel's ``pallas_call`` for ``q3`` [S, H, W]: jitted and
    named for :func:`_paged_flash`'s reasons (one trace for a model's layers;
    a device trace shows ``attention._latent_decode_step`` whoever calls
    it). The pool stays in HBM and a page is copied as the ``[page, W]`` rows
    it is stored as; the tables, the lengths, the rows' grouping and the
    turns that are runs (:func:`latent_runs`) ride as scalar prefetch; every
    row's query is held in VMEM for the whole call (a leader needs its
    members'). ``first_key [S]`` makes it the WINDOWED call
    (:func:`_latent_decode_kernel`), which the device trace shows under a name
    of its own, ``attention._window_latent_decode_step``."""
    s, h, w = q3.shape
    page = pool.shape[1]
    npb = int(pages_per_block)
    m_rows = GROUP_ROWS * h
    windowed = first_key is not None
    scalars = [
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        leader.astype(jnp.int32), shared.astype(jnp.int32),
        runs.astype(jnp.int32),
    ]
    if windowed:
        scalars.append(first_key.astype(jnp.int32))

    def row_spec(shape):
        return pl.BlockSpec(
            shape, lambda b, *_: (b,) + (0,) * (len(shape) - 1),
            memory_space=pltpu.VMEM,
        )

    scratch = [
        pltpu.VMEM((LATENT_BUFFERS, npb, page, w), pool.dtype),
        pltpu.SemaphoreType.DMA((LATENT_BUFFERS,)),
        pltpu.SMEM((5,), jnp.int32),  # where the stream of copies stands
        pltpu.SMEM((GROUP_ROWS,), jnp.int32),  # a group's rows
        pltpu.VMEM((m_rows, w), pool.dtype),  # a group's queries
        pltpu.VMEM((m_rows, 128), jnp.float32),  # its running max m
        pltpu.VMEM((m_rows, 128), jnp.float32),  # its denominator l
        pltpu.VMEM((m_rows, v_width), jnp.float32),  # its accumulator
        pltpu.VMEM((s, h, 128), jnp.float32),  # every row's m
        pltpu.VMEM((s, h, 128), jnp.float32),  # every row's l
        pltpu.VMEM((s, h, v_width), jnp.float32),  # every row's accumulator
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(s,),
        in_specs=[
            pl.BlockSpec(
                (s, h, w), lambda b, *_: (0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=row_spec((1, h, v_width)),
        scratch_shapes=scratch,
    )
    # What the kernel holds in VMEM: the page buffers and a group's queries,
    # the softmax states, the rows' queries (twice: the pipeline's two
    # buffers) and a block's scores and weights.
    item = jnp.dtype(pool.dtype).itemsize
    held = (
        (LATENT_BUFFERS * npb * page + m_rows) * w * item
        + 4 * (m_rows + s * h) * (256 + v_width)
        + 2 * s * h * w * jnp.dtype(q3.dtype).itemsize
        + 3 * m_rows * npb * page * 4
    )
    return pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, npb=npb, v_width=v_width,
            sm_scale=sm_scale, **({"windowed": True} if windowed else {}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, v_width), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, int(1.5 * held)),
        ),
        interpret=interpret,
        name=WINDOW_KERNEL if windowed else "attention._latent_decode_step",
    )(*scalars, q3, pool)


def paged_latent_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    v_width: int,
    kernel="auto",
    pages_per_block: Optional[int] = None,
    sm_scale: Optional[float] = None,
    row_groups=None,
    window: int = 0,
) -> jnp.ndarray:
    """Paged attention of ``q`` [S, T_step, H, W] over ONE latent pool
    ``[num_pages, page, W]``: every head's key at a position is the pool's
    vector there and its value that vector's first ``v_width`` numbers.
    Returns ``[S, T_step, H, v_width]``. As :func:`paged_attention`: a
    single-token step dispatches per ``kernel``, everything else takes
    :func:`paged_attention_reference`'s latent case; ``sm_scale`` ``None`` is
    ``W ** -0.5``; :func:`latent_block_pages` looks the block up under the
    pool's width. ``row_groups`` is :func:`shared_prefix_groups`' ``(leader,
    shared)`` for these tables and lengths where the caller has worked it out
    (a decode program does, once for all its layers:
    ``serving/decode_reads.py``'s ``operands``);
    ``None`` works it out here; a third member is :func:`latent_runs` of the
    same dispatch, for a caller that has worked that out once too. Only the
    kernel reads either.

    ``window`` keeps a query at ``t`` to the keys ``(t - window, t]``. The
    kernel is then handed each row's table FROM the window's first live page
    (:func:`window_tables`: ``window_pages`` entries, whatever the table's
    width) and walks those pages alone, every row by itself."""
    s, t_step, h, w = q.shape
    if pool.ndim != 3 or pool.shape[2] != w:
        raise ValueError(
            f"a latent pool is [num_pages, page, {w}], got {pool.shape}"
        )
    if not 0 < v_width <= w:
        raise ValueError(f"v_width {v_width} of a latent of width {w}")
    mode = resolve_kernel(kernel)
    if mode == "xla" or t_step != 1:
        return paged_attention_reference(
            q, pool, None, block_tables, seq_lens, sm_scale=sm_scale,
            v_width=v_width, **({"window": window} if window else {}),
        )
    first_key = ()
    if window:
        block_tables, seq_lens, lo = window_tables(
            block_tables, seq_lens, pool.shape[1], window
        )
        rows = jnp.arange(s, dtype=jnp.int32)
        row_groups, first_key = (rows, jnp.zeros_like(rows)), (lo,)
    npb = latent_block_pages(block_tables.shape[1], pool, pages_per_block)
    if row_groups is None:
        row_groups = shared_prefix_groups(
            block_tables, seq_lens, pool.shape[1], npb
        )
    if len(row_groups) < 3:
        row_groups = (*row_groups, latent_runs(
            block_tables, seq_lens, *row_groups, pool.shape[1], npb
        ))
    out3 = _latent_flash(
        q.reshape(s, h, w), pool, block_tables, seq_lens, *row_groups,
        *first_key, pages_per_block=npb, interpret=(mode == "interpret"),
        sm_scale=float(w**-0.5 if sm_scale is None else sm_scale),
        v_width=int(v_width),
    )
    return out3.reshape(s, 1, h, v_width)


# ------------------------------------------------------------ windowed latent
#
# A sliding layer's decode reads the pages that meet its window and no other:
# the kernel above, handed each row's table from the window's first live page
# on (the block loop's "first live block").

WINDOW_KERNEL = "attention._window_latent_decode_step"


def window_pages(window: int, page: int) -> int:
    """Pages that a window of ``window`` key positions (the query's own among
    them) can meet: its first key may stand last in its page."""
    return 1 + -(-(window - 1) // page)


def window_tokens_read(positions, window: int, page: int):
    """Key positions the windowed kernel copies for decode rows at
    ``positions`` (NumPy): the whole pages from the one that holds the
    window's first key to the one that holds ``pos``."""
    first = np.maximum(positions - (window - 1), 0) // page
    return (positions // page + 1 - first) * page


def window_tables(block_tables, seq_lens, page: int, window: int):
    """``(tables, lens, first_key)`` of a windowed decode call: row ``r``'s
    table from the page that holds its window's first key ``max(pos - window
    + 1, 0)`` on, ``window_pages`` entries (past the table's end: its last
    entry again, which lies past ``pos`` there and is never copied), and its
    position and that first key counted from that page's first token. A row
    out of the dispatch keeps a table that starts at the null page. NumPy or
    traced, as :func:`shared_prefix_groups` is (the host counts the windowed
    call's copies from the same tables)."""
    xp = np if isinstance(block_tables, np.ndarray) else jnp
    lens = seq_lens.astype(xp.int32)
    first = xp.maximum(lens - (window - 1), 0)
    page0 = first // page
    index = xp.minimum(
        page0[:, None]
        + xp.arange(window_pages(window, page), dtype=xp.int32),
        block_tables.shape[1] - 1,
    )
    tables = xp.take_along_axis(block_tables.astype(xp.int32), index, axis=1)
    return tables, lens - page0 * page, first - page0 * page


# ---------------------------------------------------- learned sparse attention
#
# A full layer of a model with an INDEXER (``models/mla.py``) keeps a second
# pool a layer, ``[num_pages, page, index width]``: one index key a token. A
# decode row scores every cached token with it (:func:`paged_index_scores`),
# keeps the ``k`` best (:func:`top_k_mask`, :func:`selected_positions`: exact)
# and attends over those tokens' latents alone
# (:func:`sparse_latent_attention`).

INDEX_KERNEL = "attention._index_scores"
SPARSE_KERNEL = "attention._sparse_latent_decode_step"
POSITIONS_KERNEL = "attention._selected_positions"
#: Pages the index kernel copies and scores at a time (512 keys of 128 at 16
#: a page: a 128 KB block in bf16).
INDEX_BLOCK_PAGES = 32


def index_scores_reference(q, w, pool, block_tables, seq_lens):
    """:func:`paged_index_scores` on the gather path: every row's index keys
    through its table, ``sum_h w_h relu(q_h . k)``, ``-inf`` past ``pos``."""
    s = q.shape[0]
    keys = pool[block_tables].reshape(s, -1, pool.shape[-1])
    logits = jnp.einsum(
        "shd,skd->shk", q, keys, preferred_element_type=jnp.float32
    )
    scores = jnp.einsum(
        "shk,sh->sk", jax.nn.relu(logits), w.astype(jnp.float32)
    )
    seen = jnp.arange(keys.shape[1])[None, :] <= seq_lens[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def index_block_pages(pages_per_seq: int) -> int:
    """Pages the index kernel copies and scores at a time for a table of
    ``pages_per_seq``: :data:`INDEX_BLOCK_PAGES`, or the whole of a narrower
    table."""
    return min(INDEX_BLOCK_PAGES, pages_per_seq)


def index_tokens_fetched(
    positions, leader, shared, page: int, npb: int, pages_per_seq: int
) -> int:
    """Index keys the index kernel copies out of the pool for a decode
    dispatch grouped as :func:`shared_prefix_groups` says (NumPy): every walk
    is of whole blocks of ``npb`` pages, a group's shared whole blocks are
    walked once, at its leader's turn (what is left of ``shared`` under a
    whole block is each member's own), and every row walks its own blocks
    from there to the one that holds its ``pos``."""
    last = np.minimum(positions // page, pages_per_seq - 1)
    ahead = np.where(leader == np.arange(len(leader)), 0, shared // npb)
    return int((last // npb + 1 - ahead).sum()) * npb * page


def index_rows_grouped(shared, npb: int) -> int:
    """Rows of a dispatch grouped as :func:`shared_prefix_groups` says that
    the index kernel serves in a group: the ones whose group shares a whole
    block of ``npb`` pages (NumPy)."""
    return int((shared >= npb).sum())


def index_runs(tables, positions, page: int, npb: int):
    """How many LEADING blocks of each row's table the index kernel copies
    as ONE copy each: ``[S]`` int32, the blocks of ``npb`` pages (the
    kernel's turn) from the table's first up to the first that is no run or
    does not lie wholly at or below the page that holds the row's ``pos``
    (:func:`_turn_runs`). A resident document's blocks are runs from its
    first page on, so the kernel walks a row's blocks as two loops, those
    below this count and then the rest a copy a page, with no test a block.
    NumPy or traced: the kernel's operand and the host's count
    (:func:`index_copies_started`), as :func:`latent_runs` is."""
    xp = np if isinstance(tables, np.ndarray) else jnp
    width = tables.shape[1]
    last = xp.minimum(positions // page, width - 1)
    padded = xp.pad(tables, ((0, 0), (0, -width % npb)))
    return _leading(_turn_runs(padded, last + 1, npb))


def index_copies_started(tables, positions, leader, shared, page: int,
                         npb: int):
    """``(copies, pages in runs)``: the copy descriptors the index kernel
    starts for a decode dispatch's live rows and the pages among them that
    went as part of a run, as :func:`latent_copies_started` counts the latent
    kernel's: the blocks :func:`index_tokens_fetched` counts (NumPy), one
    copy a block below the row's :func:`index_runs`, else a copy a page."""
    end = np.minimum(
        positions // (npb * page) + 1, -(-tables.shape[1] // npb)
    )
    first = np.minimum(
        np.where(leader == np.arange(len(tables)), 0, shared // npb), end - 1
    )
    known = np.clip(index_runs(tables, positions, page, npb), first, end)
    runs = int((known - first).sum())
    return runs + (int((end - first).sum()) - runs) * npb, runs * npb


def _index_scores_kernel(
    bt_ref, lens_ref, lead_ref, shared_ref, runs_ref, q_ref, w_ref, pool_hbm,
    o_ref, buf, sems, first_slot, members, qg_scr, wg_scr, *, npb,
):
    """One slot (grid step): the frame of :func:`_decode_kernel` over the
    index-key pool, for rows grouped as :func:`shared_prefix_groups` says
    (``lead_ref``, ``shared_ref``), as :func:`_latent_decode_kernel`'s are.

    A step is ONE walk over blocks of ``npb`` pages of the row's table, up to
    the block that holds ``pos``: a block's pages are copied into one of two
    buffers (the next block's copies, or the next live row's first block's,
    started before this one is scored; ONE copy where the block is among
    the row's LEADING blocks whose pages are runs, neighbouring pages of the
    pool, of which ``runs_ref`` holds the count (:func:`index_runs`), else a
    copy a page, and one wait either way: a wait counts bytes, not copies;
    a walk is two loops, its blocks below that count and the rest, with no
    test a block), its ``[keys, D]`` meet index queries
    on the MXU, and ReLU, the head weights and the sum over a row's heads
    leave ONE float32 a key and row, written to that row's ``[blocks, block
    keys]`` of the result, which stays in VMEM for the whole call. Keys past
    ``pos``, and whole blocks past it, read ``-inf``; a row out of the
    dispatch reads ``-inf`` everywhere.

    A row served alone (``shared`` 0, or under a whole block) walks from its
    table's first block and scores every block against its own ``[H, D]``
    queries. A group's LEADER (its first row) walks from the first block
    too, but scores the group's shared whole blocks, ``shared // npb`` of
    them, against the members' queries STACKED (``[rows x H, D]``: a pair's
    at M = 2 H, a wider group's at ``GROUP_ROWS`` x H, a place no member
    takes being the leader's again), each member's part weighted and summed
    over its own heads and written to that member's row: the same products,
    ReLU, weights and sums a row alone makes, so the same bits. Every shared key lies below every member's position: no mask.
    A member then walks from the first block after the shared ones. Every
    row's queries and weights are held for the whole call (a leader needs
    its members')."""
    b = pl.program_id(0)
    slots, pages_per_seq = bt_ref.shape
    h = q_ref.shape[1]
    page, d = buf.shape[2:]
    bkv = npb * page
    n_blocks_max = o_ref.shape[1]
    group_rows = members.shape[0]

    def is_live(row):
        return bt_ref[row, 0] != NULL_PAGE

    def walk_of(row):
        """``(first, end)`` blocks of ``row``'s step: a member starts after
        its group's shared blocks, which its leader has scored."""
        end = jnp.minimum(lens_ref[row] // bkv + 1, n_blocks_max)
        ahead = jnp.where(lead_ref[row] == row, 0, shared_ref[row] // npb)
        return jnp.minimum(ahead, end - 1), end

    def page_copy(phys, slot, n):
        return pltpu.make_async_copy(
            pool_hbm.at[phys], buf.at[slot, n], sems.at[slot]
        )

    def run_copy(first, slot):
        """ONE copy of the block's ``npb`` neighbouring pages of the pool
        from ``first`` on: the bytes of its page copies in the same places."""
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(first, npb)], buf.at[slot], sems.at[slot]
        )

    # A pool of fewer pages than a block holds no run (nor a slice to wait by).
    by_runs = 1 < npb <= pool_hbm.shape[0]

    def a_run(row, blk, slot):
        """Start ``row``'s block ``blk``, a run, as ONE copy."""
        run_copy(bt_ref[row, blk * npb], slot).start()

    def a_copy_a_page(row, blk, slot):
        """Start ``row``'s block ``blk`` a copy a page. A logical page past
        the row's last live one clamps to that one, as in
        :func:`_decode_kernel`."""
        last = jnp.minimum(lens_ref[row] // page, pages_per_seq - 1)
        for n in range(npb):
            phys = bt_ref[row, jnp.minimum(blk * npb + n, last)]
            page_copy(phys, slot, n).start()

    def runs_of(row):
        """The blocks of ``row``'s table, from the first, that are runs."""
        return runs_ref[row] if by_runs else 0

    def start(row, blk, slot):
        """Start the copies of ``row``'s block ``blk``, whichever it is."""
        if by_runs:
            jax.lax.cond(
                blk < runs_of(row), lambda: a_run(row, blk, slot),
                lambda: a_copy_a_page(row, blk, slot),
            )
        else:
            a_copy_a_page(row, blk, slot)

    def keys(slot):
        """Wait for the block in buffer ``slot``: its ``[keys, D]``. A wait
        counts BYTES on the buffer's semaphore, whatever copies brought
        them: one wait for a run's copy or a copy a page."""
        if by_runs:
            run_copy(0, slot).wait()
        else:
            for n in range(npb):
                page_copy(0, slot, n).wait()
        return buf[slot].reshape(bkv, d)

    def products(q, k):
        """``q [M, D]`` against ``k [keys, D]``: ``[M, keys]`` float32."""
        return jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def weighted(s_blk, w):
        """``sum_h w_h relu(s_h)`` over one row's ``[H, keys]`` products and
        ``[H, 1]`` weights: ``[1, keys]``."""
        return jnp.sum(jnp.maximum(s_blk, 0.0) * w, axis=0, keepdims=True)

    @pl.when(b == 0)
    def _nothing_scored_yet():
        def fill(r, carry):
            o_ref[r] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, slots, fill, 0)

    @pl.when(is_live(b))
    def _row():
        pos = lens_ref[b]
        first, end = walk_of(b)
        leads = jnp.logical_and(lead_ref[b] == b, shared_ref[b] >= npb)
        stacked = jnp.where(leads, shared_ref[b] // npb, 0)
        # The row before, if live, started this row's first block while it
        # scored its own last one; else this row starts it itself.
        prefetched = jnp.logical_and(b > 0, is_live(jnp.maximum(b - 1, 0)))
        slot0 = jnp.where(prefetched, first_slot[0], 0)

        @pl.when(jnp.logical_not(prefetched))
        def _first():
            start(b, first, 0)

        def two_loops(lo, hi, last_start, block):
            """``block(j)`` for ``j`` in ``[lo, hi)``, each starting the
            copies of block ``j + 1`` (none past ``last_start``): first the
            ``j`` whose next block is among the row's leading runs, ONE copy,
            then the rest, a copy a page: no test a block."""
            split = jnp.clip(
                jnp.minimum(runs_of(b) - 1, last_start), lo, hi
            )
            if by_runs:
                jax.lax.fori_loop(
                    lo, split, functools.partial(block, next_is_run=True), 0
                )
            jax.lax.fori_loop(
                split, hi, functools.partial(block, next_is_run=False), 0
            )

        @pl.when(leads)
        def _shared_walk():
            # The group's rows in slot order: this one, then the rows after
            # it that name it.
            def find(r, count):
                mine = lead_ref[r] == b

                @pl.when(mine)
                def _member():
                    members[count] = r

                return count + mine.astype(jnp.int32)

            count = jax.lax.fori_loop(b, slots, find, 0)
            for m in range(group_rows):
                # A place no member takes is this row's again: it scores and
                # writes what this row's own place does (a branch a member
                # inside the block loop cost a quarter of the call).
                r = jnp.where(m < count, members[m], b)
                members[m] = r
                qg_scr[m * h : (m + 1) * h] = q_ref[r].astype(qg_scr.dtype)
                wg_scr[m * h : (m + 1) * h] = w_ref[r]
            # A pair rides at M = 2 H; only a wider group pays for more.
            sizes = sorted({min(2, group_rows), group_rows})
            for lo, rows in zip([0] + sizes, sizes):

                @pl.when(jnp.logical_and(lo < count, count <= rows))
                def _stacked(rows=rows):
                    q = qg_scr[0 : rows * h]

                    def block(j, carry, next_is_run):
                        slot = (slot0 + j) % 2
                        # The leader's own blocks follow the shared ones.
                        (a_run if next_is_run else a_copy_a_page)(
                            b, j + 1, 1 - slot
                        )
                        s_blk = products(q, keys(slot))  # [rows x H, keys]
                        for m in range(rows):
                            part = slice(m * h, (m + 1) * h)
                            o_ref[members[m], pl.ds(j, 1), :] = weighted(
                                s_blk[part], wg_scr[part]
                            )
                        return carry

                    two_loops(0, stacked, stacked, block)

        q = q_ref[b].astype(buf.dtype)
        w = w_ref[b]
        next_row = jnp.minimum(b + 1, slots - 1)
        next_live = jnp.logical_and(b + 1 < slots, is_live(next_row))

        def block(j, carry, next_is_run):
            slot = (slot0 + j - first) % 2
            if next_is_run:
                # Below the row's runs a next block follows, and is one.
                a_run(b, j + 1, 1 - slot)
            else:

                @pl.when(j + 1 < end)
                def _next_block():
                    a_copy_a_page(b, j + 1, 1 - slot)

                @pl.when(jnp.logical_and(j + 1 == end, next_live))
                def _next_row():
                    start(next_row, walk_of(next_row)[0], 1 - slot)
                    first_slot[0] = 1 - slot

            total = weighted(products(q, keys(slot)), w)
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
            o_ref[b, pl.ds(j, 1), :] = jnp.where(kpos <= pos, total, -jnp.inf)
            return carry

        two_loops(jnp.maximum(first, stacked), end, end - 1, block)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def _index_flash(q, w, pool, block_tables, seq_lens, leader, shared, *,
                 pages_per_block, interpret):
    """The index kernel's ``pallas_call`` for ``q [S, H, D]`` and ``w [S,
    H]``: jitted and named for :func:`_paged_flash`'s reasons. The tables,
    the lengths, the rows' grouping and the blocks that are runs
    (:func:`index_runs`, worked out here: a reshape and a few comparisons of
    the table) ride as scalar prefetch; every row's
    queries and weights, and the whole ``[S, blocks, block keys]`` result (a
    leader writes its members' rows), stay in VMEM for the call."""
    s, h, d = q.shape
    page = pool.shape[1]
    npb = int(pages_per_block)
    nblk = -(-block_tables.shape[1] // npb)
    m_rows = GROUP_ROWS * h

    def whole(shape):
        return pl.BlockSpec(
            shape, lambda b, *_: (0,) * len(shape), memory_space=pltpu.VMEM
        )

    # What the kernel holds in VMEM: the result, the rows' queries and
    # weights (each twice: the pipeline's two buffers; a weight takes a
    # lane's 128), the page buffers, a group's queries and weights and a
    # stacked block's scores.
    item = jnp.dtype(pool.dtype).itemsize
    held = (
        2 * s * (-(-nblk // 8) * 8) * npb * page * 4
        + 2 * s * h * (d * jnp.dtype(q.dtype).itemsize + 128 * 4)
        + 2 * npb * page * d * item
        + m_rows * (d * item + 128 * 4)
        + 3 * m_rows * npb * page * 4
    )
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, npb=npb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s,),
            in_specs=[
                whole((s, h, d)), whole((s, h, 1)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=whole((s, nblk, npb * page)),
            scratch_shapes=[
                pltpu.VMEM((2, npb, page, d), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # where a row's first block is
                pltpu.SMEM((GROUP_ROWS,), jnp.int32),  # a group's rows
                pltpu.VMEM((m_rows, d), pool.dtype),  # a group's queries
                pltpu.VMEM((m_rows, 1), jnp.float32),  # its head weights
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, nblk, npb * page), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, int(1.5 * held)),
        ),
        interpret=interpret,
        name=INDEX_KERNEL,
    )(
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        leader.astype(jnp.int32), shared.astype(jnp.int32),
        index_runs(block_tables, seq_lens, page, npb), q,
        w.astype(jnp.float32)[..., None], pool,
    )
    return out.reshape(s, nblk * npb * page)[:, : block_tables.shape[1] * page]


def paged_index_scores(
    q: jnp.ndarray,
    w: jnp.ndarray,
    pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    kernel="auto",
    row_groups=None,
) -> jnp.ndarray:
    """A decode row's index score of every cached token: ``q [S, H, D]`` index
    queries and ``w [S, H]`` head weights (the scores' scales folded in)
    against the index-key pool ``[num_pages, page, D]`` through the rows'
    tables: float32 ``[S, pages_per_seq * page]``, ``sum_h w_h relu(q_h .
    k_s)`` at ``s <= pos`` and ``-inf`` past it. Dispatches per ``kernel``
    as :func:`paged_attention` does. The kernel scores the index keys of
    pages that rows share (askers of one cached document) once for the rows
    that share them: ``row_groups`` is :func:`shared_prefix_groups`' ``(leader,
    shared)`` for these tables and lengths, as :func:`paged_latent_attention`
    takes it; ``None`` works it out here. The scores are the same bits
    however the rows are grouped."""
    if pool.ndim != 3 or pool.shape[2] != q.shape[-1]:
        raise ValueError(
            f"an index-key pool is [num_pages, page, {q.shape[-1]}], got "
            f"{pool.shape}"
        )
    mode = resolve_kernel(kernel)
    if mode == "xla":
        return index_scores_reference(q, w, pool, block_tables, seq_lens)
    npb = index_block_pages(block_tables.shape[1])
    if row_groups is None:
        row_groups = shared_prefix_groups(
            block_tables, seq_lens, pool.shape[1], npb
        )
    return _index_flash(
        q, w, pool, block_tables, seq_lens, *row_groups[:2],
        pages_per_block=npb, interpret=(mode == "interpret"),
    )


def _ordered_bits(scores):
    """float32 scores as uint32 that order as the scores do."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


#: Bits of the ``k``-th largest score that one pass of :func:`top_k_mask`
#: settles: a pass counts the scores at or above ``2 ** SELECT_BITS - 1``
#: trial values at once, and 32 / SELECT_BITS passes follow one another.
SELECT_BITS = 4


def top_k_mask(scores, k: int):
    """The EXACT top ``k`` of ``scores [..., N]`` along the last axis, as a
    mask: true at the ``k`` largest, equal scores by lowest index
    (``jax.lax.top_k``'s order), and never at ``-inf`` (fewer than ``k``
    where fewer are finite). No sort: the ``k``-th largest is found
    ``SELECT_BITS`` bits a pass from the top (a pass counts the scores, as
    ordered integers, at or above every value those bits can make it), then
    the ties at it are counted off."""
    n = scores.shape[-1]
    if k >= n:
        return scores > -jnp.inf
    bits = _ordered_bits(scores)
    trials = jnp.arange(1, 1 << SELECT_BITS, dtype=jnp.uint32)

    def refine(i, kth):
        shift = (32 - SELECT_BITS * (i + 1)).astype(jnp.uint32)
        trial = kth[..., None] | (trials << shift)  # [..., trials], rising
        enough = jnp.sum(
            bits[..., None, :] >= trial[..., None], axis=-1
        ) >= k
        best = jnp.sum(enough, axis=-1).astype(jnp.uint32)  # trials that hold
        return kth | (best << shift)

    kth = jax.lax.fori_loop(
        0, 32 // SELECT_BITS, refine,
        jnp.zeros(scores.shape[:-1], jnp.uint32),
    )[..., None]
    above = bits > kth
    tied = bits == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    return chosen & (scores > -jnp.inf)


def _positions_kernel(held_ref, where_ref, real_ref):
    """One row of the mask, ``[blocks, lanes]`` of 0/1, and every wanted rank
    ``j`` (on the lanes, so that what comes out is a row): the row's two
    running counts by products with triangles of ones, the block of every
    ``j`` by comparisons with the blocks' counts, the block's own running
    count by a one-hot product, and the lane by comparisons with it. Every
    operand of a product is 0, 1 or a count of at most ``lanes``, exact in
    bfloat16; the sums are float32, exact."""
    blocks, lanes = held_ref.shape[1:]
    k = where_ref.shape[-1]
    held = held_ref[0]

    def ones_where(shape, keep):
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return jnp.where(keep(rows, cols), 1.0, 0.0).astype(jnp.bfloat16)

    # inside[l, b]: the true positions of block b at lanes <= l
    inside = jax.lax.dot_general(
        ones_where((lanes, lanes), lambda l, m: m <= l), held,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(jnp.bfloat16)
    # upto[b]: the true positions of the blocks <= b (lane by lane, then summed)
    upto = jnp.sum(jnp.dot(
        ones_where((blocks, blocks), lambda b, c: c <= b), held,
        preferred_element_type=jnp.float32,
    ), axis=1, keepdims=True)  # [blocks, 1]
    want = (
        1 + jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    ).astype(jnp.float32)
    below = upto < want  # [blocks, k]: the blocks that end before j
    block = jnp.minimum(
        jnp.sum(below.astype(jnp.int32), axis=0, keepdims=True), blocks - 1
    )
    before = jnp.max(jnp.where(below, upto, 0.0), axis=0, keepdims=True)
    onehot = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (blocks, k), 0) == block, 1.0, 0.0
    ).astype(jnp.bfloat16)
    counts = jnp.dot(
        inside, onehot, preferred_element_type=jnp.float32
    )  # [lanes, k]: the running count of j's block
    lane = jnp.sum(
        (counts < want - before).astype(jnp.int32), axis=0, keepdims=True
    )
    real = want <= upto[blocks - 1:, :]
    where = block * lanes + jnp.minimum(lane, lanes - 1)
    where_ref[0] = jnp.where(real, where, 0)
    real_ref[0] = real.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _positions_flash(held, *, k, interpret):
    """The positions kernel's ``pallas_call`` over ``held [S, blocks,
    lanes]`` bfloat16, a row a grid step, named as the file's others are."""
    s, blocks, lanes = held.shape

    def row_spec(shape):
        return pl.BlockSpec(
            shape, lambda r: (r, 0, 0), memory_space=pltpu.VMEM
        )

    where, real = pl.pallas_call(
        _positions_kernel,
        grid=(s,),
        in_specs=[row_spec((1, blocks, lanes))],
        out_specs=[row_spec((1, 1, k))] * 2,
        out_shape=[jax.ShapeDtypeStruct((s, 1, k), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a handful of [blocks, k] arrays of four bytes at a time
            vmem_limit_bytes=max(16 << 20, 40 * blocks * k),
        ),
        interpret=interpret,
        name=POSITIONS_KERNEL,
    )(held)
    return where[:, 0], real[:, 0] > 0


def selected_positions(mask, k: int, *, kernel="auto"):
    """The positions at which ``mask [S, N]`` is true, ascending, as ``[S,
    k]`` int32 and which of them are real (a row with fewer than ``k`` pads
    with position 0, marked false). No sort, no loop, no gather: the ``j``-th
    true position lies in the block of 128 lanes at which the blocks' running
    count first reaches ``j`` (comparisons of every ``j`` with every block's
    count), at the lane at which that block's own running count first reaches
    what is left (the block's counts by a ONE-HOT PRODUCT, exact: one term a
    sum). Dispatches per ``kernel`` as :func:`paged_attention` does: the
    kernel on the chip, the same arithmetic in XLA elsewhere."""
    s, n = mask.shape
    lanes = 128
    mode = resolve_kernel(kernel)
    # the kernel holds the blocks on its lanes too: whole tiles of them
    blocks = -(-n // lanes) if mode == "xla" else -(-n // lanes**2) * lanes
    held = jnp.pad(mask, ((0, 0), (0, blocks * lanes - n))).reshape(
        s, blocks, lanes
    )
    if mode != "xla":
        return _positions_flash(
            held.astype(jnp.bfloat16), k=int(k),
            interpret=(mode == "interpret"),
        )
    inside = jnp.cumsum(held.astype(jnp.int32), axis=-1)  # a block's own count
    upto = jnp.cumsum(inside[..., -1], axis=-1)[:, None, :]  # [S, 1, blocks]
    want = jnp.arange(1, k + 1, dtype=jnp.int32)[None, :, None]
    below = upto < want  # [S, k, blocks]: the blocks that end before j
    block = jnp.minimum(jnp.sum(below, axis=-1), blocks - 1)  # [S, k]
    before = jnp.max(jnp.where(below, upto, 0), axis=-1, keepdims=True)
    counts = jnp.einsum(
        "skb,sbl->skl", jax.nn.one_hot(block, blocks, dtype=jnp.float32),
        inside.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
    )
    lane = jnp.sum(counts < want - before, axis=-1)
    real = want[..., 0] <= upto[..., -1]
    where = block * lanes + jnp.minimum(lane, lanes - 1)
    return jnp.where(real, where, 0).astype(jnp.int32), real


def _rows_at(pool, block_tables, positions):
    """The pool's rows of the token ``positions [S, k]`` through the rows'
    tables: ``[S, k, W]`` (XLA's gather)."""
    page = pool.shape[1]
    phys = jnp.take_along_axis(
        block_tables.astype(jnp.int32), positions // page, axis=1
    )
    return pool[phys, positions % page]


def _sparse_decode_kernel(q_ref, k_ref, real_ref, o_ref, *, v_width, sm_scale):
    """One slot: ``[H, W]`` absorbed queries against the row's ``[k, W]``
    gathered latents, one softmax over the real ones, and their first
    ``v_width`` columns as values."""
    keys = k_ref[0]
    s_all = jax.lax.dot_general(
        q_ref[0].astype(keys.dtype), keys, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [H, k]
    s_all = jnp.where(real_ref[0] > 0, s_all, NEG_INF)
    p = jnp.exp(s_all - jnp.max(s_all, axis=-1, keepdims=True))
    pv = jax.lax.dot_general(
        p.astype(keys.dtype), keys[:, :v_width], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = (pv / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret", "sm_scale", "v_width")
)
def _sparse_flash(q3, pool, block_tables, positions, real, *, interpret,
                  sm_scale, v_width):
    """Gather the selected tokens' latents through the tables (XLA's gather:
    ``[S, k, W]``), then the sparse kernel's ``pallas_call`` over them,
    named as the others are."""
    s, h, w = q3.shape
    k = positions.shape[1]
    keys = _rows_at(pool, block_tables, positions)

    def row_spec(shape):
        return pl.BlockSpec(
            shape, lambda b: (b,) + (0,) * (len(shape) - 1),
            memory_space=pltpu.VMEM,
        )

    item = jnp.dtype(pool.dtype).itemsize
    held = 2 * k * w * item + 3 * h * k * 4 + 4 * h * (w + v_width) * 4
    return pl.pallas_call(
        functools.partial(
            _sparse_decode_kernel, v_width=v_width, sm_scale=sm_scale
        ),
        grid=(s,),
        in_specs=[
            row_spec((1, h, w)), row_spec((1, k, w)), row_spec((1, 1, k)),
        ],
        out_specs=row_spec((1, h, v_width)),
        out_shape=jax.ShapeDtypeStruct((s, h, v_width), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(16 << 20, int(1.5 * held)),
        ),
        interpret=interpret,
        name=SPARSE_KERNEL,
    )(q3, keys, real.astype(jnp.int32)[:, None, :])


def sparse_latent_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    real: jnp.ndarray,
    *,
    v_width: int,
    kernel="auto",
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Absorbed latent attention of decode rows ``q [S, 1, H, W]`` over a LIST
    of cached token positions a row (``positions [S, k]``, of which ``real
    [S, k]`` count) instead of the row's whole table: ``[S, 1, H,
    v_width]``. The tokens' latents are gathered out of the pool ``[num_pages,
    page, W]`` through the tables; nothing else of the pool is read."""
    s, t_step, h, w = q.shape
    if t_step != 1:
        raise ValueError("a list of selected positions serves decode rows")
    mode = resolve_kernel(kernel)
    scale = float(w**-0.5 if sm_scale is None else sm_scale)
    if mode == "xla":
        keys = _rows_at(pool, block_tables, positions)
        logits = jnp.einsum("bqhd,bkd->bhqk", q, keys) * scale
        logits = jnp.where(real[:, None, None, :], logits, NEG_INF)
        weights = jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
        return jnp.einsum("bhqk,bkd->bqhd", weights, keys[..., :v_width])
    out3 = _sparse_flash(
        q.reshape(s, h, w), pool, block_tables, positions, real,
        interpret=(mode == "interpret"), sm_scale=scale,
        v_width=int(v_width),
    )
    return out3.reshape(s, 1, h, v_width)
