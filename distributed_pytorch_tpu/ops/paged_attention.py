"""Pallas paged-attention (flash-decode) over the serving engine's KV pools.

The serving decode hot path reads the paged KV cache — a global per-layer
pool ``[num_pages, page_size, Hkv, D]`` addressed through per-sequence block
tables — and until this module existed it did so via a plain-XLA gather
(``models/transformer.py:_paged_decode_step``): materialize every row's
``[pages_per_seq * page_size, Hkv, D]`` logical view in HBM, then attend.
``obs/roofline.py`` classifies that program bandwidth-bound; the gather
writes and re-reads the whole working set once per generated token.

This kernel fuses the block-table indirection into the attention loop:

* grid ``(slots, kv_blocks)`` with the KV dim innermost. Each grid step
  streams ``pages_per_block`` PHYSICAL pages HBM->VMEM — the block table
  rides as a scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so
  every page's ``BlockSpec`` index map picks its physical page id before the
  body runs and Pallas double-buffers the page fetches like any other block.
  The gathered logical view is never materialized.
* online softmax (FlashAttention-style running max / denominator / output
  accumulator in fp32 VMEM scratch, persisting across the KV blocks) with
  grouped-query head mapping: query head ``h`` reads kv head ``h // group``,
  the same contraction layout as the XLA reference's grouped einsums.
* masking is positional, exactly as the reference: key position ``kpos`` is
  visible iff ``kpos <= pos`` (the row's current absolute position). NULL
  pages (physical page 0 — inactive slots, padded table tails) are read but
  every one of their positions fails the visibility test, so their contents
  die in the softmax; KV blocks entirely past ``pos`` skip their MXU work
  via ``pl.when``.
* int8 KV pages: with ``k_scale``/``v_scale`` (``[num_pages, page_size,
  Hkv]`` float32, quantized on page write by the model) the kernel fetches
  int8 pages plus their scales and dequantizes in VMEM — HBM sees a quarter
  of the fp32 page bytes plus one scale per (slot, head).

``paged_attention_reference`` is the pure-XLA fallback: op-for-op the read
side of ``_paged_decode_step``, so an engine toggling the kernel off is
bitwise-identical to the pre-kernel engine. Mode resolution ("auto") uses
the kernel on TPU and the reference elsewhere; ``kernel="interpret"`` runs
the Pallas kernel through the interpreter — the CPU test rig's way of
exercising the real kernel code path.

GSPMD cannot partition a ``pallas_call``, so under a sharded jit pass
``mesh`` (as :class:`models.transformer.Attention` does): the kernel then
runs per-shard under ``shard_map`` with the KV-head dim split over the
``model`` axis — the same placement ``serving/mesh.py:KV_POOL_SPEC`` gives
the pools, so no collective is added beyond what the weight split implies.

Block sizing (``pages_per_block``) comes from the ``ops/flash_autotune``
harness' ``paged_decode`` family: measured winners on real hardware, a
seeded table entry for CPU/interpret so CI never autotunes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu.ops.attention import NEG_INF
from distributed_pytorch_tpu.utils.platform import on_tpu

#: Accepted ``kernel=`` modes: "auto" resolves per backend, "pallas" forces
#: the compiled kernel, "interpret" runs the kernel through the Pallas
#: interpreter (CPU tests), "xla" forces the reference fallback.
KERNEL_MODES = ("auto", "pallas", "interpret", "xla")


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
) -> jnp.ndarray:
    """The XLA gather path: op-for-op the read side of
    ``_paged_decode_step`` (gather each row's pages into its contiguous
    logical view, positional visibility mask, grouped GQA einsums, f32
    softmax) — the bitwise-parity anchor the kernel is tested against.

    ``q`` [S, T_step, H, D] is post-RoPE; ``seq_lens`` [S] is each row's
    token count BEFORE the step (= the absolute position of its first new
    token). With ``k_scale``/``v_scale`` the pools are int8 and dequantize
    at the gather, mirroring the contiguous quantized-cache idiom."""
    s, t_step, h, d = q.shape
    kv_heads = k_pool.shape[2]
    page = k_pool.shape[1]
    pages_per_seq = block_tables.shape[1]
    kv_len = pages_per_seq * page

    positions = seq_lens.astype(jnp.int32)[:, None] + jnp.arange(
        t_step, dtype=jnp.int32
    )
    keys = k_pool[block_tables].reshape(s, kv_len, kv_heads, d)
    values = v_pool[block_tables].reshape(s, kv_len, kv_heads, d)
    if k_scale is not None:
        ks = k_scale[block_tables].reshape(s, kv_len, kv_heads)
        vs = v_scale[block_tables].reshape(s, kv_len, kv_heads)
        keys = keys.astype(q.dtype) * ks[..., None].astype(q.dtype)
        values = values.astype(q.dtype) * vs[..., None].astype(q.dtype)
    scale = d**-0.5
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


def _decode_kernel(
    bt_ref, lens_ref, q_ref, *refs, npb, group, sm_scale, quantized
):
    """One (slot, kv-block) grid step of the flash-decode kernel.

    ``refs`` unpacks to ``npb`` K page blocks, ``npb`` V page blocks,
    (when quantized) ``npb`` + ``npb`` scale blocks, the output block, and
    the three fp32 scratch accumulators (running max ``m``, denominator
    ``l``, output ``acc``) that persist across the innermost grid dim."""
    k_refs, v_refs = refs[:npb], refs[npb : 2 * npb]
    if quantized:
        ks_refs = refs[2 * npb : 3 * npb]
        vs_refs = refs[3 * npb : 4 * npb]
        o_ref, m_scr, l_scr, acc_scr = refs[4 * npb :]
    else:
        ks_refs = vs_refs = None
        o_ref, m_scr, l_scr, acc_scr = refs[2 * npb :]

    b = pl.program_id(0)
    j = pl.program_id(1)
    page = k_refs[0].shape[1]
    kv_heads, d = k_refs[0].shape[2], k_refs[0].shape[3]
    h = q_ref.shape[1]
    bkv = npb * page
    block_start = j * bkv

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = lens_ref[b]  # the decode token's absolute position (T_step == 1)

    # Blocks wholly past the row's current position contribute nothing —
    # skip their MXU work (the page DMAs still happen; the grid is static).
    # Every computed block has key `block_start` visible, so the running
    # max stays finite and no exp(NEG_INF - NEG_INF) row can arise.
    @pl.when(block_start <= pos)
    def _step():
        def load(page_refs, scale_refs):
            tiles = []
            for n in range(npb):
                tile = page_refs[n][0].astype(jnp.float32)
                if quantized:
                    tile = tile * scale_refs[n][0].astype(jnp.float32)[
                        ..., None
                    ]
                tiles.append(tile)
            return (
                jnp.concatenate(tiles, axis=0) if npb > 1 else tiles[0]
            )  # [bkv, Hkv, D] f32

        k = load(k_refs, ks_refs)
        v = load(v_refs, vs_refs)
        q = q_ref[0].astype(jnp.float32)  # [H, D]
        # Grouped-query mapping: query head h reads kv head h // group —
        # kv leads group, matching the reference's qg reshape.
        qg = q.reshape(kv_heads, group, d)
        kt = k.transpose(1, 0, 2)  # [Hkv, bkv, D]
        s_blk = (
            jax.lax.dot_general(
                qg, kt, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # [Hkv, group, bkv]
        kpos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, bkv), 2
        )
        # Positional visibility IS the NULL-page mask: padded table tails
        # and inactive slots resolve to physical page 0, whose every key
        # position here fails kpos <= pos — their contents never survive.
        s_blk = jnp.where(kpos <= pos, s_blk, NEG_INF)
        s2 = s_blk.reshape(h, bkv)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
        p = jnp.exp(s2 - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        pg = p.reshape(kv_heads, group, bkv)
        vt = v.transpose(1, 0, 2)  # [Hkv, bkv, D]
        pv = jax.lax.dot_general(
            pg, vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, group, D]
        acc_scr[:] = acc_scr[:] * correction + pv.reshape(h, d)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _paged_flash(
    q3, k_pool, v_pool, block_tables, seq_lens, k_scale, v_scale,
    *, pages_per_block, interpret,
):
    """Build and invoke the pallas_call for ``q3`` [S, H, D] (T_step == 1).

    Each of the ``pages_per_block`` pages in a KV block is its own input
    operand (the same pool array, aliased) with its own index map reading
    the scalar-prefetched block table — Pallas fetches ``pages_per_block``
    non-contiguous physical pages per grid step and the kernel concatenates
    them in VMEM. Logical pages past the table width clamp to the last
    entry; their key positions sit past any legal ``pos``, so the
    visibility mask kills the duplicates."""
    s, h, d = q3.shape
    page = k_pool.shape[1]
    kv_heads = k_pool.shape[2]
    pages_per_seq = block_tables.shape[1]
    group = h // kv_heads
    npb = max(1, min(int(pages_per_block), pages_per_seq))
    nblk = -(-pages_per_seq // npb)
    quantized = k_scale is not None

    def page_index(n):
        def index_map(b, j, bt, lens):
            logical = jnp.minimum(j * npb + n, pages_per_seq - 1)
            return (bt[b, logical], 0, 0, 0)

        return index_map

    def scale_index(n):
        def index_map(b, j, bt, lens):
            logical = jnp.minimum(j * npb + n, pages_per_seq - 1)
            return (bt[b, logical], 0, 0)

        return index_map

    def row_spec(shape):
        return pl.BlockSpec(
            shape, lambda b, j, bt, lens: (b, 0, 0),
            memory_space=pltpu.VMEM,
        )

    k_specs = [
        pl.BlockSpec(
            (1, page, kv_heads, d), page_index(n), memory_space=pltpu.VMEM
        )
        for n in range(npb)
    ]
    in_specs = [row_spec((1, h, d))] + k_specs + k_specs
    operands = [q3] + [k_pool] * npb + [v_pool] * npb
    if quantized:
        s_specs = [
            pl.BlockSpec(
                (1, page, kv_heads), scale_index(n),
                memory_space=pltpu.VMEM,
            )
            for n in range(npb)
        ]
        in_specs += s_specs + s_specs
        operands += [k_scale] * npb + [v_scale] * npb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, nblk),
        in_specs=in_specs,
        out_specs=row_spec((1, h, d)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),  # running max m
            pltpu.VMEM((h, 128), jnp.float32),  # denominator l
            pltpu.VMEM((h, d), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, npb=npb, group=group, sm_scale=d**-0.5,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, d), q3.dtype),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        *operands,
    )


def paged_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    kernel="auto",
    pages_per_block: Optional[int] = None,
    mesh=None,
    heads_axis: str = "model",
) -> jnp.ndarray:
    """Paged attention over ``q`` [S, T_step, H, D] against the page pools.

    Kernel-eligible steps (T_step == 1, the batched decode step) dispatch
    per ``kernel`` (see :func:`resolve_kernel`); chunked reads — prefill
    chunks, speculative verification — always take the XLA reference, which
    handles any T_step. ``pages_per_block`` defaults to the autotune
    harness' ``paged_decode`` family entry for this shape.

    Under a sharded jit pass ``mesh``: the kernel runs per-shard via
    ``shard_map`` with Q heads and KV heads (and scale heads) split over
    ``heads_axis`` and everything else replicated — the exact placement the
    engine's pool/param shardings already use, so no extra collective."""
    s, t_step, h, d = q.shape
    kv_heads = k_pool.shape[2]
    if h % kv_heads:
        raise ValueError(
            f"query heads {h} not divisible by kv heads {kv_heads}"
        )
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    mode = resolve_kernel(kernel)
    if mode == "xla" or t_step != 1:
        return paged_attention_reference(
            q, k_pool, v_pool, block_tables, seq_lens,
            k_scale=k_scale, v_scale=v_scale,
        )

    if pages_per_block is None:
        from distributed_pytorch_tpu.ops.flash_autotune import lookup_paged

        page = k_pool.shape[1]
        pages_per_block = lookup_paged(
            block_tables.shape[1] * page, page, d,
            dtype_name=jnp.dtype(q.dtype).name,
        )

    run = functools.partial(
        _paged_flash,
        pages_per_block=pages_per_block,
        interpret=(mode == "interpret"),
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
