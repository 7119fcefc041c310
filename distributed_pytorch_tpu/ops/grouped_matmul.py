"""The expert layers' grouped products as a weight-stationary Pallas kernel.

``models/moe.py``'s ``RoutedExperts`` sorts its routed (token, expert) pairs
by held expert and multiplies each expert's rows with that expert's weights:
``rows [R, K]`` (group ``e`` is rows ``starts[e] .. starts[e] + sizes[e]``,
the groups back to back from row 0, rows past them in no group) against ``w
[G, K, N]``. At serving loads an expert has 3-70 rows, so the product is the
time its WEIGHTS take to stream out of HBM, and that is what the kernel is
built round:

* the grid is (held expert, column tile of the weight); the expert's ``[K,
  tn]`` block is the pipelined operand, double-buffered behind the matmuls
  by Pallas, each block fetched exactly once a call;
* an expert no row reached is not fetched: its grid steps name the block the
  pipeline already holds (the last reached expert's last, or the first
  reached expert's first), so they move nothing and compute nothing;
* the expert's own rows are copied out of HBM in row tiles of
  :data:`ROW_TILE`, as many as :func:`tile_span` says its group touches, once
  an expert (kept in VMEM for its column tiles; the NEXT reached expert's are
  started behind this one's products, so only the first expert waits for
  its rows), and multiplied tile by tile in a loop whose trip count is the
  group's: a decode program (3-9 rows an expert) and a 512-wide prefill
  piece (~70) run the same kernel. Rows of no group are never visited;
* the tiles are ALIGNED tiles of the sorted rows, so no copy starts inside a
  tile of the chip's layout and the rows need no second, padded layout: where
  a group starts inside a tile, the tile's earlier rows are the previous
  groups', already computed, and are taken from a ``[tm, tn]`` carry a column
  tile instead of from this expert's product. A tile's rows past the group's
  end are overwritten by the groups that follow, or belong to no group:
  ``RoutedExperts`` masks those as it always did.

The arithmetic is ``jax.lax.ragged_dot``'s: operands as given (bf16 in the
benchmark's configurations), float32 accumulation, float32 results.
:func:`grouped_matmul` is one product, :func:`gated_experts` a gated-SiLU
layer's two with the activation between them (what ``RoutedExperts`` calls:
one jitted call a layer); ``mode`` is ``ops/paged_attention.resolve_kernel``'s
(``"pallas"``, ``"interpret"``, ``"xla"`` = ``jax.lax.ragged_dot`` itself, the
plain form the tests hold the kernel to and what a CPU runs).

What the kernel costs at SET-UP shapes it too. The persistent compile cache
spares a program the Mosaic compile, not the kernel body's trace and
lowering, which every program that calls it repeats for its own row count;
on a serving host that was +0.6 s a program and a fifth of a cell's set-up
(PERF.md section 6). So the kernel divides nothing and branches twice (tiles,
offsets, buffer turns and what to copy ahead ride in the scalar prefetch,
worked out by XLA in a jitted :func:`_metadata`), its scalar work is ``lax`` on
int32, a layer's two products are one jitted call, and ``RoutedExperts``
gathers its rows into a power of two's worth of tokens, so a model's prefill
widths share a few traces.

The ``pallas_call`` is NAMED ``ragged-dot-stationary``: a v5e device trace
names an operation by its HLO instruction, and the benchmark's readers
(``benchmarks/harness/moe_hybrid.py`` ``is_moe_op``) count an instruction
whose name holds ``ragged-dot`` to the expert layers, as they counted the
compiler's own kernel.

The serving engine refuses a mesh for a model with routed layers
(``serving/engine.py``: no expert axis on the serving mesh yet), so the kernel
runs on one chip's held experts and needs no ``shard_map``; an expert axis
(ROADMAP R1) would wrap it as ``ops/paged_attention.py`` wraps its kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Rows a tile: one bf16 tile of the chip's layout (16 sublanes), two float32
#: ones. The ONE tiling rule: the kernel walks it, the host counts by it.
ROW_TILE = 16

#: What a weight block may take in VMEM (the pipeline holds two). On the v5e
#: the widest blocks read fastest once the rows are copied ahead (a whole
#: ``[768, 4096]`` expert, half of a ``[4096, 1536]`` one: PERF.md section 6).
BLOCK_BYTES = 6 << 20


def tile_span(start, size, tile=ROW_TILE):
    """``(first, n)``: the aligned row tiles ``first .. first + n - 1`` that
    a group of ``size`` rows from row ``start`` touches; none for an empty
    group. Integer arithmetic alone, so it serves JAX arrays (the kernel's
    scalar prefetch, :func:`_metadata`) and the host's NumPy counts alike."""
    first = start // tile
    n = (start + size + tile - 1) // tile - first
    return first, n * (size > 0)


def rows_computed(sizes, tile=ROW_TILE) -> int:
    """Rows the kernel multiplies for groups of ``sizes [.., G]`` (whole row
    tiles a reached group, by :func:`tile_span`), summed over everything
    before the last axis: the host's side of the rule, for the engine's
    ``moe_rows_computed``."""
    sizes = np.asarray(sizes, np.int64)
    starts = np.cumsum(sizes, axis=-1) - sizes
    return int(tile_span(starts, sizes, tile)[1].sum()) * tile


def column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest column tile of a ``[k, n]`` weight that divides ``n``, is
    whole lanes (a multiple of 128) and fits :data:`BLOCK_BYTES`; ``n``
    itself where it has no such divisor (toy widths)."""
    fits = [
        tn for tn in range(128, n + 1, 128)
        if n % tn == 0 and k * tn * itemsize <= BLOCK_BYTES
    ]
    return max(fits) if fits else n


#: The rows of the kernel's scalar prefetch (``_metadata``), ``[., G]``.
START, ROW0, TILES, SLOT, OWN, NEXT, AHEAD, DRAIN, FETCH, HOLD = range(10)


@functools.partial(jax.jit, static_argnames="tile")
def _metadata(sizes, *, tile):
    """``[10, G] int32`` for the kernel's scalar prefetch, an expert: its
    group's first row (``START``); the first row of its first row tile and how
    many tiles it touches (``ROW0``, ``TILES``: :func:`tile_span`; none: no
    row reached it); which of the two row buffers its rows go to (``SLOT``:
    reached experts take turns); the tiles it copies for itself (``OWN``: the
    first reached expert's, else none) and for the reached expert after it
    (``NEXT``, ``AHEAD``: none after the last); whether an out-write is to be
    awaited after it (``DRAIN``: the last expert, if any was reached); the
    expert whose block its grid steps name (``FETCH``: its own if reached)
    and, for an expert no row reached, which column tile (``HOLD`` 1: the
    last, of the reached expert before it; 2: the first, of the first reached
    expert, where none is before it). Worked out here, in XLA, so that the
    kernel holds little scalar arithmetic and no division: every program
    that calls the kernel traces and lowers it again (the persistent cache
    spares only the compile), and that is set-up time (PERF.md section 6).
    Jitted for the same reason: one trace a process, whatever the rows."""
    sizes = sizes.astype(jnp.int32)
    g = sizes.shape[0]
    index = jnp.arange(g, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tile0, tiles = tile_span(starts, sizes, tile)
    reached = sizes > 0
    before = jax.lax.cummax(jnp.where(reached, index, -1))
    after = jax.lax.cummin(jnp.where(reached, index, g), reverse=True)
    following = jnp.concatenate([after[1:], jnp.full((1,), g, jnp.int32)])
    nxt = jnp.minimum(following, g - 1)
    return jnp.stack([
        starts, tile0 * tile, tiles, jnp.cumsum(reached) % 2,
        jnp.where(index == after[0], tiles, 0), nxt,
        jnp.where(following < g, tiles[nxt], 0),
        jnp.where(index == g - 1, jnp.any(reached), False),
        jnp.where(before >= 0, before, after[0] % g),
        jnp.where(reached, 0, jnp.where(before >= 0, 1, 2)),
    ]).astype(jnp.int32)


def _kernel(meta, x_hbm, w_ref, out_hbm, walked, xs, obuf, carry, sems,
            *, tm, tn, nj):
    # Scalar work goes through ``jax.lax`` on int32 and there are two
    # branches in all: the body is traced and lowered again by every program
    # that calls it (``_metadata``), and a ``jnp`` operator on a tracer costs
    # several times a ``lax`` one.
    lax, i32 = jax.lax, np.int32
    e, j = pl.program_id(0), pl.program_id(1)
    start, row0, n_tiles, slot = (
        meta[START, e], meta[ROW0, e], meta[TILES, e], meta[SLOT, e]
    )
    column0 = lax.convert_element_type(lax.eq(j, i32(0)), jnp.int32)
    opens = lax.mul(meta[OWN, e], column0)  # tiles: this step opens the call

    def rows_copies(expert, slot, trips, go):
        """Start (``go``) or await the copies of ``expert``'s first ``trips``
        row tiles into ``xs[slot]``."""
        first_row = meta[ROW0, expert]

        def one(i, c):
            at = pl.multiple_of(lax.mul(i, i32(tm)), tm)
            copy = pltpu.make_async_copy(
                x_hbm.at[pl.ds(pl.multiple_of(lax.add(first_row, at), tm), tm)],
                xs.at[slot, pl.ds(at, tm)], sems.at[0],
            )
            copy.start() if go else copy.wait()
            return c

        lax.fori_loop(i32(0), trips, one, i32(0))

    def out_copy(at):
        return pltpu.make_async_copy(
            obuf,
            out_hbm.at[pl.ds(pl.multiple_of(lax.add(row0, at), tm), tm),
                       pl.ds(pl.multiple_of(lax.mul(j, i32(tn)), tn), tn)],
            sems.at[1],
        )

    # At an expert's first column step: the first reached expert copies its
    # own rows; every other's were started by the reached expert before it,
    # behind its products, as this one starts the next one's. Loops of no
    # trips where branches would stand.
    rows_copies(e, slot, opens, go=True)
    rows_copies(e, slot, lax.mul(n_tiles, column0), go=False)
    rows_copies(
        meta[NEXT, e], lax.sub(i32(1), slot),
        lax.mul(lax.mul(meta[AHEAD, e], column0), lax.min(n_tiles, i32(1))),
        go=True,
    )

    def tile(i, done):
        at = pl.multiple_of(lax.mul(i, i32(tm)), tm)
        res = jnp.dot(
            xs[slot, pl.ds(at, tm), :], w_ref[...], preferred_element_type=F32
        )
        row = lax.add(
            lax.broadcasted_iota(jnp.int32, (tm, 1), 0), lax.add(row0, at)
        )
        # Rows before the group's start are earlier groups', computed
        # already: the carry holds them.
        res = jnp.where(lax.ge(row, start), res, carry[j])
        carry[j] = res

        @pl.when(lax.gt(lax.add(i, lax.sub(n_tiles, opens)), i32(0)))
        def _last_write_done():  # every tile but the call's first
            out_copy(at).wait()

        obuf[...] = res
        out_copy(at).start()
        return lax.add(done, i32(1))

    # No trip for an expert no row reached.
    walked[e] = lax.fori_loop(i32(0), n_tiles, tile, i32(0))

    @pl.when(lax.gt(
        lax.mul(meta[DRAIN, e], lax.convert_element_type(
            lax.eq(j, i32(nj - 1)), jnp.int32)), i32(0)))
    def _drain():
        out_copy(i32(0)).wait()


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "max_group", "tile", "column"),
)
def _stationary(rows, w, sizes, *, interpret, max_group=None, tile=ROW_TILE,
                column=None):
    """The kernel's ``pallas_call``: ``(out [R, N] float32, walked [G])``,
    ``walked`` the row tiles the kernel multiplied an expert. Jitted and
    named for ``ops/paged_attention._paged_flash``'s reasons: one trace for
    a model's layers, one name in a device trace whoever calls it."""
    r, k = rows.shape
    g, _, n = w.shape
    tm = int(tile)
    item = jnp.dtype(w.dtype).itemsize
    tn = int(column or column_tile(k, n, item))
    nj = n // tn
    # The tiles of the largest group, which may begin inside one and end
    # inside another.
    held_rows = min(r, -(-int(max_group or r) // tm) * tm + tm)

    def weight_block(e, j, meta):
        hold = meta[HOLD, e]
        return (
            meta[FETCH, e], 0,
            jnp.where(hold == 0, j, jnp.where(hold == 1, nj - 1, 0)),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, nj),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((None, k, tn), weight_block),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            # The expert's rows, and the next reached expert's on their way.
            pltpu.VMEM((2, held_rows, k), rows.dtype),
            pltpu.VMEM((tm, tn), F32),  # a tile on its way out
            pltpu.VMEM((nj, tm, tn), F32),  # the carry a column tile
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    held = (
        2 * k * tn * item
        + 2 * held_rows * k * jnp.dtype(rows.dtype).itemsize
        + (nj + 4) * tm * tn * 4
    )
    out, walked = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, nj=nj),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, n), F32),
            jax.ShapeDtypeStruct((g,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, int(1.25 * held) + (4 << 20)),
        ),
        interpret=interpret,
        name="ragged-dot-stationary",
    )(_metadata(sizes, tile=tm), rows, w)
    return out, walked


def _whole_tiles(rows):
    """``rows`` padded to whole row tiles (toy sizes only: the engine's
    programs have them)."""
    pad = -rows.shape[0] % ROW_TILE
    return jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows


def grouped_matmul(rows, w, sizes, *, mode: str, max_group=None):
    """``rows [R, K]`` grouped by ``sizes [G]`` (module docstring) times ``w
    [G, K, N]``: ``[R, N]`` float32; what rows of no group hold is not a
    result. ``mode``: ``"xla"`` is ``jax.lax.ragged_dot``; ``"pallas"`` and
    ``"interpret"`` the kernel, compiled or through the Pallas interpreter.
    ``max_group`` (static) bounds a group's rows where the caller knows better
    than ``R`` (a token stands in a group once: the tokens), which is what the
    kernel keeps room for in VMEM."""
    sizes = sizes.astype(jnp.int32)
    if mode == "xla":
        return jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=F32)
    out, _ = _stationary(
        _whole_tiles(rows), w, sizes, interpret=(mode == "interpret"),
        max_group=max_group,
    )
    return out[: rows.shape[0]]


@functools.partial(jax.jit, static_argnames=("interpret", "max_group"))
def _stationary_gated(rows, w_in, w_out, sizes, *, interpret, max_group):
    """:func:`gated_experts` through the kernel: ONE jitted call a layer, so
    a program's trace and lowering meet one call site a layer, not two."""
    kw = dict(interpret=interpret, max_group=max_group)
    g, u = jnp.split(_stationary(rows, w_in, sizes, **kw)[0], 2, axis=-1)
    act = (jax.nn.silu(g) * u).astype(rows.dtype)
    return _stationary(act, w_out, sizes, **kw)[0]


def gated_experts(rows, w_in, w_out, sizes, *, mode: str, max_group=None):
    """A gated-SiLU expert layer's two grouped products on sorted ``rows
    [R, d]``: ``silu(gate) * up`` of ``rows x w_in [G, d, 2 f]`` (``[gate,
    up]``), handed on in ``rows``' type, times ``w_out [G, f, d]``: ``[R,
    d]`` float32. ``mode`` and ``max_group`` as :func:`grouped_matmul`'s."""
    sizes = sizes.astype(jnp.int32)
    if mode == "xla":
        g, u = jnp.split(grouped_matmul(rows, w_in, sizes, mode=mode), 2, axis=-1)
        act = (jax.nn.silu(g) * u).astype(rows.dtype)
        return grouped_matmul(act, w_out, sizes, mode=mode)
    out = _stationary_gated(
        _whole_tiles(rows), w_in, w_out, sizes,
        interpret=(mode == "interpret"), max_group=max_group,
    )
    return out[: rows.shape[0]]
