"""PR 35's latent decode kernel, kept word for word as a yardstick: every row
walks its own table alone, in whole blocks. ``ops/paged_attention.py``'s
kernel now serves rows that share pages as a group; on a dispatch with no
sharing it has to give what this one gives (``tests/test_paged_attention.py``).
Nothing but that test runs it."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu.ops.attention import NEG_INF
from distributed_pytorch_tpu.ops.paged_attention import NULL_PAGE


def _kernel(
    bt_ref, lens_ref, q_ref, pool_hbm, o_ref, buf, sems, first_buf, m_scr,
    l_scr, acc_scr, *, npb, v_width, sm_scale,
):
    b = pl.program_id(0)
    slots, pages_per_seq = bt_ref.shape
    h, w = q_ref.shape[1:]
    page = buf.shape[2]
    bkv = npb * page

    def is_live(row):
        return bt_ref[row, 0] != NULL_PAGE

    def page_copy(phys, slot, n):
        return pltpu.make_async_copy(
            pool_hbm.at[phys], buf.at[slot, n], sems.at[slot]
        )

    def start(row, blk, slot):
        last = jnp.minimum(lens_ref[row] // page, pages_per_seq - 1)
        for n in range(npb):
            phys = bt_ref[row, jnp.minimum(blk * npb + n, last)]
            page_copy(phys, slot, n).start()

    def wait(slot):
        for n in range(npb):
            page_copy(0, slot, n).wait()

    @pl.when(jnp.logical_not(is_live(b)))
    def _absent():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(is_live(b))
    def _row():
        pos = lens_ref[b]
        n_blocks = jnp.minimum(pos // bkv + 1, pl.cdiv(pages_per_seq, npb))
        prefetched = jnp.logical_and(b > 0, is_live(jnp.maximum(b - 1, 0)))
        slot0 = jnp.where(prefetched, first_buf[0], 0)

        @pl.when(jnp.logical_not(prefetched))
        def _first():
            start(b, 0, 0)

        next_row = jnp.minimum(b + 1, slots - 1)
        next_live = jnp.logical_and(b + 1 < slots, is_live(next_row))

        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        def block(j, carry):
            slot = (slot0 + j) % 2

            @pl.when(j + 1 < n_blocks)
            def _next_block():
                start(b, j + 1, 1 - slot)

            @pl.when(jnp.logical_and(j + 1 == n_blocks, next_live))
            def _next_row():
                start(next_row, 0, 1 - slot)
                first_buf[0] = 1 - slot

            wait(slot)
            k = buf[slot].reshape(bkv, w)
            q = q_ref[0].astype(k.dtype)
            s_blk = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
            s_blk = jnp.where(kpos <= pos, s_blk, NEG_INF)
            m_prev = m_scr[:, :1]
            l_prev = l_scr[:, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s_blk, axis=-1, keepdims=True)
            )
            p = jnp.exp(s_blk - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[:] = acc_scr[:] * correction + pv
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def parent_latent_attention(
    q, pool, block_tables, seq_lens, *, v_width, pages_per_block, sm_scale
):
    """``q`` [S, 1, H, W] through PR 35's kernel, interpreted."""
    s, _, h, w = q.shape
    page = pool.shape[1]
    npb = int(pages_per_block)

    def row_spec(shape):
        return pl.BlockSpec(
            shape, lambda b, bt, lens: (b,) + (0,) * (len(shape) - 1),
            memory_space=pltpu.VMEM,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[row_spec((1, h, w)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_spec((1, h, v_width)),
        scratch_shapes=[
            pltpu.VMEM((2, npb, page, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, v_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, npb=npb, v_width=v_width, sm_scale=sm_scale
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, v_width), q.dtype),
        interpret=True,
    )(
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        q.reshape(s, h, w), pool,
    )
    return out.reshape(s, 1, h, v_width)
