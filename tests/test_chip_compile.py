"""The main path's Pallas kernels, compiled by the TPU compiler for a v5e that
is described, not attached (on-chip-measurement guide, section 2.3) — at the
shapes ``chip_smoke.py`` runs them. Interpret mode cannot see what this does:
a block the tiling refuses, or more VMEM than a kernel may use. Nothing is
executed, so this says nothing about results or times; about a second a case.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from distributed_pytorch_tpu.ops.flash_attention import flash_attention_4d
from distributed_pytorch_tpu.ops.flash_autotune import (
    DEFAULT_TABLE,
    PAGED_DEFAULT_TABLE,
)
from distributed_pytorch_tpu.ops.paged_attention import paged_attention

KIND = "tpu v5 lite"
# chip_smoke.py's engine: 8 slots, 16 heads of 128, max_seq_len 2048 in
# pages of 16.
SLOTS, HEADS, HEAD_DIM, PAGE, PAGES_PER_SEQ = 8, 16, 128, 16, 128


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip to compile for, with the persistent compile
    cache off: an executable compiled here cannot be read back without a
    chip, and every later compile would warn about it."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def paged_case(chip, heads, kv_heads, quantized, slots=SLOTS,
               pages_per_seq=PAGES_PER_SEQ, num_pages=None):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    num_pages = num_pages or slots * pages_per_seq + 1
    pool = (num_pages, PAGE, kv_heads, HEAD_DIM)
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    scale = arg(pool[:-1], jnp.float32) if quantized else None
    fn = functools.partial(
        paged_attention, kernel="pallas",
        pages_per_block=PAGED_DEFAULT_TABLE[KIND],
    )
    return jax.jit(fn).lower(
        arg((slots, 1, heads, HEAD_DIM), jnp.bfloat16),
        arg(pool, pool_dtype), arg(pool, pool_dtype),
        arg((slots, pages_per_seq), jnp.int32), arg((slots,), jnp.int32),
        k_scale=scale, v_scale=scale,
    )


def flash_case(chip, t, grad):
    block_q, block_k = DEFAULT_TABLE[KIND][(t, HEAD_DIM)]
    qkv = jax.ShapeDtypeStruct(
        (1, t, HEADS, HEAD_DIM), jnp.bfloat16, sharding=chip
    )

    def attend(q, k, v):
        return flash_attention_4d(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            interpret=False,
        )

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else attend
    return jax.jit(fn).lower(qkv, qkv, qkv)


CASES = {
    # (query heads, KV heads): the smoke's model, its Hkv=4 sibling, what
    # one of four tensor-parallel shards of the smoke's model holds, and a
    # grouping that is no multiple of the sublane count: 20 query heads on
    # one KV head (the benchmark's hybrid configuration's attention layers).
    **{
        f"paged-H{h}-Hkv{kv}-{'int8' if quant else 'bf16'}": functools.partial(
            paged_case, heads=h, kv_heads=kv, quantized=quant
        )
        for h, kv in ((16, 8), (16, 4), (4, 2), (20, 1))
        for quant in (False, True)
    },
    # The benchmark's engines, whole: StarCoder2-3B's 32 slots of 256 pages
    # out of 12,288, and Jamba2-3B's 128 slots of 128 out of 16,385. The
    # kernel copies pages out of the pool itself, so the pool's size and the
    # table's width are part of what the compiler is asked.
    "paged-sc2-3b": functools.partial(
        paged_case, heads=24, kv_heads=2, quantized=False, slots=32,
        pages_per_seq=256, num_pages=12288,
    ),
    "paged-jamba2-3b": functools.partial(
        paged_case, heads=20, kv_heads=1, quantized=False, slots=128,
        pages_per_seq=128, num_pages=16385,
    ),
    **{
        f"flash-T{t}-{'grad' if grad else 'fwd'}": functools.partial(
            flash_case, t=t, grad=grad
        )
        for t in (2048, 8192)
        for grad in (False, True)
    },
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    compiled = CASES[name](chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
