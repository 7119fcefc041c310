"""The paged decode path where every query head has a KV head of its own
(1:1 grouping) and the KV heads are no power of two: the kernel's plain form
at those shapes, and the pool's padding rule (``pool_kv_heads``: a count above
8 that is no multiple of 8 is held as the next one) through the model, a
prompt prefilled through the pages and decoded, against the same model's
plain forward pass."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_paged_attention import (  # noqa: E402
    assert_rows_match,
    ragged_problem,
    through,
)

from distributed_pytorch_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    pool_kv_heads,
)


@pytest.mark.parametrize("kv_heads, d, width", [
    (30, 128, 128),  # the published shape: 30 on 30 heads of 128, 2,048 a row
    (32, 128, 128),  # as the pool holds it
    (6, 16, 8),
    (12, 8, 8),
])
def test_the_kernel_at_one_query_head_a_kv_head(kv_heads, d, width):
    problem = ragged_problem(
        [130, None, 0, 37], h=kv_heads, kv_heads=kv_heads, d=d, page=16,
        width=max(width, 9), seed=5)
    kernel, reference = through("fp", npb=4)
    assert_rows_match(
        kernel(*problem), reference(*problem), problem[3], tol=1e-5)


def test_the_pools_padding_rule():
    assert [pool_kv_heads(n) for n in (1, 2, 4, 6, 8, 16, 32)] == [
        1, 2, 4, 6, 8, 16, 32]
    assert pool_kv_heads(30) == 32 and pool_kv_heads(12) == 16
    assert pool_kv_heads(9) == 16 and pool_kv_heads(20) == 24


@pytest.mark.parametrize("heads, kv_heads, kernel", [
    (12, 12, "xla"), (12, 12, "interpret"),  # 1:1, a pool of 16
    (20, 10, "interpret"),  # grouped 2:1 on a padded pool
    (6, 6, "interpret"),  # 1:1, no power of two, not padded
])
def test_prefill_and_decode_through_a_padded_pool(heads, kv_heads, kernel):
    model = TransformerLM(
        vocab_size=128, d_model=heads * 8, n_layers=2, n_heads=heads,
        d_ff=64, n_kv_heads=kv_heads, norm="rmsnorm", mlp="gated_silu",
        use_bias=False, rope=False, qk_norm=True, norm_placement="output")
    tokens = np.random.default_rng(0).integers(1, 128, size=40)
    params = model.init(jax.random.PRNGKey(1), jnp.asarray([tokens]))["params"]
    want = np.asarray(model.apply({"params": params}, jnp.asarray([tokens]))[0])
    slots, page, pages = 2, 16, 4
    decode = model.clone(
        decode=True, page_size=page, num_pages=slots * pages + 1,
        paged_kernel=kernel)
    cache = decode.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32))["cache"]
    pool = cache["block_0"]["attention"]["cached_key"]
    assert pool.shape == (slots * pages + 1, page, pool_kv_heads(kv_heads), 8)
    table = np.zeros((slots, pages), np.int32)
    table[1] = 1 + np.arange(pages)
    run = lambda cache, toks, tables, lens, **kw: decode.apply(  # noqa: E731
        {"params": params, "cache": cache}, jnp.asarray(toks),
        block_tables=jnp.asarray(tables), seq_lens=jnp.asarray(lens),
        mutable=["cache"], **kw)
    piece = np.zeros((1, 32), np.int32)
    piece[0, :25] = tokens[:25]
    _, updated = run(cache, piece, table[1:2], [0], valid_lens=jnp.asarray([25]))
    cache = updated["cache"]
    for t in range(25, 40):
        toks = np.zeros((slots, 1), np.int32)
        toks[1, 0] = tokens[t]
        logits, updated = run(cache, toks, table, [0, t])
        cache = updated["cache"]
        np.testing.assert_allclose(
            np.asarray(logits[1, 0]), want[t], atol=2e-4, rtol=2e-4)
