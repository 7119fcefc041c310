"""The block ``serving/decode_reads.py`` counts a kernel's reads with is the
block that kernel's call was lowered with: both ask the one helper of
``ops/paged_attention.py`` with what the call is handed, the layer's own pool
and the table's width. Lowering only (``tests/lowered_defaults.py``'s toy
models and its way to the decode program); nothing is run."""

import jax
import pytest

import dots3_toy
import lowered_defaults
from distributed_pytorch_tpu.ops import paged_attention as pa


def window_first():
    """The sparse + window toy with a sliding layer FIRST and a sliding rank
    whose pool is wider (256 lanes) than a full layer's (128): the first
    latent pool of the cache tree is no longer the grouping layers'."""
    return dots3_toy.toy_program(dict(
        dots3_toy.TOY, swa_kv_lora_rank=200,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention", "full_attention"]))[1:]


FAMILIES = dict(lowered_defaults.MODELS, sparse_window_first=window_first)
#: family -> the kernels its decode program calls.
KERNELS = {
    "default_block_gqa": {"kv"}, "hybrid_s6": {"kv"},
    "hybrid_mamba2_routed": {"kv"}, "hybrid_gated_delta": {"kv"},
    "latent_routed": {"latent"}, "sparse_window_latent": {"index", "window"},
    "sparse_window_first": {"index", "window"},
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_kernel_is_counted_at_the_block_it_is_lowered_with(
        family, monkeypatch):
    # Another block under every width, so that a block looked up under
    # another pool's shows: K/V heads of 8 and 16 -> 1 page, 128 lanes -> 2,
    # 256 lanes -> 3 (the window's 3 pages whole).
    monkeypatch.setattr(
        pa, "block_pages",
        lambda pages_per_seq, page, width, dtype, pages_per_block=None:
        min(pages_per_seq, 1 + width // 128))
    lowered_with = {}

    def spy(kernel, call):
        def lowered(*operands, pages_per_block, **static):
            # A windowed call alone brings its rows' first keys (an eighth
            # operand).
            name = "window" if kernel == "latent" and len(operands) == 8 \
                else kernel
            lowered_with.setdefault(name, set()).add(pages_per_block)
            return call(*operands, pages_per_block=pages_per_block, **static)
        return lowered

    for kernel, call in (("kv", "_paged_flash"), ("latent", "_latent_flash"),
                         ("index", "_index_flash")):
        monkeypatch.setattr(pa, call, spy(kernel, getattr(pa, call)))
    model, params = FAMILIES[family]()
    engine = lowered_defaults.engine_for(model, params, "interpret")
    lowered_defaults.lower_decode(engine)
    assert set(lowered_with) == KERNELS[family]
    assert engine.reads.blocks == {
        kernel: block for kernel, (block,) in lowered_with.items()}
    # ... and a K/V kernel's call names how it computes a block, which
    # ``stats()`` shows.
    assert engine.reads.forms == {
        kernel: pa.KV_BLOCK_FORM for kernel in KERNELS[family] & {"kv"}}
    assert engine.stats().get("kv_decode_block_form") == (
        pa.KV_BLOCK_FORM if "kv" in KERNELS[family] else None)
    if family == "sparse_window_first":
        pools = [leaf.shape[-1] for path, leaf in
                 jax.tree_util.tree_flatten_with_path(engine.cache)[0]
                 if path[-1].key == "cached_latent"]
        assert pools[:2] == [256, 128]
        # A group shares a block at the GROUPING layers' width, the full
        # layers' 128 lanes, whichever pool the tree begins with.
        assert engine.reads.blocks["window"] == 3
        assert engine.reads.group_pages == 2
    # The gather path is one more plan: no kernel, no block.
    gather = lowered_defaults.engine_for(model, params, False)
    assert gather.reads.blocks == {} and gather.reads.group_pages is None
