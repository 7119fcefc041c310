"""The main path's Pallas kernels, compiled by the TPU compiler for a v5e that
is described, not attached (on-chip-measurement guide, section 2.3) — at the
shapes ``chip_smoke.py`` runs them. Interpret mode cannot see what this does:
a block the tiling refuses, or more VMEM than a kernel may use. Nothing is
executed, so this says nothing about results or times; about a second a case.
"""

import functools
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
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


def paged_call(chip, heads, kv_heads, quantized, slots=SLOTS,
               pages_per_seq=PAGES_PER_SEQ, num_pages=None):
    """``(fn, operands)`` of the K/V kernel's call at the device's block."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    num_pages = num_pages or slots * pages_per_seq + 1
    pool = (num_pages, PAGE, kv_heads, HEAD_DIM)
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    scales = (arg(pool[:-1], jnp.float32),) * 2 if quantized else ()

    def fn(q, k_pool, v_pool, tables, lens, k_scale=None, v_scale=None):
        return paged_attention(
            q, k_pool, v_pool, tables, lens, k_scale=k_scale, v_scale=v_scale,
            kernel="pallas", pages_per_block=PAGED_DEFAULT_TABLE[KIND],
        )

    return fn, (
        arg((slots, 1, heads, HEAD_DIM), jnp.bfloat16),
        arg(pool, pool_dtype), arg(pool, pool_dtype),
        arg((slots, pages_per_seq), jnp.int32), arg((slots,), jnp.int32),
        *scales,
    )


def paged_case(chip, **call):
    fn, operands = paged_call(chip, **call)
    return jax.jit(fn).lower(*operands)


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
    # granite-4.0-h-small's 64 slots of 128 pages out of 8,193, 32 query
    # heads on 8, and K-EXAONE's full layers' 32 slots of 800 out of 9,217,
    # 64 on 8 (its window layers' call: ``window-paged-k-exaone`` below).
    "paged-granite-4.0-h-small": functools.partial(
        paged_case, heads=32, kv_heads=8, quantized=False, slots=64,
        pages_per_seq=128, num_pages=8193,
    ),
    "paged-k-exaone-236b-a23b": functools.partial(
        paged_case, heads=64, kv_heads=8, quantized=False, slots=32,
        pages_per_seq=800, num_pages=9217,
    ),
    # zaya1-8b's 96 slots of 192 pages out of 8,193, 8 query heads on 2
    # (StarCoder2's pool geometry at a third of its grouping).
    "paged-zaya1-8b": functools.partial(
        paged_case, heads=8, kv_heads=2, quantized=False, slots=96,
        pages_per_seq=192, num_pages=8193,
    ),
    **{
        f"flash-T{t}-{'grad' if grad else 'fwd'}": functools.partial(
            flash_case, t=t, grad=grad
        )
        for t in (2048, 8192)
        for grad in (False, True)
    },
}


# ------------------------------------------------------------ latent pages
#
# The second kind of page (``models/mla.py``): one pool ``[num_pages, page,
# lanes]`` a layer. How its layout was found, before any run on the chip.

LATENT = dict(slots=32, heads=16, pages_per_seq=1024, num_pages=10241)


def latent_case(chip, width, pages_per_block=None, groups_told=False):
    """``benchmarks/configs/deepseek-v2-lite.json``'s engine: 32 slots of
    1,024 pages out of 10,241, 16 heads on one latent a token. The kernel
    serves rows that share pages as a group, at M = ``GROUP_ROWS`` rows'
    heads at the widest, whoever works the groups out: its own wrapper, or
    (``groups_told``) the caller, as the engine's decode program does."""
    from distributed_pytorch_tpu.ops.paged_attention import (
        paged_latent_attention,
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn = functools.partial(
        paged_latent_attention, v_width=512, kernel="pallas",
        pages_per_block=pages_per_block or PAGED_DEFAULT_TABLE[KIND],
        sm_scale=0.114721,
    )
    rows = arg((LATENT["slots"],), jnp.int32)
    told = {"row_groups": (rows, rows)} if groups_told else {}
    return jax.jit(fn).lower(
        arg((LATENT["slots"], 1, LATENT["heads"], width), jnp.bfloat16),
        arg((LATENT["num_pages"], PAGE, width), jnp.bfloat16),
        arg((LATENT["slots"], LATENT["pages_per_seq"]), jnp.int32), rows,
        **told,
    )


def latent_program(chip, t_step, layers=2):
    """A serving program of the ``deepseek-v2-lite`` configuration at its own
    shapes (published widths, the cell's engine), lowered for the described
    chip on abstract operands: the decode step over all 32 slots (``t_step``
    1, through the Pallas kernel) or a prefill piece of ``t_step`` tokens.
    ``layers`` of the 27: the dense layer and the first expert layers, which
    is every shape the compiler is asked about (the whole depth is compiled
    by hand, for its memory: PERF.md section 6)."""
    import json

    from deepseek_toy import ROOT, driver, reference

    with open(os.path.join(
            ROOT, "benchmarks", "configs", "deepseek-v2-lite.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=layers)
    engine = cfg["assumed"]["engine"]

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    weights = jax.eval_shape(lambda: reference.make_weights(cfg, 0))
    model, params = driver.build_program(cfg, weights)
    decode_model = model.clone(
        decode=True, page_size=engine["page_size"],
        num_pages=engine["num_pages"], paged_kernel="pallas")
    rows = engine["max_slots"] if t_step == 1 else 1
    cache = jax.eval_shape(
        decode_model.init, jax.random.PRNGKey(0),
        jnp.zeros((rows, 1), jnp.int32))["cache"]
    pages_per_seq = engine["max_seq_len"] // engine["page_size"]

    def run(params, cache, tokens, tables, lens, valid):
        kw = {"valid_lens": valid}
        if t_step == 1:  # what the engine's decode program tells the model
            from distributed_pytorch_tpu.serving.decode_reads import (
                DecodeReads,
            )

            kw = DecodeReads(
                decode_model, cache, max_slots=rows,
                pages_per_seq=pages_per_seq).operands(tables, lens)
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache}, tokens, block_tables=tables,
            seq_lens=lens, state_slots=jnp.arange(rows, dtype=jnp.int32),
            mutable=["cache", "routing"], **kw)
        return logits[:, -1], updated["cache"]

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    return jax.jit(run, donate_argnums=(1,)).lower(
        abstract(params), abstract(cache), arg((rows, t_step)),
        arg((rows, pages_per_seq)), arg((rows,)), arg((rows,)))


CASES.update({
    **{f"latent-640-npb{npb}": functools.partial(
        latent_case, width=640, pages_per_block=npb)
       for npb in (16, 64, PAGED_DEFAULT_TABLE[(KIND, 640)])},
    "latent-640-groups-told": functools.partial(
        latent_case, width=640,
        pages_per_block=PAGED_DEFAULT_TABLE[(KIND, 640)], groups_told=True),
    "latent-cell-decode": functools.partial(latent_program, t_step=1),
    "latent-cell-prefill-64": functools.partial(latent_program, t_step=64),
    "latent-cell-prefill-512": functools.partial(latent_program, t_step=512),
})


# ------------------------------------------- learned sparse attention, windows
#
# ``benchmarks/configs/dots3-note-prev.json``'s engine: 32 slots of 3,104
# pages out of 18,433, three pool widths under one table. The three kernels
# that configuration added (``ops/paged_attention.py``) and its serving
# programs, at their own shapes.

SPARSE = dict(slots=32, pages_per_seq=3104, num_pages=18433, top_k=2048)


def sparse_kernel_case(chip, which, groups_told=False):
    """One of the three kernels at the cell's shapes. The index kernel serves
    rows that share pages as a group (M = ``GROUP_ROWS`` x 64 heads at the
    widest, the whole ``[32, 97, 512]`` result in VMEM), whoever works the
    groups out: its own wrapper, or (``groups_told``) the caller."""
    from distributed_pytorch_tpu.ops import paged_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    s = SPARSE
    tables = arg((s["slots"], s["pages_per_seq"]), jnp.int32)
    lens = arg((s["slots"],), jnp.int32)
    if which == "index":  # 64 index heads of 128 on one 128-wide key a token
        told = {"row_groups": (lens, lens)} if groups_told else {}
        return jax.jit(
            functools.partial(pa.paged_index_scores, kernel="pallas")
        ).lower(
            arg((s["slots"], 64, 128), jnp.bfloat16),
            arg((s["slots"], 64), jnp.float32),
            arg((s["num_pages"], PAGE, 128), jnp.bfloat16), tables, lens,
            **told)
    if which == "sparse":  # 128 heads over 2,048 selected latents of 640
        return jax.jit(functools.partial(
            pa.sparse_latent_attention, v_width=512, kernel="pallas",
            sm_scale=0.0721688,
        )).lower(
            arg((s["slots"], 1, 128, 640), jnp.bfloat16),
            arg((s["num_pages"], PAGE, 640), jnp.bfloat16), tables,
            arg((s["slots"], s["top_k"]), jnp.int32),
            arg((s["slots"], s["top_k"]), jnp.bool_))
    if which == "positions":  # 2,048 of a table of 49,664 tokens a row
        return jax.jit(functools.partial(
            pa.selected_positions, k=s["top_k"], kernel="pallas",
        )).lower(arg((s["slots"], s["pages_per_seq"] * PAGE), jnp.bool_))
    # 64 heads on a latent of 1,088 held as 1,152, a window of 513
    return jax.jit(functools.partial(
        pa.paged_latent_attention, v_width=1024, kernel="pallas",
        sm_scale=0.0625, window=513,
    )).lower(
        arg((s["slots"], 1, 64, 1152), jnp.bfloat16),
        arg((s["num_pages"], PAGE, 1152), jnp.bfloat16), tables, lens)


def sparse_program(chip, t_step):
    """A serving program of the ``dots3-note-prev`` configuration at its own
    shapes, lowered for the described chip on abstract operands, as
    ``latent_program`` lowers ``deepseek-v2-lite``'s: the decode step over
    all 32 slots or a prefill piece of ``t_step`` tokens. Layers 0-2 of the
    six: full + dense, full + experts, sliding + experts, which is every
    shape the compiler is asked about."""
    import json

    from dots3_toy import ROOT, driver, reference

    with open(os.path.join(
            ROOT, "benchmarks", "configs", "dots3-note-prev.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, num_hidden_layers=3, layer_types=cfg["layer_types"][:3])
    engine = cfg["assumed"]["engine"]

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    weights = jax.eval_shape(lambda: reference.make_weights(cfg, 0))
    model, params = driver.build_program(cfg, weights)
    decode_model = model.clone(
        decode=True, page_size=engine["page_size"],
        num_pages=engine["num_pages"], paged_kernel="pallas")
    rows = engine["max_slots"] if t_step == 1 else 1
    cache = jax.eval_shape(
        decode_model.init, jax.random.PRNGKey(0),
        jnp.zeros((rows, 1), jnp.int32))["cache"]
    pages_per_seq = engine["max_seq_len"] // engine["page_size"]

    def run(params, cache, tokens, tables, lens, valid):
        kw = {"valid_lens": valid}
        if t_step == 1:  # what the engine's decode program tells the model
            from distributed_pytorch_tpu.serving.decode_reads import (
                DecodeReads,
            )

            kw = DecodeReads(
                decode_model, cache, max_slots=rows,
                pages_per_seq=pages_per_seq).operands(tables, lens)
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache}, tokens, block_tables=tables,
            seq_lens=lens, state_slots=jnp.arange(rows, dtype=jnp.int32),
            mutable=["cache", "routing", "selection"], **kw)
        return logits[:, -1], updated["cache"]

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    return jax.jit(run, donate_argnums=(1,)).lower(
        abstract(params), abstract(cache), arg((rows, t_step)),
        arg((rows, pages_per_seq)), arg((rows,)), arg((rows,)))


CASES.update({
    **{f"sparse-kernel-{which}": functools.partial(
        sparse_kernel_case, which=which)
       for which in ("index", "sparse", "window", "positions")},
    "sparse-kernel-index-groups-told": functools.partial(
        sparse_kernel_case, which="index", groups_told=True),
    "sparse-cell-decode": functools.partial(sparse_program, t_step=1),
    "sparse-cell-prefill-64": functools.partial(sparse_program, t_step=64),
    "sparse-cell-prefill-512": functools.partial(sparse_program, t_step=512),
})


@functools.lru_cache(maxsize=None)
def compiled_text(chip, name):
    """A case's compiled text, compiled once for the tests that read it."""
    return CASES[name](chip).compile().as_text()


def test_the_new_kernels_are_named_for_the_benchmarks_readers(chip):
    """``benchmarks/harness/dsa.py`` tells three kernels by their names (the
    positions' by its result's sizes: the test below)."""
    from distributed_pytorch_tpu.ops import paged_attention as pa

    for which, name in (("index", pa.INDEX_KERNEL), ("sparse", pa.SPARSE_KERNEL),
                        ("window", pa.WINDOW_KERNEL),
                        ("positions", pa.POSITIONS_KERNEL)):
        text = compiled_text(chip, f"sparse-kernel-{which}")
        calls = [line.split(" = ", 1)[0] for line in text.splitlines()
                 if "tpu_custom_call" in line]
        assert any(name in call for call in calls), (which, calls)


def test_the_position_search_is_still_read_as_selection_and_gathers_nothing(
        chip):
    """The decode program of the cell: ``benchmarks/harness/dsa.py`` counts
    every instruction of the position search (a result with a size of the
    2,048 selected or of the 32 x 2,048, other than the latents' gather) as
    ``select``, the kernel's among them, so ``dsa.device_ms_per_step`` and
    ``dsa.sparse_decode_roofline_share`` read what they read; and no gather
    of the blocks' running counts is left, 65,536 rows of 128."""
    import json
    import re

    from dots3_toy import ROOT
    from distributed_pytorch_tpu.ops import paged_attention as pa

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import dsa

    with open(os.path.join(
            ROOT, "benchmarks", "configs", "dots3-note-prev.json")) as f:
        cfg = json.load(f)
    s = dsa.sizes(cfg)
    rows, top_k = SPARSE["slots"], SPARSE["top_k"]
    assert s["topk"] == top_k and s["full"] == 640
    text = compiled_text(chip, "sparse-cell-decode")
    entry = text[text.index("\nENTRY "):]
    searched = {}
    for line in entry.splitlines():
        line = line.strip()
        if " = " not in line or line.startswith("ROOT"):
            continue
        dims = dsa.result_shapes(line)
        if any({top_k, rows * top_k} & set(d) for d in dims):
            searched[line.split(" = ", 1)[0]] = (dsa.kind_of(line, s), dims)
    kinds = {kind for kind, _ in searched.values()}
    assert kinds == {"select", "gather"}, searched
    for name, (kind, dims) in searched.items():
        is_latents = any(d and d[-1] == s["full"] for d in dims)
        assert kind == ("gather" if is_latents else "select"), (name, dims)
    kernels = [n for n in searched if pa.POSITIONS_KERNEL in n]
    assert len(kernels) == 2, searched  # the two full layers of the three
    assert not re.search(
        rf"s32\[({rows},{top_k}|{rows * top_k}),128\]\S* gather\(", text)


# ------------------------------------------------ the experts' grouped product
#
# ``ops/grouped_matmul.py``'s weight-stationary kernel at the shapes of the two
# cells that run ``RoutedExperts``: both products of a decode program (64 rows
# x top 10 on 36 held experts of 4096 x 2*768; 32 x top 6 on 8 of 2048 x
# 2*1408) and of the widest prefill piece, 512 tokens (its rows' room in VMEM
# is what the compiler is asked about there).

GROUPED = {
    "granite": dict(held=36, top_k=10, d=4096, f=768, slots=64),
    "deepseek": dict(held=8, top_k=6, d=2048, f=1408, slots=32),
}


def grouped_case(chip, model, tokens, second):
    from distributed_pytorch_tpu.ops.grouped_matmul import grouped_matmul

    m = GROUPED[model]
    k, n = (m["f"], m["d"]) if second else (m["d"], 2 * m["f"])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn = functools.partial(grouped_matmul, mode="pallas", max_group=tokens)
    return jax.jit(fn).lower(
        arg((tokens * m["top_k"], k), jnp.bfloat16),
        arg((m["held"], k, n), jnp.bfloat16), arg((m["held"],), jnp.int32),
    )


CASES.update({
    f"grouped-{model}-{'decode' if tokens is None else f'prefill-{tokens}'}-"
    f"{'out' if second else 'in'}": functools.partial(
        grouped_case, model=model, tokens=tokens or GROUPED[model]["slots"],
        second=second)
    for model in GROUPED for tokens in (None, 512) for second in (False, True)
})


def test_the_grouped_product_is_named_for_the_benchmarks_readers(chip):
    """``benchmarks/harness/moe_hybrid.py`` counts an instruction whose NAME
    holds ``ragged-dot`` to the expert layers: the kernel's does."""
    text = grouped_case(chip, "granite", 64, False).compile().as_text()
    call = next(line for line in text.splitlines() if "tpu_custom_call" in line)
    assert "ragged-dot" in call.split(" = ", 1)[0]


# ------------------------------------------- the gated delta rule, 30 KV heads
#
# ``benchmarks/configs/olmo-hybrid-7b.json``'s engine: 96 slots of 128 pages
# out of 3,073, a matrix state ``[15, 96, 384]`` float32 a slot and layer (two
# heads of 96 x 192 side by side on the lanes) and K/V pools of 32 heads for
# the model's 30. The decode kernel of ``ops/linear_attention.py``, the paged
# kernel at one query head a KV head, and the cell's serving programs.

OLMO = dict(slots=96, heads=30, d_k=96, d_v=192, pages_per_seq=128,
            num_pages=3073)


def gdn_step_case(chip):
    from distributed_pytorch_tpu.ops import linear_attention as la

    b, h, dk, dv = OLMO["slots"], OLMO["heads"], OLMO["d_k"], OLMO["d_v"]
    pack = la.lane_pack(h, dv)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn = functools.partial(la.gated_delta_step, pack=pack, kernel="pallas")
    return jax.jit(fn, donate_argnums=(5,)).lower(
        arg((b, h, dk)), arg((b, h, dk)), arg((b, h, dv)), arg((b, h)),
        arg((b, h)), arg((b, h // pack, dk, pack * dv)), arg((b,), jnp.int32))


def olmo_program(chip, t_step, layers=4):
    """A serving program of the ``olmo-hybrid-7b`` configuration at its own
    shapes (published widths, the cell's engine), lowered for the described
    chip on abstract operands: the decode step over all 96 slots (``t_step``
    1, through both kernels) or a prefill piece of ``t_step`` tokens.
    ``layers`` of the 16: one whole period, every shape the compiler is asked
    about (the whole depth is compiled by hand, for its memory: PERF.md 6)."""
    import json

    from hybrid_toy import ROOT, load_by_path

    reference = load_by_path("benchmarks/reference/olmo_hybrid.py")
    driver = load_by_path("benchmarks/drivers/serve_linear_hybrid.py")
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "olmo-hybrid-7b.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=layers)
    engine = cfg["assumed"]["engine"]

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    weights = jax.eval_shape(lambda: reference.make_weights(cfg, 0))
    model, params = driver.build_program(cfg, weights)
    decode_model = model.clone(
        decode=True, page_size=engine["page_size"],
        num_pages=engine["num_pages"], paged_kernel="pallas")
    slots = engine["max_slots"]
    rows = slots if t_step == 1 else 1
    cache = jax.eval_shape(
        decode_model.init, jax.random.PRNGKey(0),
        jnp.zeros((slots, 1), jnp.int32))["cache"]
    pages_per_seq = engine["max_seq_len"] // engine["page_size"]

    def run(params, cache, tokens, tables, lens, valid, state_slots):
        kw = {} if t_step == 1 else {"valid_lens": valid}
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache}, tokens, block_tables=tables,
            seq_lens=lens, state_slots=state_slots, mutable=["cache"], **kw)
        return logits[:, -1], updated["cache"]

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    return jax.jit(run, donate_argnums=(1,)).lower(
        abstract(params), abstract(cache), arg((rows, t_step)),
        arg((rows, pages_per_seq)), arg((rows,)), arg((rows,)), arg((rows,)))


CASES.update({
    "gdn-step-96x30x96x192": gdn_step_case,
    # The pool holds 32 heads for the model's 30 (``pool_kv_heads``).
    "paged-olmo-hybrid-7b": functools.partial(
        paged_case, heads=32, kv_heads=32, quantized=False,
        slots=OLMO["slots"], pages_per_seq=OLMO["pages_per_seq"],
        num_pages=OLMO["num_pages"]),
    "olmo-cell-decode": functools.partial(olmo_program, t_step=1),
})


@pytest.mark.parametrize("t_step", [64, 512])
def test_the_olmo_cells_prefill_programs_compile_for_v5e(chip, t_step):
    """The blocked form is plain XLA operations (a unit-triangular solve a
    block among them) and a prefill piece's full layers walk their blocks:
    no kernel to look for, so what is held is that the compiler takes the
    program, and what it needs beside the weights and the pools."""
    compiled = olmo_program(chip, t_step).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_the_gated_delta_kernel_is_named_and_updates_the_states_in_place(chip):
    """``benchmarks/harness/linear.py`` tells the decode kernel by its name;
    the slot table's states (212 MB a layer) alias the kernel's result."""
    from distributed_pytorch_tpu.ops.linear_attention import STEP_KERNEL

    compiled = gdn_step_case(chip).compile()
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line)
    assert STEP_KERNEL in call.split(" = ", 1)[0]
    memory = compiled.memory_analysis()
    state = 96 * 15 * 96 * 384 * 4
    assert memory.alias_size_in_bytes == state
    assert memory.temp_size_in_bytes < state // 8


def whole_pool_copies(compiled, num_pages):
    import re

    return [line for line in compiled.as_text().splitlines()
            if re.search(rf" = bf16\[{num_pages},[\d,]*\]\S* copy\(", line)]


def test_thirty_kv_heads_are_held_as_thirty_two(chip, monkeypatch, t_step=1):
    """Why ``pool_kv_heads`` pads: a pool ``[pages, 16, 30, 128]`` bf16 is
    copied WHOLE, there and back, round every write and kernel call (the
    compiler keeps it in a layout of its own); as 32 heads it is copied
    nowhere. One period of the configuration, its decode step (a prefill
    piece shows the same: four copies a full layer)."""
    from distributed_pytorch_tpu.models import transformer

    padded = olmo_program(chip, t_step, layers=4).compile()
    assert not whole_pool_copies(padded, OLMO["num_pages"])
    monkeypatch.setattr(transformer, "pool_kv_heads", lambda n: n)
    plain = olmo_program(chip, t_step, layers=4).compile()
    copies = whole_pool_copies(plain, OLMO["num_pages"])
    assert len(copies) >= 4, len(copies)
    assert (plain.memory_analysis().temp_size_in_bytes
            > padded.memory_analysis().temp_size_in_bytes + 300e6)


def test_a_latent_pool_of_576_is_refused_by_mosaic(chip):
    """Why the pool's rows are 640 wide: the chip stores a ``[.., 576]`` bf16
    array in tiles of 128 lanes (640 a token in HBM whatever the shape says),
    and Mosaic will not copy a page out of it."""
    with pytest.raises(Exception, match="aligned to tiling"):
        latent_case(chip, width=576).compile()


# ---------------------------------------------------------- a window group
#
# ``k-exaone-236b-a23b`` (PR 46): K/V layers with a window of 128 on block
# tables of their own. The windowed call at the cell's shapes (32 rows, 64
# query heads on 8 KV heads of 128, short tables of 9 pages over the window
# group's ``[513, 16, 8, 128]`` pools), and the cell's programs over one
# period of its layers (a dense layer and three sparse ones; three window
# layers and a full one).

EXAONE = dict(slots=32, heads=64, kv_heads=8, window=128, window_pages=513)


def window_paged_case(chip):
    from distributed_pytorch_tpu.ops.paged_attention import (
        paged_window_attention,
        window_pages,
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    s = EXAONE
    pool = (s["window_pages"], PAGE, s["kv_heads"], HEAD_DIM)
    fn = functools.partial(
        paged_window_attention, window=s["window"], kernel="pallas",
        pages_per_block=PAGED_DEFAULT_TABLE[KIND])
    return jax.jit(fn).lower(
        arg((s["slots"], 1, s["heads"], HEAD_DIM), jnp.bfloat16),
        arg(pool, jnp.bfloat16), arg(pool, jnp.bfloat16),
        arg((s["slots"], window_pages(s["window"], PAGE)), jnp.int32),
        arg((s["slots"],), jnp.int32))


def exaone_program(chip, t_step, layers=4):
    """A serving program of the ``k-exaone-236b-a23b`` configuration at its
    own shapes (published widths, the cell's engine and BOTH its table
    groups), lowered for the described chip on abstract operands: the decode
    step over all 32 slots (``t_step`` 1, through both K/V kernels and the
    experts') or a prefill piece of ``t_step`` tokens. ``layers`` of the 8:
    one whole period (the whole depth compiled by hand: PERF.md 4)."""
    import json

    from hybrid_toy import ROOT, load_by_path

    from distributed_pytorch_tpu.ops.paged_attention import window_group_pages

    reference = load_by_path("benchmarks/reference/exaone_moe.py")
    driver = load_by_path("benchmarks/drivers/serve_window_moe.py")
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, num_hidden_layers=layers, **{
        key: cfg[key][:layers]
        for key in ("layer_types", "mlp_layer_types", "sliding_windows")})
    engine = cfg["assumed"]["engine"]

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    weights = jax.eval_shape(lambda: reference.make_weights(cfg, 0))
    model, params = driver.build_program(cfg, weights)
    decode_model = model.clone(
        decode=True, page_size=engine["page_size"],
        num_pages=engine["num_pages"],
        window_num_pages=engine["window_pages"], paged_kernel="pallas")
    slots = engine["max_slots"]
    rows = slots if t_step == 1 else 1
    cache = jax.eval_shape(
        decode_model.init, jax.random.PRNGKey(0),
        jnp.zeros((slots, 1), jnp.int32))["cache"]
    pages_per_seq = engine["max_seq_len"] // engine["page_size"]
    short = window_group_pages(
        cfg["sliding_window"], engine["page_size"],
        1 if t_step == 1 else engine["max_prefill_chunk"])

    def run(params, cache, tokens, tables, lens, valid, window_tables, slots):
        kw = {} if t_step == 1 else {"valid_lens": valid}
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache}, tokens, block_tables=tables,
            seq_lens=lens, window_tables=window_tables, state_slots=slots,
            mutable=["cache", "routing"], **kw)
        return logits[:, -1], updated["cache"]

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    return jax.jit(run, donate_argnums=(1,)).lower(
        abstract(params), abstract(cache), arg((rows, t_step)),
        arg((rows, pages_per_seq)), arg((rows,)), arg((rows,)),
        arg((rows, short)), arg((rows,)))


CASES.update({
    "window-paged-k-exaone": window_paged_case,
    "k-exaone-cell-decode": functools.partial(exaone_program, t_step=1),
})


def test_the_windowed_call_is_named_and_copies_no_pool(chip):
    """``benchmarks/harness/window.py`` tells the window layers' calls from
    the full layers' by the kernel's name; a row's 9 pages are ONE block (the
    scratch holds two buffers of 9 pages a pool); neither pool is copied."""
    from distributed_pytorch_tpu.ops.paged_attention import KV_WINDOW_KERNEL

    compiled = window_paged_case(chip).compile()
    text = compiled.as_text()
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line)
    assert KV_WINDOW_KERNEL in call.split(" = ", 1)[0]
    assert not whole_pool_copies(compiled, EXAONE["window_pages"])
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


def test_the_exaone_cells_decode_program_calls_both_kernels(chip):
    """Three calls of the windowed kernel and one of the full layers' in a
    period, the experts' products beside them, and no pool of either group
    copied whole."""
    compiled = exaone_program(chip, 1).compile()
    names = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert sum(n.startswith("attention._window_paged_decode_step")
               for n in names) == 3
    assert sum(n.startswith("attention._paged_decode_step")
               for n in names) == 1
    assert sum(n.startswith("ragged-dot-stationary") for n in names) == 6
    assert not whole_pool_copies(compiled, 513)
    assert not whole_pool_copies(compiled, 9217)
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("t_step", [64, 512])
def test_the_exaone_cells_prefill_programs_compile_for_v5e(chip, t_step):
    """A piece attends in plain XLA in both groups (the window layers
    through the gather path over their short table of 41 pages, the full
    layers as a walk over blocks of 512 keys of the 12,800-key table): no
    attention kernel, the experts' products, and the widest piece under
    300 MB beside the weights and the pools (225 MB compiled; 887 MB while
    the full layers scored the whole table at once, before PR 47)."""
    compiled = exaone_program(chip, t_step).compile()
    names = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert names and all(n.startswith("ragged-dot-stationary") for n in names)
    assert not whole_pool_copies(compiled, 513)
    assert not whole_pool_copies(compiled, 9217)
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6


# ``zaya1-8b`` (PR 49): every layer compressed convolutional attention (K and V
# pages AND a two-token slot state) and ONE expert of 16 by a router network.


def zaya_program(chip, t_step, layers=2):
    """A serving program of the ``zaya1-8b`` configuration at its own shapes
    (published widths, all 16 experts, the 262,272-row tied head, the cell's
    engine), lowered for the described chip on abstract operands: the decode
    step over all 96 slots (``t_step`` 1, greedy over the whole vocabulary)
    or a prefill piece of ``t_step`` tokens. ``layers`` of the 20 (the whole
    depth compiled by hand: PERF.md 4)."""
    import json

    from hybrid_toy import ROOT, load_by_path

    reference = load_by_path("benchmarks/reference/zaya.py")
    driver = load_by_path("benchmarks/drivers/serve_cca_moe.py")
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "zaya1-8b.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, num_hidden_layers=layers,
               layer_types=cfg["layer_types"][:layers])
    engine = cfg["assumed"]["engine"]

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    weights = jax.eval_shape(lambda: reference.make_weights(cfg, 0))
    model, params = driver.build_program(cfg, weights)
    decode_model = model.clone(
        decode=True, page_size=engine["page_size"],
        num_pages=engine["num_pages"], paged_kernel="pallas")
    slots = engine["max_slots"]
    rows = slots if t_step == 1 else 1
    cache = jax.eval_shape(
        decode_model.init, jax.random.PRNGKey(0),
        jnp.zeros((slots, 1), jnp.int32))["cache"]

    def run(params, cache, tokens, tables, lens, valid, slots):
        kw = {} if t_step == 1 else {"valid_lens": valid}
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache}, tokens, block_tables=tables,
            seq_lens=lens, state_slots=slots, mutable=["cache", "routing"],
            **kw)
        if t_step == 1:
            return jnp.argmax(logits[:, -1], axis=-1), updated["cache"]
        return updated["cache"]  # a piece samples nothing: no head

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    return jax.jit(run, donate_argnums=(1,)).lower(
        abstract(params), abstract(cache), arg((rows, t_step)),
        arg((rows, engine["max_seq_len"] // engine["page_size"])),
        arg((rows,)), arg((rows,)), arg((rows,)))


def kernel_names(compiled):
    return [line.split(" = ", 1)[0].strip().lstrip("%")
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line]


def test_the_zaya_cells_decode_program_calls_the_kernels_it_has(chip):
    """A layer calls the K/V decode kernel once (under the name the
    benchmark's readers tell it by) and the experts' weight-stationary
    product twice, at 96 rows on ONE expert each; neither pool is copied
    whole, the slot state is updated in place, and beside weights and pools
    the program needs under 60 MB (the float32 logits ``[96, 262272]`` are
    never whole under a greedy choice: 6 MB compiled at two layers, 40 MB at
    all twenty, by hand)."""
    compiled = zaya_program(chip, 1).compile()
    names = kernel_names(compiled)
    assert sum(n.startswith("attention._paged_decode_step") for n in names) == 2
    assert sum(n.startswith("ragged-dot-stationary") for n in names) == 4
    assert not whole_pool_copies(compiled, 8193)
    assert compiled.memory_analysis().temp_size_in_bytes < 60e6


@pytest.mark.parametrize("t_step", [64, 512])
def test_the_zaya_cells_prefill_programs_compile_for_v5e(chip, t_step):
    """A piece's convolutions, shift and walk over its blocks are plain XLA:
    the only kernels are the experts' products; the widest piece needs under
    200 MB beside weights and pools (145 MB at all twenty layers, by hand)."""
    compiled = zaya_program(chip, t_step).compile()
    names = kernel_names(compiled)
    assert names and all(n.startswith("ragged-dot-stationary") for n in names)
    assert not whole_pool_copies(compiled, 8193)
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("name", [
    "paged-sc2-3b", "paged-jamba2-3b", "paged-granite-4.0-h-small",
    "paged-k-exaone-236b-a23b", "paged-olmo-hybrid-7b", "paged-H16-Hkv8-int8",
    "paged-zaya1-8b",
])
def test_a_block_is_computed_on_its_tiles_as_stored(chip, name):
    """The K/V kernel's body (its jaxpr) at the cells' geometries: both
    products take the pool's bf16 rows as copied, ``[block tokens * Hkv,
    128]`` (int8 pages: cast to the queries' bf16), no tile is transposed,
    and no float32 array is as large as a tile: the largest is the block's
    score tile ``[H, block tokens * Hkv]``, H under 128."""
    import re

    fn, operands = paged_call(chip, **CASES[name].keywords)
    heads, kv_heads = operands[0].shape[2], operands[1].shape[2]
    rows = PAGED_DEFAULT_TABLE[KIND] * PAGE * kv_heads
    text = str(jax.make_jaxpr(fn)(*operands))
    kernel = text[text.index("pallas_call"):]
    assert "transpose" not in kernel
    assert kernel.count("= dot_general[") == 2
    floats = {tuple(map(int, dims.split(",")))
              for dims in re.findall(r"f32\[([\d,]+)\]", kernel)}
    assert max(floats, key=math.prod) == (heads, rows)
    assert f"bf16[{rows},{HEAD_DIM}]" in kernel
    assert f"f32[{rows},{HEAD_DIM}]" not in kernel


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    assert "tpu_custom_call" in compiled_text(chip, name)
