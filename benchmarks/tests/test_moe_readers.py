"""The readers of a ``serve_hybrid_moe`` cell's per-layer metrics, on a
recorded trace: ``data/moe_trace_recorded.json`` is 150 ms of
``granite-4.0-h-small-chat``'s traced window on the v5e (PR 31), cut by
``record_moe_trace.py``: three decode programs and several prefill chunks,
whole operation names. What is pinned is HOW the Mamba-2 mixers' and the
expert layers' operations are recognised (``harness/moe_hybrid.py``), and the
readers' arithmetic."""

import importlib.util
import json
import os
import types

import pytest

from harness import hybrid, moe_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(cfg):
    return moe_hybrid.sizes(cfg)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "moe_trace_recorded.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("text, kind", [
    # the grouped products, named by the compiler, and their metadata kernel
    ("%ragged-dot-none.7 = f32[640,1536] custom-call(...)", "moe"),
    ("%ragged-dot-none.12 = f32[160,4096] custom-call(...)", "moe"),
    ("%ragged-dot-metadata.3 = (s32[37], s32[40], s32[40], s32[1]) "
     "custom-call(...)", "moe"),
    # the router's scores sorted for the top 10 of 72, a decode step
    ("%sort.9 = (f32[64,72], s32[64,72]) sort(...)", "moe"),
    # the pairs sorted by expert, the gathered rows, the weighted rows
    ("%sort.21 = (s32[640], s32[640]) sort(...)", "moe"),
    ("%fusion.88 = bf16[640,4096] fusion(...)", "moe"),
    ("%multiply_select_fusion.4 = f32[640,4096] fusion(...)", "moe"),
    ("%fusion.31 = f32[640] fusion(...)", "moe"),
    # a two-token chunk: 20 pairs, the product as a mask a held expert
    ("%fusion.61 = f32[20,1536] fusion(...)", "moe"),
    ("%output_mask_fusion.2 = pred[36,20,1] fusion(...)", "moe"),
    # the counts a routed and a held expert
    ("%fusion.70 = s32[73] fusion(...)", "moe"),
    ("%fusion.71 = s32[37] fusion(...)", "moe"),
    # the decode step's fused update of every slot's state [64, H, P, N]
    ("%fusion.24 = (f32[64,128,64,128], f32[64,128,64]) fusion(...)", "ssd"),
    # the conv's tail [.., K-1, H P + 2 G N]
    ("%dynamic-update-slice.52 = bf16[64,3,8448] dynamic-update-slice(...)", "ssd"),
    ("%constant_dynamic-slice_fusion.13 = (bf16[1,3,8448], bf16[1,3,8448]) "
     "fusion(...)", "ssd"),
    # a chunk's blocks: the states at the borders and their scan, the decay
    # matrix as XLA lays it out, a product [.., L, G, R, P]
    ("%while.4 = (s32[], f32[1,1,128,64,128], f32[2,1,1,128,64,128], "
     "f32[2,1,1,128], f32[2,1,1,128,64,128], s32[]) while(...)", "ssd"),
    ("%fusion.90 = f32[2,128,64,64] fusion(...)", "ssd"),
    ("%fusion.91 = f32[1,2,64,1,128,64] fusion(...)", "ssd"),
    # with layouts, as the trace has them
    ("%fusion.24 = (f32[64,128,64,128]{3,2,1,0:T(8,128)S(1)}, "
     "f32[64,128,64]{2,1,0:T(8,128)}) fusion(f32[64,128,64,128]{3,2,1,0} %p)",
     "ssd"),
    # neither: attention, the projections (no such shape among the RESULTS),
    # the norms, the head, the shared expert, another grouping's tokens
    ("%attention._paged_decode_step.3 = bf16[64,32,128] custom-call(...)", None),
    ("%convolution_bitcast_fusion.5 = bf16[64,1,16768] fusion(...)", None),
    ("%multiply_reduce_fusion.8 = (f32[64], f32[64,4096]) fusion(...)", None),
    ("%fusion.2 = f32[64,100352] fusion(...)", None),
    ("%fusion.77 = bf16[64,4096]{1,0} fusion(f32[64,128,64,128]{3,2,1,0} %x)", None),
    ("%fusion.78 = bf16[512,4096] fusion(...)", None),
    ("%fusion.79 = bf16[64,3072] fusion(...)", None),
    ("not an instruction", None),
])
def test_which_operations_are_whose(sizes, text, kind):
    got = ("moe" if moe_hybrid.is_moe_op(text, sizes)
           else "ssd" if moe_hybrid.is_ssd_op(text, sizes) else None)
    assert got == kind


def test_sizes_are_the_configurations_and_the_engines(sizes):
    assert sizes["state"] == (128, 64, 128) and sizes["tail"] == (3, 8448)
    assert sizes["router"] == 72 and sizes["held"] == 36 and sizes["top_k"] == 10
    assert sizes["tokens"] == {64, 512, 256, 128, 32, 16, 8, 4, 2, 1}
    assert 640 in sizes["pairs"] and 5120 in sizes["pairs"] and 10 in sizes["pairs"]


def test_the_device_time_of_each_kind_in_the_recorded_window(cfg, sizes, recorded):
    window = tuple(recorded["window"])
    spans = moe_hybrid.classify(recorded["events"], sizes)
    union = lambda kind, w=window: sum(  # noqa: E731
        e - s for s, e in hybrid.clipped_union(spans[kind], w))
    # of the 150 ms: the expert layers' 87.1, the mixers' 29.6; the recorder
    # dropped 5.6 ms of operations under 10 us that are neither's
    assert union("moe") == 87_120_537 and union("ssd") == 29_606_842
    assert recorded["dropped"][1] < 6_000_000
    # the named grouped products are most of the expert layers' time
    named = sum(d for n, s, d in recorded["events"] if "ragged-dot-none" in n)
    assert 0.9 < named / union("moe") < 1.0
    # a loop over the block borders and the operations inside it count once
    summed = sum(d for s, d in spans["ssd"])
    assert any(" while(" in n for n, _, _ in recorded["events"])
    assert summed > union("ssd")
    # clipped to the window it is handed
    assert 0 < union("moe", (0, 75_000_000)) < union("moe")
    # another configuration's sizes find no state and no conv tail here, and
    # only what the compiler names of the experts
    other = moe_hybrid.sizes(dict(
        cfg, mamba_n_heads=64, mamba_d_state=64, num_experts_per_tok=6,
        num_local_experts_published=64, num_local_experts=32))
    elsewhere = moe_hybrid.classify(recorded["events"], other)
    assert elsewhere["ssd"] == []
    assert 0 < len(elsewhere["moe"]) < len(spans["moe"])
    # what the driver keeps of the file is these spans
    ctx = {"moe_ops": (window, spans["moe"]), "ssd_ops": (window, spans["ssd"])}
    assert moe_hybrid.device_seconds(ctx, "moe") == pytest.approx(0.087120537)
    assert moe_hybrid.device_seconds(ctx, "ssd") == pytest.approx(0.029606842)
    assert moe_hybrid.device_seconds({"moe_ops": (None, [])}, "moe") is None
    assert moe_hybrid.device_seconds({"moe_ops": (window, [])}, "moe") is None
    assert moe_hybrid.device_seconds({}, "ssd") is None


def routing(step, **kw):
    args = dict(
        step=step, moe_programs=2, moe_pairs_held=3000, moe_pairs_absent=3100,
        moe_experts_hit=700, moe_tokens_per_expert_max=320,
        moe_tokens_per_expert_mean=166.0)
    return {"name": "moe.routing", "ph": "i", "ts": 0.0, "args": dict(args, **kw)}


def context(cfg, **kw):
    """A context as ``drivers/serve.py`` builds it: two steps start inside
    the traced window (the second ends after it), both decoding 60 rows at
    18,000 tokens of context, one with two chunks; a third starts after it.
    The counters of a step are written during the next."""
    step = lambda rows, ctx_tokens, pre: dict(  # noqa: E731
        decode_rows=rows, decode_context=ctx_tokens, prefill_tokens=pre,
        prefill_context=0.0, prefill_keys=0)
    chunk = lambda t_ms, tokens, start: {  # noqa: E731
        "name": "prefill.chunk", "ph": "X", "ts": 0.0, "dur": 1000.0,
        "args": {"perf_counter_ns": int(t_ms * 1e6), "tokens": tokens,
                 "start": start}}
    slice_ = lambda index, t_ns: {  # noqa: E731
        "name": "step", "ph": "X", "ts": 0.0, "dur": 1.0,
        "args": {"step": index, "perf_counter_ns": t_ns}}
    events = [
        slice_(6, 10**9 - 60_000_000),  # before the traced window
        chunk(1010, 128, 0), chunk(1020, 32, 128),
        slice_(7, 10**9), routing(6, moe_pairs_held=9),
        slice_(8, 10**9 + 50_000_000), routing(7),
        slice_(9, 10**9 + 108_000_000),
        routing(8, moe_programs=1, moe_pairs_held=300, moe_pairs_absent=340,
                moe_experts_hit=355, moe_tokens_per_expert_max=170,
                moe_tokens_per_expert_mean=83.0),
        routing(9, moe_pairs_held=7),
    ]
    ctx = dict(
        cfg=cfg, device_kind="TPU v5 lite", traced=(1.0, 1.1),
        counters={"plans": [step(60, 18000, 160), step(60, 18000, 0),
                            step(50, 15000, 0)]},
        step_rows=[(1.0, 1.045), (1.05, 1.105), (1.108, 1.14)],
        engine_events=events,
        trace=types.SimpleNamespace(busy_s=0.09),
    )
    ctx.update(kw)
    return ctx


def test_traced_routing_sums_the_counters_of_the_steps_that_started_inside(cfg):
    total = moe_hybrid.traced_routing(context(cfg))
    assert total["steps"] == 2 and total["moe_programs"] == 3
    assert total["moe_pairs_held"] == 3300 and total["moe_pairs_absent"] == 3440
    assert total["moe_experts_hit"] == 1055
    # a program that writes no such instant (this cell's parent): nothing
    without = context(cfg)
    without["engine_events"] = [
        e for e in without["engine_events"] if e["name"] != "moe.routing"]
    assert moe_hybrid.traced_routing(without) is None
    assert moe_hybrid.traced_routing({"cfg": cfg}) is None


def test_device_ms_per_step_of_both_kinds(cfg):
    ctx = context(cfg, moe_device_s=0.058, ssd_device_s=0.017)
    assert reader("moe.device_ms_per_step").read(ctx) == pytest.approx(29.0)
    assert reader("ssd.device_ms_per_step").read(ctx) == pytest.approx(8.5)
    nothing = context(cfg, moe_device_s=None, ssd_device_s=None)
    for name in ("moe.device_ms_per_step", "ssd.device_ms_per_step",
                 "moe.expert_roofline_share", "ssd.state_roofline_share"):
        assert reader(name).read(nothing) is None
    assert reader("moe.device_ms_per_step").read({"cfg": cfg}) is None


def test_the_mixers_roofline_share(cfg):
    ctx = context(cfg, ssd_device_s=0.017)
    ref = hybrid.reference_for(cfg)
    state = ref.state_bytes_per_slot(cfg)
    assert state == 38_204_928
    moved = 2 * state * (120 + 2) + ref.scan_io_bytes_per_token(cfg) * (120 + 160)
    want = 100 * (moved / 819e9) / 0.017
    assert reader("ssd.state_roofline_share").read(ctx) == pytest.approx(want)
    assert 50 < want < 100


def test_the_experts_roofline_share_is_counted_from_the_counters(cfg):
    """The weights of every held expert a program reached, once a program
    and layer, and the pairs' rows; or the pairs' FLOPs, whichever takes
    longer: here the bytes."""
    ctx = context(cfg, moe_device_s=0.058)
    share = reader("moe.expert_roofline_share").read(ctx)
    nbytes = 1055 * 2 * 9_437_184 + 3300 * 2 * 2 * 4096
    flops = 2 * 9_437_184 * 3300
    assert nbytes / 819e9 > flops / 197e12
    assert share == pytest.approx(100 * (nbytes / 819e9) / 0.058)
    assert ctx["moe_roofline_bound"] == "memory" and 0 < share < 100
    # the same counters over less device time: a better implementation of
    # the products reads higher, whatever it is
    faster = reader("moe.expert_roofline_share").read(
        context(cfg, moe_device_s=0.029))
    assert faster == pytest.approx(2 * share)
    without = context(cfg, moe_device_s=0.058)
    without["engine_events"] = [
        e for e in without["engine_events"] if e["name"] != "moe.routing"]
    assert reader("moe.expert_roofline_share").read(without) is None


def test_the_imbalance_is_the_most_loaded_held_expert_over_the_mean(cfg):
    read = reader("moe.tokens_per_expert_imbalance").read
    assert read(context(cfg)) == pytest.approx((320 + 170) / (166.0 + 83.0))
    assert read({"cfg": cfg}) is None


def test_roofline_share_serve_hybrid_takes_this_references_counts(cfg):
    """``device.roofline_share.serve_hybrid``'s reader with the counts of
    ``reference/granite.py``: every weight held here once a step."""
    ctx = context(cfg)
    share = reader("device.roofline_share.serve_hybrid").read(ctx)
    ref = hybrid.reference_for(cfg)
    assert ref.__name__.endswith("granite")
    first = ref.serve_min_bytes(cfg, 60, 160, 18000, 2)
    second = ref.serve_min_bytes(cfg, 60, 0, 18000, 0)
    assert first - second == 2 * 2 * 38_204_928 + 160 * 4096
    assert 2 * 4.9e9 < second < 16e9
    assert share == pytest.approx(100 * ((first + second) / 819e9) / 0.09)
    assert ctx["roofline_bound"] == "memory"


def test_read_ops_finds_nothing_on_a_cpu_trace_and_says_so(tmp_path, cfg):
    import jax
    import jax.numpy as jnp

    from harness import trace

    assert moe_hybrid.read_ops(str(tmp_path), cfg)["events"] == 0
    profiler = trace.Profiler(str(tmp_path))
    profiler.start()
    profiler.open_window()
    jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    path = profiler.stop()
    assert moe_hybrid.newest_trace(str(tmp_path)) == path
    ops = moe_hybrid.read_ops(str(tmp_path), cfg)
    assert ops["ssd"] == [] and ops["moe"] == [] and ops["read_s"] > 0


def test_the_state_is_read_whole_and_on_the_heads_that_remember_longest():
    """``drivers/serve_hybrid_moe.py``'s ``state_gaps`` on hand-made states:
    what it returns is every Mamba-2 layer's distance over all its heads, so
    a fault in any head reads; what it leaves in the probe is how much
    farther the first layer's slow heads (the eighth with the smallest
    ``exp(A_log) softplus(dt_bias)``, by that layer's own weights) lie than
    its heads on average."""
    import numpy as np

    driver = reader("../drivers/serve_hybrid_moe")
    heads, rng = 16, np.random.default_rng(0)
    want = rng.standard_normal((2, heads, 4, 8)).astype(np.float32)
    # Layer 0 forgets slowest in heads 3 and 9; an attention layer, which has
    # no such weights, lies between it and the second Mamba-2 layer.
    a_log = np.full(heads, np.log(8.0), np.float32)
    dt_bias = np.full(heads, -2.0, np.float32)
    a_log[3], dt_bias[9] = 0.0, -7.0
    first = {"a_log": a_log, "dt_bias": dt_bias}
    weights = {"layers": [first, {"wq": None}, {"a_log": a_log[::-1]}]}
    assert sorted(driver.longest_memories(first)) == [3, 9]
    said = []
    cell = types.SimpleNamespace(
        config={}, say=said.append,
        traffic={"check": {"state_gap_memory_limit": 0.5}},
        reference=types.SimpleNamespace(
            final_states=lambda cfg, w, tokens, **kw: want))
    whole = lambda layer: (  # noqa: E731
        np.linalg.norm(got[layer] - want[layer]) / np.linalg.norm(want[layer]))
    got = want.copy()
    got[0, 3] *= 1.02  # a slow head of layer 0: 2% off
    got[1, 7] *= 3.0  # a head of layer 1
    probe = {"tokens": [1], "states": got}
    gaps = driver.state_gaps(cell, weights, probe)
    assert gaps == pytest.approx([whole(0), whole(1)], rel=1e-5)
    assert 0.003 < gaps[0] < 0.008 and gaps[1] > 0.3
    # The two slow heads lie 0.01 off on average, the sixteen 0.00125.
    assert probe["state_gap_memory"] == pytest.approx(0.01 - 0.00125, abs=1e-6)
    assert "2 of 16 that remember longest 0.010000: +0.008750" in said[0]
    assert "routing_gap" not in probe  # no programs' routing: nothing to hold
    # A fault in a head that forgets fast reads in the whole layer's number,
    # and pulls the slow heads' BELOW the average.
    got[0, 5] *= 1.5
    assert driver.state_gaps(cell, weights, probe)[0] > 0.1
    assert probe["state_gap_memory"] < 0


def test_the_routing_is_held_against_the_references_own():
    """``drivers/serve_hybrid_moe.py``'s ``routing_gap`` on hand-made counts:
    the programs' counts are laid along the probe's tokens in the order they
    ran, a program that carried one token names that token's experts, and
    what is read is the share of its (layer, expert) pairs that are not the
    reference's. Chunks are passed over; counts that do not add up to the
    probe's tokens are an error, not a number."""
    import numpy as np

    driver = reader("../drivers/serve_hybrid_moe")
    layers, tokens, experts, top_k = 2, 7, 6, 2
    rng = np.random.default_rng(1)
    want = np.zeros((layers, tokens, experts), bool)
    for layer in range(layers):
        for t in range(tokens):
            want[layer, t, rng.choice(experts, top_k, replace=False)] = True
    said = []
    cell = types.SimpleNamespace(
        config={"num_experts_per_tok": top_k}, say=said.append,
        traffic={"check": {"routing_gap_limit": 0.1}},
        reference=types.SimpleNamespace(
            routing_at=lambda cfg, w, toks, **kw: want))
    count = lambda lo, hi: want[:, lo:hi].sum(axis=1).astype(np.int64)  # noqa: E731
    # A chunk of 4 tokens, an idle program, then three tokens alone.
    programs = [count(0, 4), count(0, 0), count(4, 5), count(5, 6), count(6, 7)]
    probe = {"tokens": list(range(tokens)), "routing": programs}
    assert driver.routing_gap(cell, None, probe) == 0.0
    assert "3 tokens that a program carried alone" in said[-1]
    # One of token 5's two experts in layer 1 is another: 1 of 3 x 2 x 2 pairs.
    moved = programs[3].copy()
    was = int(np.flatnonzero(moved[1])[0])
    now = int(np.flatnonzero(moved[1] == 0)[0])
    moved[1, was], moved[1, now] = 0, 1
    probe["routing"] = programs[:3] + [moved] + programs[4:]
    assert driver.routing_gap(cell, None, probe) == pytest.approx(1 / 12)
    assert said[-1].endswith("0.0000 0.1667")
    # The chunk's own routing is not judged (its counts are sums) ...
    probe["routing"] = [np.roll(programs[0], 1, axis=-1)] + programs[1:]
    assert driver.routing_gap(cell, None, probe) == 0.0
    # ... but a token gone missing is an error.
    probe["routing"] = programs[:-1]
    with pytest.raises(RuntimeError, match="routed 6 tokens"):
        driver.routing_gap(cell, None, probe)
