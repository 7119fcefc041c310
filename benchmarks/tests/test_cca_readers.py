"""The readers of a ``serve_cca_moe`` cell's per-layer metrics: HOW the CCA
sublayers', the K/V kernel's and the expert sublayers' operations are
recognised (``harness/cca.py``), the readers' arithmetic on counters a test
can reckon by hand, the reference module's counts at the published sizes, and
all of it on a recorded trace where there is one
(``data/cca_trace_recorded.json``: some engine steps of
``zaya1-8b-reasoning``'s traced window on the v5e, cut by
``record_cca_trace.py``)."""

import importlib.util
import json
import os

import pytest

from harness import cca, hybrid, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "cca_trace_recorded.json")
CELL = "zaya1-8b-reasoning"
NEW = ("cca.device_ms_per_step", "cca.decode_roofline_share",
       "moe_top1.device_ms_per_step", "moe_top1.expert_roofline_share")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "zaya1-8b.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("text, kind", [
    # the kernel, named by the program, as the compiler numbers it
    ("%attention._paged_decode_step.7 = bf16[96,8,128]{2,1,0:T(8,128)(2,1)} "
     "custom-call(...)", "kernel"),
    # the expert sublayer: the grouped products, the router network in
    # float32, scores and the choice, the counts, the rows gathered into 128
    ("%ragged-dot-stationary.8 = (f32[128,4096], s32[16]) custom-call(...)", "moe"),
    ("%fusion.312 = f32[96,256]{1,0:T(8,128)} fusion(...)", "moe"),
    ("%multiply_reduce_fusion.4 = (f32[96], f32[96,256]) fusion(...)", "moe"),
    ("%fusion.681 = (f32[96], f32[96,16]) fusion(...)", "moe"),
    ("%fusion.489 = (f32[96,1], s32[96,1]) fusion(...)", "moe"),
    ("%fusion.14 = s32[17] fusion(...)", "moe"),
    ("%fusion.5 = bf16[128,2048] fusion(...)", "moe"),
    ("%fusion.77 = f32[512,256] fusion(...)", "moe"),
    # the CCA sublayer: u, a and the tails; the second convolution however it
    # is laid out; the norms' sums; the rotation's halves; the shifted value;
    # the pools' writes; the projections into q, k, v; the state leaves
    ("%maximum_convert_fusion = f32[96,1,1280] fusion(...)", "cca"),
    ("%copy_select_fusion = (f32[96,2,1280], f32[96,2,1280]) fusion(...)", "cca"),
    ("%fusion.680 = (f32[96,1,1024], f32[12,8,8,128]) fusion(...)", "cca"),
    ("%fusion.41 = f32[96,1,10,128] fusion(...)", "cca"),
    ("%fusion.19 = f32[10,128,96] fusion(...)", "cca"),
    ("%fusion.3 = f32[96,8] fusion(...)", "cca"),
    ("%fusion.4 = (bf16[96,8,32], bf16[96,8,32]) fusion(...)", "cca"),
    ("%pad_maximum_fusion.2 = bf16[96,2,128] fusion(...)", "cca"),
    ("%fusion.12 = bf16[8193,16,2,128] fusion(...)", "cca"),
    ("%fusion.475 = bf16[96,1,256] fusion(...)", "cca"),
    ("%convolution_bitcast_fusion = bf16[96,1,1024] fusion(...)", "cca"),
    ("%bitcast_select_fusion.1 = (f32[96,128], f32[96,128]) fusion(...)", "cca"),
    ("%while.3 = (s32[], f32[1,512,8,128], f32[1,512,8]) while(...)", "cca"),
    # NOT counted: W_o, the block's norms and merges on the residual stream,
    # the head, a piece's rows [width, d]
    ("%fusion.9 = bf16[96,2048] fusion(...)", None),
    ("%fusion.8 = (f32[96], bf16[96,2048]) fusion(...)", None),
    ("%fusion.2 = f32[96,262272] fusion(...)", None),
    ("%fusion.6 = bf16[512,2048] fusion(...)", None),
    ("%fusion.1 = s32[96] fusion(...)", None),
])
def test_how_an_operation_is_recognised(cfg, text, kind):
    s = cca.sizes(cfg)
    assert (s["chans"], s["room"], s["router"]) == (1280, 128, 256)
    assert cca.kind_of(text, s) == kind


def step(t_s, **args):
    return {"name": "step", "ph": "X",
            "args": dict(args, perf_counter_ns=int(t_s * 1e9))}


def routing(step_no, **args):
    return {"name": "moe.routing", "args": dict(args, step=step_no)}


def test_decode_roofline_share_by_hand(cfg):
    """Two traced steps of 96 rows at a mean context of 710: 68,160 keys a
    layer and step. By bytes 68,160 x 1,024 B x 20 layers / 819 GB/s = 1.704
    ms a step, by FLOPs 68,160 x 8 x 128 x 4 x 20 / 197 TFLOP/s = 28 us:
    memory binds. Two steps over 5 ms of kernel time."""
    ctx = dict(
        cfg=cfg, device_kind="TPU v5 lite", traced=(10.0, 11.0),
        engine_events=[
            step(9.5, decode_kv_tokens_visible=7),
            step(10.1, decode_kv_tokens_visible=68160),
            step(10.5, decode_kv_tokens_visible=68160),
            step(10.7, state_slots_updated=0),  # dispatched no decode
        ],
        cca_ops={"window": (0, 10**9), "cca": [(0, 10**6)], "moe": [],
                 "kernel": [(0, 3_000_000), (5 * 10**8, 2_000_000)]})
    assert cca.traced_visible_tokens(ctx) == 136320
    least = 136320 * 1024 * 20 / 819e9
    assert reader("cca.decode_roofline_share").read(ctx) == pytest.approx(
        100 * least / 0.005)
    ref = hybrid.reference_for(cfg)
    assert ref.decode_kv_min_bytes(cfg, 136320) == 136320 * 1024 * 20
    assert ref.decode_kv_flops(cfg, 136320) == 136320 * 8 * 128 * 4 * 20
    assert (ref.decode_kv_flops(cfg, 136320)
            / peaks.peaks_for("TPU v5 lite")["bf16_flops"]) < least


def test_device_ms_per_step_is_the_union_over_the_traced_steps(cfg):
    ctx = dict(
        traced=(0.0, 1.0), step_rows=[(0.1, 0.2), (0.3, 0.4), (1.5, 1.6)],
        counters={"plans": [{"decode_rows": 1}] * 3}, engine_events=[],
        cca_ops={"window": (0, 10**9),
                 "kernel": [(0, 4_000_000), (2_000_000, 4_000_000)],
                 "cca": [(5_000_000, 3_000_000), (2 * 10**9, 10**6)],
                 "moe": [(10_000_000, 6_000_000)]})
    # [0, 6) and [5, 8) ms merge to 8 ms; the last lies outside the window;
    # two steps started in it.
    assert reader("cca.device_ms_per_step").read(ctx) == pytest.approx(4.0)
    assert reader("moe_top1.device_ms_per_step").read(ctx) == pytest.approx(3.0)


def test_expert_roofline_share_by_hand(cfg):
    """One traced decode step of 96 rows over 20 layers that reached all 16
    experts a layer: 320 experts' weights of 12.58 M x 2 B = 8.05 GB, 1,920
    pairs' rows in and out 15.7 MB: 9.85 ms at 819 GB/s; by FLOPs 1,920 x
    25.2 M x 2 / 197 TFLOP/s = 0.25 ms. Over 12 ms of the sublayers' time."""
    ctx = dict(
        cfg=cfg, device_kind="TPU v5 lite", traced=(0.0, 1.0),
        step_rows=[(0.1, 0.2)], counters={"plans": [{}]},
        engine_events=[
            step(0.1, step=5), routing(
                5, moe_programs=1, moe_pairs_held=1920, moe_pairs_absent=0,
                moe_experts_hit=320, moe_tokens_per_expert_max=200,
                moe_tokens_per_expert_mean=120.0)],
        cca_ops={"window": (0, 10**9), "kernel": [], "cca": [],
                 "moe": [(0, 12_000_000)]})
    nbytes = 320 * 12_582_912 * 2 + 1920 * 2 * 2 * 2048
    assert reader("moe_top1.expert_roofline_share").read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / 0.012)
    assert ctx["moe_top1_roofline_bound"] == "memory"


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(cfg, name):
    """The parent's program, a CPU run: no counters, no operations."""
    ctx = dict(cfg=cfg, device_kind="TPU v5 lite", traced=(0.0, 1.0),
               step_rows=[(0.1, 0.2)], counters={"plans": [{}]},
               engine_events=[step(0.1, step=1, pages_referenced=9)],
               cca_ops={"window": None, "kernel": [], "cca": [], "moe": []})
    assert reader(name).read(ctx) is None
    assert reader(name).read({"cfg": cfg}) is None


def test_the_reference_counts_the_published_sizes(cfg):
    """The issue's arithmetic: a layer 207.6 M, 4,689 M held = 9.38 GB; 1,024
    B a token and layer; 10,752 B a slot and layer; a decode step of 96 rows
    reaches 15.97 of 16 experts a layer and moves 10.8 GB at the least: 13.2
    ms at 819 GB/s."""
    ref = hybrid.reference_for(cfg)
    p = ref.matmul_params(cfg)
    assert p == {"cca": 5_570_560, "router": 659_456, "expert": 12_582_912,
                 "head": 537_133_056}
    assert ref.held_parameters(cfg) == pytest.approx(4.6886e9, rel=1e-4)
    assert ref.kv_bytes_per_token_layer(cfg) == 1024
    assert ref.state_bytes_per_slot_layer(cfg) == 10_752
    assert ref.experts_reached(cfg, 96) == pytest.approx(15.967, abs=1e-3)
    context = 96 * 710
    nbytes = ref.serve_min_bytes(cfg, 96, 0, context, 0)
    weights = (20 * (p["cca"] + p["router"] + ref.experts_reached(cfg, 96)
                     * p["expert"]) + p["head"] + 96 * 2048)
    assert nbytes == pytest.approx(
        2 * weights + 1024 * 20 * (context + 96) + 2 * 10_752 * 20 * 96)
    assert 10.7e9 < nbytes < 10.9e9
    assert 13.0e-3 < nbytes / 819e9 < 13.3e-3
    # a prefill piece reads no head and one slot's state
    piece = ref.serve_min_bytes(cfg, 0, 512, 0, 1)
    assert piece < nbytes - 2 * p["head"]
    assert ref.serve_flops(cfg, 96, context, 96) == pytest.approx(
        2 * 96 * 20 * (p["cca"] + p["router"] + p["expert"])
        + 4 * 8 * 128 * 20 * context + 2 * p["head"] * 96)


def test_the_cells_entries_in_benchmark_json(cfg):
    """The cell: its configuration, traffic and chips, the four new metrics
    under its name alone, its name in the lists of what every serving cell
    reports; and the published keys as the catalog has them."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells[CELL] == dict(
        cells[CELL], config="zaya1-8b", traffic="reasoning_closed_c96", chips=1)
    entry = next(c for c in spec["configs"] if c["name"] == "zaya1-8b")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == cfg["source"]
    assert all(len(e["why"]) <= 200 for e in spec["configs"] + spec["workloads"])
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tpot_ms_p50"
    tpot = next(m for m in spec["end_to_end"] if m["name"] == "tpot_ms_p50")
    assert CELL in tpot["workloads"]
    for name, m in metrics.items():
        if "k-exaone-236b-mixed-queue" in m["workloads"] and not name.startswith(
                ("swa_kv.", "kv.window_")):
            assert CELL in m["workloads"], name
    assert CELL in metrics["state.resets_per_step"]["workloads"]
    # every published width, all 16 experts, one a token, the whole vocabulary
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 8, 2, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["router_hidden_size"],
            cfg["vocab_size"]) == (16, 1, 2048, 256, 262272)
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 20
    assert cfg["experts_held"] == [0, 16] and cfg["tie_word_embeddings"]
    with open(os.path.join(BENCH, "traffic", "reasoning_closed_c96.json")) as f:
        traffic = json.load(f)
    deck = traffic["deck"]
    assert len(deck) == 96 and traffic["clients"] == 96
    assert sum(p for p, _ in deck) == 33_659
    assert sum(o for _, o in deck) == 63_669
    assert max(p + o for p, o in deck) <= (
        cfg["assumed"]["engine"]["max_seq_len"])
    assert traffic["stagger_warm"] == [[64, 8 * i] for i in range(1, 97)]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no trace was recorded on the chip yet")
def test_the_recorded_trace_holds_all_three_kinds(cfg):
    with open(RECORDED) as f:
        recorded = json.load(f)
    ops = cca.classify(recorded["events"], cfg)
    span = tuple(recorded["window"])
    s = cca.sizes(cfg)
    # Twenty calls of the kernel a decode program: whole programs.
    assert len(ops["kernel"]) >= 20
    names = {n.split(" = ")[0].rsplit(".", 1)[0] for n, _, _ in recorded["events"]
             if cca.kind_of(n, s) == "kernel"}
    assert names == {"%attention._paged_decode_step"}
    products = [n for n, _, _ in recorded["events"] if "ragged-dot" in n]
    assert len(products) >= 40
    assert all(cca.kind_of(n, s) == "moe" for n in products)
    busy = {kind: sum(e - s0 for s0, e in hybrid.clipped_union(ops[kind], span))
            for kind in ("kernel", "cca", "moe")}
    assert all(0 < busy[kind] < span[1] - span[0] for kind in busy)
    # The experts' stream is most of a step; the kernel and the small
    # operations each a small share.
    assert busy["moe"] > busy["cca"] and busy["moe"] > busy["kernel"]
    ctx = dict(traced=(0.0, 1.0), step_rows=[(0.5, 0.6)],
               counters={"plans": [{}]}, engine_events=[],
               cca_ops=dict(ops, window=span))
    assert reader("cca.device_ms_per_step").read(ctx) >= busy["cca"] / 1e6
    assert reader("moe_top1.device_ms_per_step").read(ctx) == pytest.approx(
        busy["moe"] / 1e6)
