"""The readers of a ``serve_window_moe`` cell's per-layer metrics: HOW the
window layers' operations are recognised (``harness/window.py``), the readers'
arithmetic on counters a test can reckon by hand, the reference module's
counts at the published sizes, and all of it on a recorded trace where there
is one (``data/swa_kv_trace_recorded.json``: some engine steps of
``k-exaone-236b-mixed-queue``'s traced window on the v5e, cut by
``record_window_trace.py``)."""

import importlib.util
import json
import os

import pytest

from harness import hybrid, peaks, window

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "swa_kv_trace_recorded.json")
CELL = "k-exaone-236b-mixed-queue"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")) as f:
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
    ("%attention._window_paged_decode_step.5 = bf16[32,64,128]{2,1,0:T(8,128)(2,1)} "
     "custom-call(...)", "decode"),
    ("%attention._window_paged_decode_step = bf16[32,64,128] custom-call(...)",
     "decode"),
    # a prefill piece's gather of a window layer's pages through its short
    # table of 41 x 16 = 656 keys, and the scores and softmax over them
    ("%fusion.31 = bf16[1,656,8,128]{3,2,1,0:T(8,128)(2,1)} fusion(...)", "rest"),
    ("%fusion.40 = f32[1,8,8,512,656] fusion(...)", "rest"),
    ("%reduce_fusion.2 = (f32[8,8,64], bf16[8,8,64,656]) fusion(...)", "rest"),
    # NOT the window layers': the full layers' kernel and gathered view, the
    # experts, the projections, the pools' writes, the head
    ("%attention._paged_decode_step.3 = bf16[32,64,128] custom-call(...)", None),
    ("%fusion.9 = bf16[1,12800,8,128] fusion(...)", None),
    ("%ragged-dot-stationary.7 = f32[512,4096] custom-call(...)", None),
    ("%fusion.88 = bf16[32,6144] fusion(...)", None),
    ("%scatter_fusion.5 = bf16[513,16,8,128] fusion(...)", None),
    ("%convert_reduce_fusion.4 = f32[32,19200] fusion(...)", None),
])
def test_how_an_operation_is_recognised(cfg, text, kind):
    assert window.piece_tokens(cfg) == 656
    assert window.kind_of(text, 656) == kind


def step(t_s, **args):
    return {"name": "step", "ph": "X",
            "args": dict(args, perf_counter_ns=int(t_s * 1e9))}


def test_decode_roofline_share_by_hand(cfg):
    """Two traced steps of 32 rows past their windows: 32 x 128 = 4,096 keys
    inside the windows a layer and step. By bytes 4,096 x 4,096 B x 6 layers /
    819 GB/s = 122.9 us a step, by FLOPs 4,096 x 64 x 128 x 4 x 6 / 197
    TFLOP/s = 4.1 us: memory binds. Two steps over 1.2 ms of kernel time."""
    ctx = dict(
        cfg=cfg, device_kind="TPU v5 lite", traced=(10.0, 11.0),
        engine_events=[
            step(9.5, window_pages_held=1, decode_window_tokens_visible=7),
            step(10.1, window_pages_held=288, decode_window_tokens_visible=4096),
            step(10.5, window_pages_held=288, decode_window_tokens_visible=4096),
            step(10.7, window_pages_held=288),  # dispatched no decode
        ],
        swa_kv_ops={"window": (0, 10**9),
                    "decode": [(0, 700_000), (5 * 10**8, 500_000)],
                    "rest": [(10**8, 1_000_000)]})
    assert window.traced_window_tokens(ctx) == 8192
    least = 8192 * 4096 * 6 / 819e9
    assert reader("swa_kv.decode_roofline_share").read(ctx) == pytest.approx(
        100 * least / 0.0012)
    ref = hybrid.reference_for(cfg)
    assert ref.window_decode_min_bytes(cfg, 8192) == 8192 * 4096 * 6
    assert ref.window_decode_flops(cfg, 8192) == 8192 * 64 * 128 * 4 * 6
    assert (ref.window_decode_flops(cfg, 8192)
            / peaks.peaks_for("TPU v5 lite")["bf16_flops"]) < least


def test_device_ms_per_step_is_the_union_over_the_traced_steps(cfg):
    ctx = dict(
        traced=(0.0, 1.0), step_rows=[(0.1, 0.2), (0.3, 0.4), (1.5, 1.6)],
        counters={"plans": [{"decode_rows": 1}] * 3}, engine_events=[],
        swa_kv_ops={"window": (0, 10**9),
                    "decode": [(0, 4_000_000), (2_000_000, 4_000_000)],
                    "rest": [(5_000_000, 3_000_000), (2 * 10**9, 10**6)]})
    # [0, 6) and [5, 8) ms merge to 8 ms; the last lies outside the window;
    # two steps started in it.
    assert reader("swa_kv.device_ms_per_step").read(ctx) == pytest.approx(4.0)


def test_the_window_groups_counters_by_hand(cfg):
    """32 decoding rows hold 9 pages each in the window group and ~160 in
    the full one; a step frees 2 pages by its decode rows, and 32 more where
    a piece of 512 ended in it."""
    events = [
        step(0.1, window_pages_held=288, pages_referenced=5120),
        {"name": "window.free", "args": {"pages": 2, "rows": 2}},
        step(0.2, window_pages_held=329, pages_referenced=5152),
        {"name": "window.free", "args": {"pages": 34, "rows": 3}},
        step(0.3, window_pages_held=0, pages_referenced=0),  # an idle step
        step(0.4, decode_rows=0),  # a program without a window group
    ]
    ctx = {"engine_events": events}
    assert reader("kv.window_held_share").read(ctx) == pytest.approx(
        (288 / 5120 + 329 / 5152) / 2)
    assert reader("kv.window_pages_freed_per_step").read(ctx) == pytest.approx(
        36 / 4)


@pytest.mark.parametrize("name", [
    "swa_kv.device_ms_per_step", "swa_kv.decode_roofline_share",
    "kv.window_held_share", "kv.window_pages_freed_per_step"])
def test_nothing_to_read_is_none(cfg, name):
    """A program without a window group, a CPU run: no counters, no
    operations."""
    ctx = dict(cfg=cfg, device_kind="TPU v5 lite", traced=(0.0, 1.0),
               step_rows=[(0.1, 0.2)], counters={"plans": [{}]},
               engine_events=[step(0.1, decode_kv_tokens_visible=5,
                                   pages_referenced=9)],
               swa_kv_ops={"window": None, "decode": [], "rest": []})
    assert reader(name).read(ctx) is None
    assert reader(name).read({"cfg": cfg}) is None


def test_the_reference_counts_the_published_sizes(cfg):
    """The issue's arithmetic: 5,979 M parameters held; K and V 4,096 B a
    token and layer; a decode step of 32 rows reaches 87% of the 16 held
    experts; a window layer reads its windows and a full layer the whole
    context."""
    ref = hybrid.reference_for(cfg)
    assert ref.held_parameters(cfg) == pytest.approx(5.979e9, rel=1e-3)
    assert ref.kv_bytes_per_token_layer(cfg) == 4096
    assert ref.window_layers(cfg) == 6
    assert ref.experts_reached(cfg, 32) == pytest.approx(16 * 0.8732, rel=1e-3)
    p = ref.matmul_params(cfg)
    assert p["attention"] == 113_246_208 and p["expert"] == 37_748_736
    # 32 rows at a mean context of 2,527: the full layers read it all, the
    # window layers 128 a row.
    context = 32 * 2527
    nbytes = ref.serve_min_bytes(cfg, 32, 0, context, 0)
    cached = 2 * context + 6 * 32 * 128
    weights = (8 * p["attention"] + p["dense"] + 7 * (
        p["shared"] + p["router"] + ref.experts_reached(cfg, 32) * p["expert"])
        + p["head"] + 32 * 6144)
    assert nbytes == pytest.approx(2 * weights + 4096 * (cached + 8 * 32))
    assert 10.5e9 < nbytes < 12.5e9  # the issue's ~11.4 GB a decode step
    # a full layer's pairs are the context, a window layer's its windows
    flops = ref.serve_flops(cfg, 32, context, 32)
    assert flops > 2 * 32 * (8 * p["attention"] + p["dense"])


def test_the_cells_entries_in_benchmark_json():
    """The tenth cell: its configuration, traffic and chips, the four new
    metrics under its name alone, and its name in the lists of what every
    serving cell reports."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    assert len(cells) == 10 and len(spec["configs"]) == 8
    assert cells[CELL] == dict(
        cells[CELL], config="k-exaone-236b-a23b", traffic="mixed_closed_c32",
        chips=1)
    assert spec["workloads"][-1]["name"] == CELL
    assert all(len(e["why"]) <= 200 for e in spec["configs"] + spec["workloads"])
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in ("swa_kv.device_ms_per_step", "swa_kv.decode_roofline_share",
                 "kv.window_held_share", "kv.window_pages_freed_per_step"):
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tpot_ms_p50"
    tpot = next(m for m in spec["end_to_end"] if m["name"] == "tpot_ms_p50")
    assert tpot["workloads"][-1] == CELL
    for name, m in metrics.items():
        if "olmo-hybrid-7b-chat" in m["workloads"] and not name.startswith(
                ("gdn.", "state.")):
            assert m["workloads"][-1] == CELL, name
    assert CELL in metrics["moe.tokens_per_expert_imbalance"]["workloads"]
    with open(os.path.join(BENCH, "traffic", "mixed_closed_c32.json")) as f:
        traffic = json.load(f)
    deck = traffic["deck"]
    assert len(deck) == 64 and traffic["clients"] == 32
    assert sum(p for p, _ in deck) == 154_191
    assert sum(o for _, o in deck) == 15_062
    assert min(p for p, _ in deck) == 256 and max(p for p, _ in deck) == 12_288


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no trace was recorded on the chip yet")
def test_the_recorded_trace_holds_the_kernel_and_the_rest(cfg):
    with open(RECORDED) as f:
        recorded = json.load(f)
    ops = window.classify(recorded["events"], cfg)
    span = tuple(recorded["window"])
    # Six calls of the windowed kernel a decode program: whole programs.
    assert len(ops["decode"]) >= 6
    names = {n.split(" = ")[0].rsplit(".", 1)[0] for n, _, _ in recorded["events"]
             if window.kind_of(n, 656) == "decode"}
    assert names == {"%attention._window_paged_decode_step"}
    full = [n for n, _, _ in recorded["events"]
            if n.startswith("%attention._paged_decode_step")]
    assert full and all(window.kind_of(n, 656) is None for n in full)
    busy = sum(e - s for s, e in hybrid.clipped_union(ops["decode"], span))
    assert 0 < busy < span[1] - span[0]
    ctx = dict(traced=(0.0, 1.0), step_rows=[(0.5, 0.6)],
               counters={"plans": [{}]}, engine_events=[],
               swa_kv_ops=dict(ops, window=span))
    assert reader("swa_kv.device_ms_per_step").read(ctx) >= busy / 1e6
