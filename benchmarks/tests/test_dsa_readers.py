"""The readers of a ``serve_sparse_latent_moe`` cell's per-layer metrics: HOW
the learned sparse attention's and the windowed attention's operations are
recognised (``harness/dsa.py``), the readers' arithmetic on counters a test
can reckon by hand, and the recognition on a recorded trace
(``data/dsa_trace_recorded.json``: 150 ms of ``dots3-note-longdoc-qa``'s
traced window on the v5e, the committed files of PR 39's final tree, cut by
``record_dsa_trace.py``, with where the whole window's device time went)."""

import importlib.util
import json
import os

import pytest

from harness import dsa, hybrid, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "dsa_trace_recorded.json")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_sizes_the_operations_are_told_by(cfg):
    s = dsa.sizes(cfg)
    assert (s["table"], s["topk"], s["full"], s["index"], s["sliding"]) == (
        49664, 2048, 640, 128, 1152)
    assert s["select"] == {49664, 32 * 49664, 2048, 65536}
    assert dsa.layer_counts(cfg) == (3, 3)


@pytest.mark.parametrize("text, kind", [
    # the three kernels, named by the program, as the compiler numbers them
    ("%attention._index_scores.3 = f32[32,97,512]{2,1,0:T(8,128)} "
     "custom-call(...)", "index"),
    ("%attention._sparse_latent_decode_step.1 = bf16[32,128,512] "
     "custom-call(...)", "sparse"),
    ("%attention._window_latent_decode_step = bf16[32,64,1024] "
     "custom-call(...)", "window"),
    # the gather of the selected latents, as laid out and as flattened
    ("%fusion.77 = bf16[65536,640]{1,0:T(8,128)(2,1)} fusion(...)", "gather"),
    ("%gather_fusion = bf16[32,2048,640] fusion(...)", "gather"),
    # the exact top-k and the search of the positions
    ("%fusion.12 = s32[65536]{0:T(1024)} fusion(...)", "select"),
    ("%reduce_fusion.4 = (u32[32], pred[32,49664]) fusion(...)", "select"),
    ("%fusion.5 = s32[32,2048,128] fusion(...)", "select"),
    ("%cumsum.2 = s32[1,512,49664] fusion(...)", "select"),
    # loops: a sliding layer's carries its pool, every other is a full layer's
    ("%while.3 = (s32[], f32[1,64,512], f32[1,64,512,128], "
     "bf16[18433,16,1152]) while(...)", "window_rest"),
    ("%while.7 = (s32[], f32[1,512,49664], bf16[18433,16,128]) while(...)",
     "full_rest"),
    ("%while = (s32[], u32[32]) while(...)", "full_rest"),
    # the new tokens' rows written into the pools
    ("%scatter_fusion.5 = bf16[18433,16,1152]{2,1,0} fusion(...)", "window_rest"),
    ("%dynamic-update-slice_fusion = bf16[18433,16,640] fusion(...)", "full_rest"),
    ("%scatter_fusion.9 = bf16[18433,16,128] fusion(...)", "full_rest"),
    # NOT theirs: the other kernels, the experts, the projections
    ("%attention._latent_decode_step.27 = bf16[32,16,512] custom-call(...)", None),
    ("%ragged-dot-stationary.7 = f32[256,3072] custom-call(...)", None),
    ("%fusion.88 = bf16[32,5120] fusion(...)", None),
    ("%convert_reduce_fusion.4 = f32[32,19008] fusion(...)", None),
    ("%fusion.12 = bf16[32,64,128] fusion(...)", None),
])
def test_how_an_operation_is_recognised(cfg, text, kind):
    assert dsa.kind_of(text, dsa.sizes(cfg)) == kind


def step(t_s, **args):
    return {"name": "step", "ph": "X",
            "args": {"perf_counter_ns": int(t_s * 1e9), **args}}


def context(cfg, ops, events):
    return {
        "cfg": cfg, "device_kind": "TPU v5 lite", "traced": (10.0, 11.0),
        "engine_events": events, "dsa_ops": ops,
        "step_rows": [(9.5, 9.9), (10.1, 10.5), (10.6, 10.9)],
        "counters": {"plans": [dict(decode_rows=32)] * 3},
    }


def test_the_readers_arithmetic_by_hand(cfg):
    """Two traced steps of 32 rows at 33,000 tokens, four askers a document;
    a kernel's calls of a millisecond each inside a window of a second."""
    visible = 32 * 33000
    counted = dict(
        decode_kv_tokens_visible=visible, decode_index_tokens_scored=visible,
        decode_index_tokens_scored_distinct=visible // 4,
        decode_kv_tokens_selected=32 * 2048,
        decode_window_tokens_visible=32 * 513,
        decode_window_tokens_read=32 * 528)
    events = [step(9.6, **counted), step(10.2, **counted),
              step(10.7, **counted), step(10.8)]
    ms = 1_000_000
    window = (5 * 10**9, 6 * 10**9)
    calls = lambda n, at: [(window[0] + at * ms + 2 * i * ms, ms)  # noqa: E731
                           for i in range(n)]
    ops = {kind: [] for kind in dsa.KINDS}
    ops.update(span=window, index=calls(6, 0), sparse=calls(6, 100),
               gather=calls(6, 200), select=calls(3, 300),
               window=calls(6, 400), window_rest=calls(2, 500),
               full_rest=[(window[0] - 5 * ms, 10 * ms)])  # half outside
    ctx = context(cfg, ops, events)
    assert dsa.step_counters(ctx)["steps"] == 2
    assert dsa.step_counters(ctx, traced_only=False)["steps"] == 3
    # 6 + 6 + 6 + 3 ms and the loop's 5 ms inside the window, less the 3 ms
    # of index calls that lie inside the loop's interval (a union), over the
    # two steps that started in the window
    assert reader("dsa.device_ms_per_step").read(ctx) == pytest.approx(11.5)
    assert reader("swa.device_ms_per_step").read(ctx) == pytest.approx(4.0)
    assert reader("dsa.selected_share").read(ctx) == pytest.approx(
        100 * 2048 / 33000)
    peak = peaks.peaks_for("TPU v5 lite")
    ref = hybrid.reference_for(cfg)
    flops = 2 * visible * 2 * 64 * 128 / peak["bf16_flops"]
    nbytes = 2 * (visible // 4) * 256 / peak["hbm_bytes_per_s"]
    assert flops > nbytes  # four askers a document: the products bound it
    assert reader("dsa.index_roofline_share").read(ctx) == pytest.approx(
        100 * 3 * flops / 6e-3)
    least = max(2 * 32 * 2048 * 1152 / peak["hbm_bytes_per_s"],
                ref.sparse_decode_flops(cfg, 2 * 32 * 2048) / peak["bf16_flops"])
    assert reader("dsa.sparse_decode_roofline_share").read(ctx) == (
        pytest.approx(100 * 3 * least / 12e-3))
    least = max(2 * 32 * 513 * 2176 / peak["hbm_bytes_per_s"],
                ref.window_decode_flops(cfg, 2 * 32 * 513) / peak["bf16_flops"])
    assert reader("swa.decode_roofline_share").read(ctx) == pytest.approx(
        100 * 3 * least / 6e-3)


@pytest.mark.parametrize("name", [
    "dsa.device_ms_per_step", "dsa.index_roofline_share",
    "dsa.sparse_decode_roofline_share", "dsa.selected_share",
    "swa.device_ms_per_step", "swa.decode_roofline_share"])
def test_a_program_without_the_operations_or_counters_reads_nothing(cfg, name):
    """The parent's program, a CPU run: ``None``, never an error."""
    empty = {kind: [] for kind in dsa.KINDS}
    empty.update(span=None, events=0)
    ctx = context(cfg, empty, [step(10.2, decode_kv_tokens_visible=5)])
    assert reader(name).read(ctx) is None
    assert reader(name).read({"cfg": cfg}) is None


def test_no_share_passes_its_roofline_on_the_counts_alone(cfg):
    """The counts are the published bytes and the visible pairs: a kernel
    that moved exactly those at the stream rate reads 100, not more."""
    ref = hybrid.reference_for(cfg)
    peak = peaks.peaks_for("TPU v5 lite")
    assert ref.index_scores_min_bytes(cfg, 1) < 128 * 2 + 1
    assert ref.sparse_decode_min_bytes(cfg, 1) == 1152 < 1280  # as held
    assert ref.window_decode_min_bytes(cfg, 1) == 2176 < 2304
    assert peak["hbm_bytes_per_s"] > 0


def test_the_recorded_trace_holds_every_kind_and_the_kernels_by_name(cfg):
    with open(RECORDED) as f:
        recorded = json.load(f)
    ops = dsa.classify(recorded["events"], cfg)
    # Four engine steps and a bit: three full and three sliding layers each.
    assert len(ops["index"]) == len(ops["sparse"]) == len(ops["gather"])
    assert len(ops["index"]) == len(ops["window"]) >= 12
    assert ops["select"] and ops["full_rest"]
    names = {name.split(" = ")[0].rstrip(".0123456789")
             for name, _, _ in recorded["events"]}
    assert {"%attention._index_scores", "%attention._sparse_latent_decode_step",
            "%attention._window_latent_decode_step"} <= names
    # A call of the index kernel takes 1.6-1.8 ms at these contexts, the
    # sparse kernel and the windowed one a tenth of that.
    per_call = lambda kind: sum(d for _, d in ops[kind]) / len(ops[kind]) / 1e6  # noqa: E731
    assert 1.2 < per_call("index") < 2.4
    assert 0.05 < per_call("sparse") < 0.3 and 0.05 < per_call("window") < 0.3
    assert 0.8 < per_call("gather") < 1.5


def test_the_recorded_windows_device_time_by_kind(cfg):
    """The whole traced window (3.02 s, 93 steps): what each kind took."""
    with open(RECORDED) as f:
        summary = json.load(f)["summary"]
    by_kind = summary["ms_by_kind"]
    assert set(by_kind) == set(dsa.KINDS) | {"other"}
    new_parts = sum(by_kind[k] for k in (
        "index", "select", "gather", "sparse", "full_rest"))
    assert 0.6 < new_parts / summary["window_ms"] < 0.75
    top = {(name, kind) for name, kind, *_ in summary["top"]}
    assert ("%attention._index_scores", "index") in top
    assert ("%ragged-dot-stationary", "other") in top
