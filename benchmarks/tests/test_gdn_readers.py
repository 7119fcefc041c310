"""The readers of a ``serve_linear_hybrid`` cell's per-layer metrics:
HOW the gated-delta mixers' operations are recognised
(``harness/linear.py``), and the readers' arithmetic.
``data/gdn_trace_recorded.json`` is 150 ms of ``olmo-hybrid-7b-chat``'s traced
window on the v5e (PR 42), cut by ``record_gdn_trace.py``: a few decode
programs and prefill pieces, whole operation names."""

import importlib.util
import json
import os

import pytest

from harness import hybrid, linear, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "gdn_trace_recorded.json")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(cfg):
    return linear.sizes(cfg)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_sizes_are_the_configurations(sizes):
    assert sizes["channels"] == 11520 and sizes["taps"] == 3
    assert sizes["packed"] == (15, 96, 384)  # two heads of 192 on the lanes


@pytest.mark.parametrize("text, kind", [
    # the decode kernel, by its name, whatever it returns
    ("%linear_attention._gated_delta_step.3 = (f32[96,15,384], "
     "f32[96,15,96,384]) custom-call(...)", "step"),
    ("%linear_attention._gated_delta_step = (f32[96,15,384]{2,1,0:T(8,128)}, "
     "f32[96,15,96,384]{3,2,1,0:T(8,128)}) custom-call(...)", "step"),
    # the blocked prefill: Gram and decay matrices, the solve, the products
    ("%fusion.12 = f32[8,1,30,64,64] fusion(...)", "blocks"),
    ("%fusion.13 = f32[8,1,30,64,288] fusion(...)", "blocks"),
    # the system's inverse by halves: diagonal blocks of 1, 2, .. 64
    ("%fusion.14 = f32[8,1,30,16,4,4] fusion(...)", "blocks"),
    ("%fusion.15 = f32[8,1,30,1,64,64] fusion(...)", "blocks"),
    ("%fusion.40 = f32[1,30,64,192] fusion(...)", "blocks"),
    ("%fusion.41 = f32[4,1,30,64,96] fusion(...)", "blocks"),
    ("%reduce-window.3 = f32[8,1,30,64] reduce-window(...)", "blocks"),
    # the scan over a piece's blocks, carrying the state as the recurrence has it
    ("%while.7 = (s32[], f32[1,30,96,192], f32[8,1,30,64,192], "
     "f32[8,1,30,64,96]) while(...)", "blocks"),
    # the conv, its tail, the heads' q / k / v / o, the kept layout
    ("%fusion.5 = f32[96,1,11520] fusion(...)", "rest"),
    ("%dynamic-update-slice.9 = bf16[96,3,11520] dynamic-update-slice(...)",
      "rest"),
    ("%fusion.77 = (f32[96,30,96], f32[96,30,96]) fusion(...)", "rest"),
    ("%fusion.78 = f32[1,512,30,192] fusion(...)", "rest"),
    ("%copy.4 = f32[1,15,96,384] copy(...)", "rest"),
    ("%fusion.80 = f32[96,15,384] fusion(...)", "rest"),
    # not the mixers': the full layers' heads of 128, the MLP, the head, the
    # paged kernel, a projection fused with nothing that gives it away
    ("%attention._paged_decode_step.2 = bf16[96,32,128] custom-call(...)", None),
    ("%fusion.90 = bf16[96,30,128] fusion(...)", None),
    ("%convert_reduce_fusion.3 = bf16[96,11008] fusion(...)", None),
    ("%fusion.91 = f32[96,100352] fusion(...)", None),
    ("%fusion.92 = bf16[96,5760] fusion(...)", None),
    ("%fusion.93 = bf16[3073,16,32,128] fusion(...)", None),
])
def test_how_an_operation_is_recognised(sizes, text, kind):
    assert linear.op_kind(text, sizes) == kind


def step(t_s, **args):
    return {"name": "step", "ph": "X",
            "args": {"perf_counter_ns": int(t_s * 1e9), **args}}


def piece(t_s, **args):
    return {"name": "prefill.chunk", "ph": "X",
            "args": {"perf_counter_ns": int(t_s * 1e9), **args}}


def context(cfg, ops, events, window=(5 * 10**9, 6 * 10**9)):
    return {
        "cfg": cfg, "device_kind": "TPU v5 lite", "traced": (10.0, 11.0),
        "engine_events": events, "gdn_ops": (window, ops),
        "step_rows": [(9.5, 9.9), (10.1, 10.5), (10.6, 10.9)],
        "counters": {"plans": [dict(decode_rows=96)] * 3},
    }


def test_the_readers_arithmetic_by_hand(cfg):
    """Two traced steps of 96 rows x 12 layers; the kernel's 24 calls of a
    millisecond each; a 512-wide and a 64-wide prefill piece."""
    state = 30 * 96 * 192 * 4
    moved = 2 * 96 * 12 * state
    events = [
        step(9.6, state_bytes_moved=moved, state_slots_updated=96 * 12),
        step(10.2, state_bytes_moved=moved, state_slots_updated=96 * 12),
        step(10.7, state_bytes_moved=moved, state_slots_updated=96 * 12),
        piece(10.3, state_blocks=8, tokens=500, width=512),
        piece(10.8, state_blocks=1, tokens=40, width=64),
        piece(9.7, state_blocks=8, tokens=512, width=512),  # before the window
    ]
    ms = 1_000_000
    w0 = 5 * 10**9
    ops = {
        "step": [(w0 + 2 * i * ms, ms) for i in range(24)],
        "blocks": [(w0 + 100 * ms + 3 * i * ms, 2 * ms) for i in range(6)],
        # half of it before the window, and a call under a kernel call
        "rest": [(w0 - 2 * ms, 4 * ms), (w0 + 2 * ms, ms // 2)],
    }
    ctx = context(cfg, ops, events)
    assert linear.state_bytes_moved(ctx) == 2 * moved
    assert linear.traced_pieces(ctx) == [8, 1]
    # 24 + 12 + 2 ms inside the window (the kernel's first call overlaps the
    # conv's last millisecond; the short call lies under a kernel call), over
    # the two steps that started in it
    assert reader("gdn.device_ms_per_step").read(ctx) == pytest.approx(
        (24 + 12 + 2 - 1) / 2)
    peak = peaks.peaks_for("TPU v5 lite")
    assert reader("gdn.state_roofline_share").read(ctx) == pytest.approx(
        100 * (2 * moved / peak["hbm_bytes_per_s"]) / 24e-3)
    ref = hybrid.reference_for(cfg)
    least = sum(
        12 * max(ref.gdn_blocks_flops(cfg, 64 * blocks) / peak["bf16_flops"],
                 ref.gdn_blocks_min_bytes(cfg, 64 * blocks, 1)
                 / peak["hbm_bytes_per_s"])
        for blocks in (8, 1))
    assert reader("gdn.blocks_roofline_share").read(ctx) == pytest.approx(
        100 * least / 12e-3)


def test_the_floors_count_what_cannot_be_avoided(cfg):
    """The state once in and once out, nothing else: a kernel that moved
    exactly that at the stream rate reads 100, not more."""
    ref = hybrid.reference_for(cfg)
    assert ref.gdn_step_state_bytes(cfg, 1) == 2 * 2_211_840
    assert ref.state_bytes_per_slot(cfg) == 12 * (2_211_840 + 3 * 11520 * 2)
    # a block and head: 2 Gram products, the solve, four products with the state
    assert ref.gdn_blocks_flops(cfg, 64) == 30 * (
        2 * 2 * 64 * 64 * 96 + 64 * 64 * 288 + 3 * 2 * 64 * 96 * 192
        + 2 * 64 * 64 * 192)
    assert ref.gdn_blocks_flops(cfg, 65) == 2 * ref.gdn_blocks_flops(cfg, 64)
    assert ref.kv_bytes_per_token(cfg) == 4 * 2 * 30 * 128 * 2  # published


@pytest.mark.parametrize("name", [
    "gdn.device_ms_per_step", "gdn.state_roofline_share",
    "gdn.blocks_roofline_share"])
def test_a_program_without_the_operations_or_counters_reads_nothing(cfg, name):
    """The parent's program, a CPU run: ``None``, never an error."""
    empty = {kind: [] for kind in linear.KINDS}
    ctx = context(cfg, empty, [step(10.2, decode_rows=5)])
    assert reader(name).read(ctx) is None
    assert reader(name).read({"cfg": cfg}) is None
    # operations but no counters (a program that times its kernel and does
    # not count its bytes): the shares read nothing, the time reads
    ops = dict(empty, step=[(5 * 10**9, 10**6)], blocks=[(5 * 10**9, 10**6)])
    ctx = context(cfg, ops, [step(10.2, decode_rows=5)])
    if name != "gdn.device_ms_per_step":
        assert reader(name).read(ctx) is None


def test_the_recorded_trace_holds_every_kind_and_the_kernel_by_name(cfg):
    with open(RECORDED) as f:
        recorded = json.load(f)
    ops = linear.classify(recorded["events"], linear.sizes(cfg))
    # 100 ms: two or three decode programs of 12 gated-delta layers each.
    assert len(ops["step"]) >= 24
    assert len(ops["blocks"]) > 500 and len(ops["rest"]) > 500
    names = {name.split(" = ")[0].rstrip(".0123456789")
             for name, _, _ in recorded["events"]}
    assert {"%linear_attention._gated_delta_step",
            "%attention._paged_decode_step"} <= names
    # A call of the decode kernel moves 96 states of 2.2 MB in and out:
    # 0.52 ms at 819 GB/s; it took 0.65-0.66.
    per_call = sum(d for _, d in ops["step"]) / len(ops["step"]) / 1e6
    assert 0.52 < per_call < 0.8
    # The system's inverse by halves is among the blocks' operations.
    assert any(",30,8,4,4]" in name for name, _, _ in recorded["events"]
               if linear.op_kind(name, linear.sizes(cfg)) == "blocks")


def test_the_recorded_windows_device_time_by_kind():
    """The whole traced window (3.02 s, 85 steps): what each kind took."""
    with open(RECORDED) as f:
        summary = json.load(f)["summary"]
    by_kind = summary["ms_by_kind"]
    assert set(by_kind) == set(linear.KINDS) | {"other"}
    assert 0.18 < by_kind["step"] / summary["window_ms"] < 0.28
    mixers = sum(by_kind[k] for k in linear.KINDS) / summary["window_ms"]
    assert 0.28 < mixers < 0.42
    top = {(name, kind) for name, kind, *_ in summary["top"]}
    assert ("%linear_attention._gated_delta_step", "step") in top
    assert ("%attention._paged_decode_step", "other") in top
