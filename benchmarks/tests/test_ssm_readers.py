"""The readers of a ``serve_hybrid`` cell's per-layer metrics, on a recorded
trace: ``data/ssm_trace_recorded.json`` is 70 ms of ``jamba2-3b-chat``'s
traced window on the v5e (PR 26), cut by ``record_ssm_trace.py``: one decode
program and several prefill chunks, whole operation names. What is pinned is
HOW the mixers' conv and scan operations are recognised
(``harness/hybrid.py``), and the readers' arithmetic."""

import importlib.util
import json
import os
import types

import pytest

from harness import hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "ssm_trace_recorded.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("text, mine", [
    # the scan of a prefill chunk: the loop that carries h [1, N, d_inner]
    ("%while.104 = (s32[], f32[1,16,5120], f32[32,8,1,5120], f32[32,8,1,16], "
     "f32[16,5120], s32[]) while(...)", True),
    # its body, one token
    ("%multiply_reduce_fusion.96 = (f32[5120], f32[1,16,5120]) fusion(...)", True),
    # the decode step's one-token update of every slot's state, and the copy
    # that brings the states in
    ("%fusion.24 = (f32[128,16,5120], f32[128,5120]) fusion(...)", True),
    ("%copy-done.25 = f32[128,16,5120] copy-done(...)", True),
    # the conv's tail [.., K-1, d_inner]: shifted in the decode step, read and
    # written for one slot by a chunk
    ("%dynamic-update-slice.52 = bf16[128,3,5120] dynamic-update-slice(...)", True),
    ("%constant_dynamic-slice_fusion.136 = (bf16[1,3,5120], bf16[1,3,5120]) "
     "fusion(...)", True),
    # with layouts, as the trace has them
    ("%fusion.31 = (f32[128,16,5120]{2,1,0:T(8,128)S(1)}, f32[128,5120]{1,0:T(8,128)}) "
     "fusion(f32[128,16,5120]{2,1,0:T(8,128)S(1)} %custom-call.64)", True),
    # not the mixers': attention, the KV pool's copies, a feed-forward, the
    # head, a projection of the mixer (no state among its RESULTS, though one
    # among its operands), the gate
    ("%attention._paged_decode_step.3 = bf16[128,20,128] custom-call(...)", False),
    ("%copy.65 = bf16[16385,16,1,128] copy(...)", False),
    ("%fusion.502 = (f32[128], bf16[128,2560]) fusion(...)", False),
    ("%convolution_add_fusion = f32[128,65536] fusion(...)", False),
    ("%fusion.77 = bf16[128,2560]{1,0} fusion(f32[128,16,5120]{2,1,0} %x)", False),
    ("%multiply_fusion.3 = bf16[128,1,5120] fusion(...)", False),
    ("not an instruction", False),
])
def test_which_operations_are_the_mixers(text, mine):
    assert hybrid.is_ssm_op(text, 5120, 16, 4) is mine


def test_result_types_closes_a_tuple_over_nested_layouts():
    text = ("%f = (f32[8]{0:T(128)(2,1)S(1)}, bf16[4,3]{1,0:T(8,128)(2,1)}) "
            "fusion(f32[2,16,5120]{2,1,0} %a)")
    assert hybrid.result_types(text) == (
        "(f32[8]{0:T(128)(2,1)S(1)}, bf16[4,3]{1,0:T(8,128)(2,1)})")


def test_the_mixers_device_time_in_the_recorded_window(cfg, recorded):
    merged = hybrid.ssm_intervals(
        recorded["events"], cfg, tuple(recorded["window"]))
    # 9.97 ms of the 70: a loop and the operations inside it count once
    assert sum(e - s for s, e in merged) == 9_970_940
    loops = [e for e in recorded["events"] if " while(" in e[0]]
    summed = sum(
        d for n, s, d in recorded["events"]
        if hybrid.is_ssm_op(n, 5120, 16, 4))
    assert loops and summed > 9_970_940  # the plain sum counts bodies twice
    # clipped to the window it is handed
    half = hybrid.ssm_intervals(recorded["events"], cfg, (0, 35_000_000))
    assert 0 < sum(e - s for s, e in half) < 9_970_940
    # another configuration's widths find nothing here
    other = dict(cfg, hidden_size=3072)
    assert hybrid.ssm_intervals(recorded["events"], other, (0, 70_000_000)) == []
    # what the driver keeps of its one reading is the same operations' spans
    spans = [(s, d) for n, s, d in recorded["events"]
             if hybrid.is_ssm_op(n, 5120, 16, 4)]
    ctx = {"ssm_ops": (tuple(recorded["window"]), spans)}
    assert hybrid.ssm_device_seconds(ctx) == pytest.approx(9_970_940 / 1e9)
    assert hybrid.ssm_device_seconds({"ssm_ops": (None, [])}) is None
    assert hybrid.ssm_device_seconds({}) is None


def context(cfg, **kw):
    """A context as ``drivers/serve.py`` builds it: two steps start inside
    the traced window (the second ends after it), both decoding 100 rows at
    30,000 tokens of context, one with two chunks; a third starts after it."""
    step = lambda rows, ctx_tokens, pre: dict(  # noqa: E731
        decode_rows=rows, decode_context=ctx_tokens, prefill_tokens=pre,
        prefill_context=0.0, prefill_keys=0)
    chunk = lambda t_ms, tokens, start: {  # noqa: E731
        "name": "prefill.chunk", "ph": "X", "ts": 0.0, "dur": 1000.0,
        "args": {"perf_counter_ns": int(t_ms * 1e6), "tokens": tokens,
                 "start": start}}
    events = [
        chunk(1010, 128, 0), chunk(1020, 32, 128),
        chunk(5000, 64, 0),  # outside the traced window
        {"name": "state.reset", "ph": "i", "ts": 1.0, "args": {}},
        {"name": "step", "ph": "X", "ts": 0.0, "dur": 1.0,
         "args": {"perf_counter_ns": 10**9}},
        {"name": "step", "ph": "X", "ts": 2.0, "dur": 1.0,
         "args": {"perf_counter_ns": 10**9 + 50_000_000}},
    ]
    ctx = dict(
        cfg=cfg, device_kind="TPU v5 lite", traced=(1.0, 1.1),
        counters={"plans": [step(100, 30000, 160), step(100, 30000, 0),
                            step(90, 27000, 0)]},
        step_rows=[(1.0, 1.045), (1.05, 1.105), (1.108, 1.14)],
        engine_events=events,
        trace=types.SimpleNamespace(busy_s=0.09),
    )
    ctx.update(kw)
    return ctx


def test_traced_steps_are_those_that_start_in_the_window_with_their_chunks(cfg):
    steps = hybrid.traced_steps(context(cfg))
    assert [(p["decode_rows"], p["prefill_tokens"], p["prefill_chunks"])
            for p in steps] == [(100, 160, 2), (100, 0, 0)]
    assert hybrid.traced_steps({"cfg": cfg}) is None


def test_ssm_device_ms_per_step_and_its_roofline_share(cfg):
    ctx = context(cfg, ssm_device_s=0.012)
    assert reader("ssm.device_ms_per_step").read(ctx) == pytest.approx(6.0)
    ref = hybrid.reference_for(cfg)
    state = ref.state_bytes_per_slot(cfg)
    assert state == 9_318_400
    moved = 2 * state * (200 + 2) + ref.scan_io_bytes_per_token(cfg) * (200 + 160)
    want = 100 * (moved / 819e9) / 0.012
    assert reader("ssm.state_roofline_share").read(ctx) == pytest.approx(want)
    assert 30 < want < 50
    # nothing recognised (the parent's program, a CPU trace): nothing read
    nothing = context(cfg, ssm_device_s=None)
    assert reader("ssm.device_ms_per_step").read(nothing) is None
    assert reader("ssm.state_roofline_share").read(nothing) is None


def test_state_resets_per_step_counts_the_engines_instants(cfg):
    read = reader("state.resets_per_step").read
    assert read(context(cfg)) == pytest.approx(0.5)
    without = context(cfg)
    without["engine_events"] = [
        e for e in without["engine_events"] if e["name"] != "state.reset"]
    assert read(without) is None  # a program without the instant
    assert read({"cfg": cfg}) is None


def test_roofline_share_serve_hybrid_counts_step_by_step(cfg):
    """As ``device.roofline_share.serve`` counts: every weight once a step,
    however many programs the engine made of it."""
    ctx = context(cfg)
    share = reader("device.roofline_share.serve_hybrid").read(ctx)
    ref = hybrid.reference_for(cfg)
    state = ref.state_bytes_per_slot(cfg)
    weights = 2 * (26 * ref.matmul_params(cfg)["mamba"]
                   + 2 * ref.matmul_params(cfg)["attention"]
                   + ref.matmul_params(cfg)["head"])
    first = weights + 2 * state * (100 + 2) + 1024 * (30000 + 100 + 160)
    second = weights + 2 * state * 100 + 1024 * (30000 + 100)
    assert ref.serve_min_bytes(cfg, 100, 160, 30000, 2) == first
    assert share == pytest.approx(100 * ((first + second) / 819e9) / 0.09)
    assert ctx["roofline_bound"] == "memory"
    assert 0 < share < 100
    # a step's second program moves the share only by its one state
    one_chunk = context(cfg)
    one_chunk["engine_events"] = one_chunk["engine_events"][1:]
    fewer = reader("device.roofline_share.serve_hybrid").read(one_chunk)
    assert share - fewer == pytest.approx(100 * (2 * state / 819e9) / 0.09)
    assert reader("device.roofline_share.serve_hybrid").read(
        context(cfg, trace=None)) is None


def test_trace_once_reads_a_file_once_and_gives_load_xplane_s_dict(
        tmp_path, monkeypatch):
    """On a CPU trace (no device plane, so nothing of the mixers'): the same
    dict ``harness/trace.py`` makes of the file, the window's annotation
    kept, and one reading however many ask."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from harness import trace

    profiler = trace.Profiler(str(tmp_path))
    profiler.start()
    profiler.open_window()
    jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    path = profiler.stop()
    reads = []
    from_file = ProfileData.from_file
    monkeypatch.setattr(
        ProfileData, "from_file",
        staticmethod(lambda p: reads.append(p) or from_file(p)))
    once = hybrid.TraceOnce({"mamba_d_state": 16, "mamba_d_conv": 4,
                             "mamba_expand": 2, "hidden_size": 2560})
    assert once(path) == trace.load_xplane(path)
    assert once(path) is once(path)
    assert reads.count(path) == 2  # one of them load_xplane's
    assert once.window is not None and once.ssm == []
