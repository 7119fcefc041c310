"""The reduction from a profiler trace to what the metrics read, checked on a
trace small enough to work out by hand, and on one recorded on the chip
(``data/trace_recorded.json``, cut by ``record_trace.py`` from a traced run of
``resnet50-train-1chip``)."""

import json
import os

import pytest

from harness.trace import (
    WINDOW_SPAN, TraceSummary, breakdown, reduce_trace, short_name, whole_steps,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000  # the trace's clock is in nanoseconds


def by_hand() -> dict:
    """Two chips, a window of 1000 us. Chip 0 runs a ``while`` of 300 us that
    holds two fusions (100 and 150 us), idles 200 us while the host is in
    ``loader.next``, runs an all-reduce of 100 us, idles 10 us (too short to
    name), runs a fusion of 90 us, and idles the last 300 us with no host
    span. Chip 1 runs one fusion of 500 us."""
    chip0 = [
        ["while_tuple", 0, 300 * US],
        ["fusion_bf16_8_8", 10 * US, 100 * US],
        ["fusion_bf16_8_8", 120 * US, 150 * US],
        ["all-reduce_f32_64", 500 * US, 100 * US],
        ["fusion_bf16_8_8", 610 * US, 90 * US],
    ]
    chip1 = [["fusion_bf16_8_8", 100 * US, 500 * US]]
    host = [
        [WINDOW_SPAN, 0, 1000 * US],
        ["train.epoch", 0, 1000 * US],
        ["loader.next", 290 * US, 220 * US],
        ["not.a.harness.span", 0, 1000 * US],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0, 700 * US]]},
            {"name": "XLA Ops", "events": chip0},
            {"name": "Async XLA Ops", "events": [["copy-start_tuple", 0, 900 * US]]},
        ]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": chip1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def test_reduction_of_a_trace_worked_out_by_hand():
    s = reduce_trace(by_hand(), ["loader.next", "train.epoch"])
    assert s.devices == 2
    assert s.window_s == pytest.approx(1000e-6)
    # chip 0 busy 300 + 100 + 90 = 490 us, chip 1 busy 500 us
    assert s.busy_s == pytest.approx(495e-6)
    # self time: the while keeps 300 - 250 = 50 us
    assert s.op_self_s["while_tuple"] == pytest.approx(50e-6)
    assert s.op_self_s["fusion_bf16_8_8"] == pytest.approx((340 + 500) * 1e-6)
    assert s.collective_s == pytest.approx(50e-6)  # 100 us on one of two chips
    assert s.ops[0] == ("fusion_bf16_8_8__x2", pytest.approx(420e-6))
    gaps = dict(s.idle_gaps)
    assert gaps == {
        "loader.next": pytest.approx(200e-6),  # the innermost span wins
        "train.epoch": pytest.approx(300e-6),
        "_gaps_under_20_us_": pytest.approx(10e-6),
    }
    assert sum(gaps.values()) == pytest.approx(1000e-6 - 490e-6)
    assert "not.a.harness.span" not in s.host_spans


def device_only(launches: int) -> dict:
    """One chip traced with the host tracer off: ``launches`` launches of
    ``jit_step`` 1000 us apart, each busy for its first 400 us, and one other
    program before them."""
    modules = [["jit_convert", 0, 5 * US]] + [
        ["jit_step(7)", (100 + 1000 * i) * US, 400 * US] for i in range(launches)]
    ops = [["fusion_f32_8", start, dur] for _, start, dur in modules]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}]}


def test_a_device_only_trace_is_cut_at_whole_steps():
    trace = device_only(5)
    window, steps = whole_steps(trace)
    # from the second launch to the last: three busy stretches, three gaps
    assert window == (1100 * US, 4100 * US) and steps == 3
    s = reduce_trace(trace, ["loader.next"], window=window)
    assert s.window_s == pytest.approx(3000e-6)
    assert s.busy_s == pytest.approx(1200e-6)
    assert dict(s.idle_gaps) == {"_no_host_span_": pytest.approx(1800e-6)}
    assert whole_steps(device_only(2)) == (None, 0)  # too few to cut
    assert whole_steps(by_hand()) == (None, 0)  # one launch: the annotation


def test_a_trace_with_no_device_operation_is_refused():
    trace = by_hand()
    trace["planes"] = [p for p in trace["planes"] if "/device" not in p["name"]]
    with pytest.raises(RuntimeError, match="no device plane"):
        reduce_trace(trace, ["loader.next"])


def test_operation_names_are_shortened_to_name_type_shape():
    hlo = ("%fusion.34 = bf16[12288,16,2,128]{3,2,1,0:T(2,128)(2,1)} "
           "fusion(bf16[1]{0} %p), kind=kLoop, calls=%fused_computation.3")
    assert short_name(hlo) == "fusion_bf16_12288_16_2_128"
    assert short_name("%copy-start = (u32[2]{0}, u32[]{:S(2)}) copy-start(%k)") \
        == "copy-start_tuple"
    assert short_name("dot.16") == "dot"


def brute_force_busy_ns(events, w0, w1):
    """Busy time by counting covered microseconds: slow, obviously right."""
    covered = set()
    for _name, start, dur in events:
        lo, hi = max(start, w0), min(start + dur, w1)
        covered.update(range(lo // US, -(-hi // US)))
    return len(covered) * US


def test_reduction_of_the_recorded_trace():
    path = os.path.join(DATA, "trace_recorded.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with open(path) as f:
        trace = json.load(f)
    s = reduce_trace(trace, ["loader.next", "train.put_batch", "train.step",
                             "train.epoch"])
    assert isinstance(s, TraceSummary) and s.devices == 1
    ops = [e for p in trace["planes"] if p["name"].startswith("/device:")
           for l in p["lines"] for e in l["events"]]
    (w0, w1), = [(st, st + d) for p in trace["planes"] for l in p["lines"]
                 for n, st, d in l["events"] if n == WINDOW_SPAN]
    # to the microsecond the brute force resolves, per operation boundary
    assert s.busy_s * 1e9 == pytest.approx(
        brute_force_busy_ns(ops, w0, w1), abs=2 * US * len(ops) ** 0.5, rel=0.02)
    assert 0 < s.busy_s <= s.window_s
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    out = breakdown(s)
    assert len(out["device_ops"]) == 10 and out["idle_gaps"]
