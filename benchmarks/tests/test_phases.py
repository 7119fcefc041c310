"""``harness/phases.py``: the program's slices mapped onto a trace's clock and
its idle gaps split among them, on a trace worked out by hand with a known
offset; the two ways onto the clock against each other on a real (CPU)
profile; and both drivers' CPU rehearsal reporting the host-side metrics."""

import copy
import json
import os
import time

import pytest

import run as bench
from harness import phases
from harness.spans import Spans
from harness.trace import WINDOW_SPAN, Profiler, load_xplane

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
US = 1000
OFFSET = 7_000_000 * US - 123  # trace ns less perf_counter ns
P0 = 5_000_000_000_000  # perf_counter ns at the traced window's opening


def slice_event(name, start_us, dur_us, **args):
    """A tracer's slice that began ``start_us`` after the window opened."""
    return {"name": name, "ph": "X", "ts": 50.0 + start_us, "dur": float(dur_us),
            "args": {"perf_counter_ns": P0 + start_us * US, **args}}


def by_hand():
    """One chip, a traced window of 1000 us that opens at trace time
    ``P0 + OFFSET``. Two engine steps of 400 us, 100 us apart; in each the
    device runs from 150 to 350 us after the step begins. So in each step the
    device idles its first 150 us (under schedule 0-20, nothing 20-30, two
    keys 30-60 and 60-90, stage 90-120, launch 120-150) and its last 50 us
    (readback.wait 350-380, readback.resolve 380-400), and between and after
    the steps it idles under no slice at all (100 us twice)."""
    events, annotations, rows, ops = [], [], [], []
    for k in range(2):
        at = 500 * k
        events += [
            slice_event("schedule", at, 20, step=k),
            slice_event("dispatch.key", at + 30, 30, step=k, slot=0),
            slice_event("dispatch.key", at + 60, 30, step=k, slot=1),
            slice_event("dispatch.stage", at + 90, 30, step=k),
            slice_event("dispatch.launch", at + 120, 30, step=k),
            slice_event("dispatch", at + 30, 125, step=k),
            slice_event("readback.wait", at + 350, 30, step=k),
            slice_event("readback.resolve", at + 380, 20, step=k),
            slice_event("readback", at + 345, 55, step=k),
            slice_event("step", at, 400, step=k, decode_rows=2),
        ]
        annotations.append(["engine.step", P0 + OFFSET + at * US, 400 * US])
        rows.append(("engine.step", (P0 + at * US) / 1e9,
                     (P0 + (at + 400) * US) / 1e9))
        ops.append(["fusion_bf16_8", P0 + OFFSET + (at + 150) * US, 200 * US])
    # a request born and admitted inside the window, and one born before it
    events += [
        {"name": "request", "cat": "request", "ph": "b", "id": 4, "ts": 100.0},
        {"name": "admit", "cat": "request", "ph": "n", "id": 4, "ts": 350.0},
        {"name": "admit", "cat": "request", "ph": "n", "id": 4, "ts": 900.0},
        {"name": "admit", "cat": "request", "ph": "n", "id": 3, "ts": 120.0},
    ]
    xplane = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_run", s, d] for _, s, d in ops]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [WINDOW_SPAN, P0 + OFFSET, 1000 * US]] + annotations}]},
    ]}
    spans = Spans()
    spans.rows = rows
    ctx = dict(engine_events=events, spans=spans, device_kind="TPU v5 lite",
               traced=(P0 / 1e9 - 1e-6, (P0 + 1000 * US) / 1e9))
    return xplane, ctx


def test_the_innermost_slice_names_each_stretch():
    spans = [("step", 0, 100), ("dispatch", 10, 60), ("dispatch.key", 10, 30),
             ("dispatch.key", 30, 50), ("readback", 70, 90), ("step", 120, 130)]
    assert phases.innermost(spans) == [
        (0, 10, "step"), (10, 30, "dispatch.key"), (30, 50, "dispatch.key"),
        (50, 60, "dispatch"), (60, 70, "step"), (70, 90, "readback"),
        (90, 100, "step"), (120, 130, "step")]
    assert phases.innermost([]) == []


def test_the_offset_and_the_split_of_a_trace_worked_out_by_hand(monkeypatch, capsys):
    xplane, ctx = by_hand()
    assert phases.offset_from_annotations(xplane, ctx) == pytest.approx(
        OFFSET, abs=US)  # a float of seconds resolves ~1 us at this size
    monkeypatch.setattr(phases, "newest_trace", lambda: "a.xplane.pb")
    monkeypatch.setattr(phases, "load_xplane", lambda path: xplane)
    monkeypatch.setattr(phases, "offset_from_start_time",
                        lambda path: OFFSET + 40 * US)  # 40 us off
    got = phases.reduced(ctx)
    assert got is ctx["phases"] and got["steps"] == 2
    idle = {k: v / US for k, v in got["idle_ns"].items()}
    assert idle == {
        "schedule": pytest.approx(40, abs=2), "step": pytest.approx(20, abs=2),
        "dispatch.key": pytest.approx(120, abs=2),
        "dispatch.stage": pytest.approx(60, abs=2),
        "dispatch.launch": pytest.approx(60, abs=2),
        "readback.wait": pytest.approx(60, abs=2),
        "readback.resolve": pytest.approx(40, abs=2),
        "_no_host_span_": pytest.approx(200, abs=2),
    }
    assert sum(idle.values()) == pytest.approx(1000 - 400)
    said = capsys.readouterr().out
    assert "clock check" in said and "+40.0 us" in said
    # what the readers make of it
    assert phases.idle_ms_per_step(ctx, ("dispatch.key",)) == pytest.approx(
        0.060, abs=0.001)
    assert phases.idle_ms_per_step(
        ctx, ("readback", "readback.wait", "readback.resolve")
    ) == pytest.approx(0.050, abs=0.001)
    assert phases.host_ms_per_step(ctx, ("dispatch.key",)) == pytest.approx(0.060)
    assert phases.host_ms_per_step(ctx, ("readback.wait",)) == pytest.approx(0.030)
    assert phases.host_ms_per_step(ctx, ("loader.index",)) is None
    assert phases.admit_wait_ms_p50(ctx) == pytest.approx(0.25)


def test_nothing_to_read_reads_none(monkeypatch):
    xplane, ctx = by_hand()
    monkeypatch.setattr(phases, "newest_trace", lambda: "a.xplane.pb")
    monkeypatch.setattr(phases, "load_xplane", lambda path: copy.deepcopy(xplane))
    monkeypatch.setattr(phases, "offset_from_start_time", lambda path: None)
    # a program from before these slices: no perf_counter_ns, no children
    old = dict(ctx, engine_events=[
        {"name": "schedule", "ph": "X", "ts": 1.0, "dur": 2.0, "args": {"step": 0}}])
    assert phases.reduced(old) is None
    assert phases.idle_ms_per_step(old, ("dispatch.key",)) is None
    assert phases.host_ms_per_step(old, ("dispatch.key",)) is None
    assert phases.admit_wait_ms_p50(old) is None
    # the CPU rehearsal: no device number under a device metric's name
    assert phases.reduced(dict(ctx, device_kind="cpu")) is None
    # a trace without a device plane
    no_device = copy.deepcopy(xplane)
    no_device["planes"] = no_device["planes"][1:]
    monkeypatch.setattr(phases, "load_xplane", lambda path: no_device)
    assert phases.reduced(dict(ctx)) is None


def test_both_ways_onto_the_clock_agree_on_a_real_profile(tmp_path):
    """The annotation twins against the trace's start time, on a profile
    taken here: the check every traced serving run prints."""
    import jax
    import jax.numpy as jnp

    spans = Spans()
    spans.recording = True
    profiler = Profiler(str(tmp_path / "trace"))
    profiler.start()
    profiler.open_window()
    spans.annotate = True
    step = jax.jit(lambda x: x @ x)
    for _ in range(20):
        with spans.span("engine.step"):
            step(jnp.ones((64, 64))).block_until_ready()
    path = profiler.stop()
    ctx = dict(spans=spans, traced=(profiler.t0, profiler.t1))
    exact = phases.offset_from_annotations(load_xplane(path), ctx)
    by_start = phases.offset_from_start_time(path)
    assert exact is not None and by_start is not None
    assert abs(by_start - exact) < 500 * US  # the issue's 0.5 ms


NEW_SERVE = ["engine.host_ms_per_step.keys", "engine.host_ms_per_step.stage",
             "engine.host_ms_per_step.launch",
             "engine.host_ms_per_step.readback_wait", "sched.admit_wait_ms_p50"]
NEW_TRAIN = ["loader.host_ms_per_batch.index", "loader.host_ms_per_batch.stack",
             "train.host_ms_per_step.put_batch", "train.host_ms_per_step.dispatch"]
NEW_DEVICE = ["device.idle_ms_per_step.keys",
              "device.idle_ms_per_step.dispatch_rest",
              "device.idle_ms_per_step.readback",
              "device.idle_ms_per_step.loader",
              "device.idle_ms_per_step.put_batch"]


@pytest.fixture(scope="module")
def spec():
    """The toy spec with the new metrics' entries of ``BENCHMARK.json`` put
    on its cells (extended here; the toy's file stays as it is)."""
    with open(os.path.join(TOY, "spec.json")) as f:
        toy = json.load(f)
    with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_SERVE + NEW_TRAIN + NEW_DEVICE:
        serving = real[name]["moves"] != "train_samples_per_s"
        toy["per_layer"].append(dict(real[name], workloads=(
            ["toy-lm.closed"] if serving
            else ["toy-resnet.steps", "toy-resnet.dp4"])))
    return toy


@pytest.mark.parametrize("cell,new", [
    ("toy-lm.closed", NEW_SERVE), ("toy-resnet.steps", NEW_TRAIN),
    ("toy-resnet.dp4", NEW_TRAIN)])
def test_the_rehearsal_reports_the_host_side_metrics(spec, cell, new):
    out = bench.run_cell(cell, 12, 1.5, True, spec=spec, allow_cpu=True,
                         t_start=time.perf_counter())
    assert out["correct"] is True
    for name in new:
        assert out["metrics"][name]["value"] > 0, name
        assert out["metrics"][name]["unit"] == "ms"
    assert not set(NEW_DEVICE) & set(out["metrics"])  # no device here
    if cell.startswith("toy-resnet"):
        # index and stack are all the loader does, and the harness's span
        # round the loader's ``next`` times the same work from outside: the
        # same size, though not the same epochs (a tenth of a millisecond
        # a batch here, so no closer than that)
        inner = sum(out["metrics"][n]["value"] for n in new[:2])
        outer = out["metrics"]["loader.host_ms_per_batch"]["value"]
        assert outer / 3 < inner < 3 * outer
