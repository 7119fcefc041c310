"""``harness/setup.py``: the set-up split over a timeline worked out by hand
(the times are disjoint and add up to the stretch exactly; a compile that
ends after the window has opened is left out; a timeline without the slices
reads ``None``), and every ``setup.*`` reader through ``ctx`` as ``run.py``
hands it over."""

import importlib.util
import json
import os

import pytest

from harness import setup
from harness.phases import slices_of
from harness.spans import Spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000  # ns
P0 = 7_000_000_000_000  # perf_counter ns at the process's start


def slice_event(name, start_ms, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": start_ms * 1e3, "dur": dur_ms * 1e3,
            "args": {"perf_counter_ns": P0 + int(start_ms * MS), **args}}


def compile_event(start_ms, trace, lower, backend, between=0.0, **args):
    """A compile whose parts take ``trace``, ``lower`` and ``backend`` ms and
    JAX's own Python between them ``between``."""
    return slice_event(
        "compile", start_ms, trace + lower + backend + between,
        trace_s=trace / 1e3, lower_s=lower / 1e3, backend_s=backend / 1e3,
        **{"fun_name": "jit(f)", "cache": "hit", **args})


def serving_by_hand():
    """A serving process whose window opens 10,000 ms after it started:
    imports until 1,000; the chip opened 1,000-3,000; a weights generator
    compiled 3,100-3,400 (trace 50, lower 50, backend 190, 10 between; under
    the threshold: a miss, never written); the engine built 5,000-6,000 with
    its pools 5,100-5,700 and a compile inside, 5,200-5,500 (20 / 30 / 250);
    the prefill programs built 6,500-8,500 around two compiles, 6,600-7,400
    (100 / 200 / 500) and 7,500-8,300 (100 / 200 / 480, 20 between); and a
    decode program compiled 9,900-10,300, across the window's opening."""
    return [
        slice_event("process.start", 0, 1000, source="proc_stat"),
        slice_event("backend.open", 1000, 2000, platform="tpu"),
        compile_event(3100, 50, 50, 190, between=10, cache="miss"),
        compile_event(5200, 20, 30, 250),
        slice_event("engine.init.pools", 5100, 600, bytes=1 << 30),
        slice_event("engine.init", 5000, 1000, slots=32, pages=12288),
        compile_event(6600, 100, 200, 500),
        compile_event(7500, 100, 200, 480, between=20, cache="miss", written=True),
        slice_event("engine.build_prefill_programs", 6500, 2000, programs=2),
        compile_event(9900, 100, 100, 200, cache="miss"),
    ]


OPENED = P0 + 10_000 * MS


def test_the_times_are_disjoint_and_add_up_to_the_stretch():
    got = setup.split(slices_of(serving_by_hand()), [], OPENED)
    assert got["stretch_s"] == 10.0
    assert got["backend_open_s"] == 2.0
    assert got["backend_compile_s"] == pytest.approx(0.19 + 0.25 + 0.5 + 0.48)
    # the rest of each compile: trace, lower and what lies between the parts
    assert got["trace_lower_s"] == pytest.approx(0.11 + 0.05 + 0.3 + 0.32)
    assert got["engine_init_s"] == pytest.approx(1.0 - 0.3)
    assert got["warm_run_s"] == pytest.approx(4.0 - 0.8 - 0.8)
    assert got["first_epoch_run_s"] is None
    # imports 1.0, then 3.0-5.0 less the generator's compile
    assert got["unattributed_s"] == pytest.approx(1.0 + 2.0 - 0.3)
    assert sum(got[name] or 0.0 for name in setup.TIMES) == pytest.approx(
        got["stretch_s"], abs=1e-9)
    # the compile that ends inside the window is the window's
    assert got["programs_compiled"] == 4
    assert got["cache_misses"] == 2 and got["written"] == 1


def test_a_training_process_reads_the_first_epoch():
    """``trainer.init`` 2,000-2,400 and the first epoch 3,000-9,000 with the
    step's compile inside it, 3,100-8,100 (1,000 / 500 / 3,500); later epochs
    are the window's."""
    kept = [
        slice_event("process.start", 0, 500, source="proc_stat"),
        slice_event("backend.open", 500, 1000, platform="tpu"),
        slice_event("trainer.init", 2000, 400, resumed_at_epoch=0),
        compile_event(3100, 1000, 500, 3500, cache="miss"),
    ]
    ring = [slice_event("step", 3050, 5100, epoch=0, step=0),
            slice_event("epoch", 3000, 6000, epoch=0),
            slice_event("epoch", 9600, 900, epoch=1)]
    got = setup.split(slices_of(kept), slices_of(ring), P0 + 9_500 * MS)
    assert got["first_epoch_run_s"] == pytest.approx(0.4 + 6.0 - 5.0)
    assert got["backend_compile_s"] == 3.5 and got["trace_lower_s"] == 1.5
    assert got["engine_init_s"] is None and got["warm_run_s"] is None
    assert got["unattributed_s"] == pytest.approx(0.5 + 0.5 + 0.6 + 0.5)
    assert sum(got[name] or 0.0 for name in setup.TIMES) == pytest.approx(9.5)


def test_nested_and_overlapping_slices_are_counted_once():
    """A backend that opens inside the first program's lowering (an entry
    point that never asks for its devices first) is the backend's time, and
    a compile inside another's trace is not counted twice."""
    kept = [
        slice_event("process.start", 0, 100, source="import"),
        compile_event(100, 100, 1300, 100),          # 100-1,600
        slice_event("backend.open", 300, 1000, platform="tpu"),
        compile_event(2000, 1000, 100, 100),         # 2,000-3,200, and in its trace:
        compile_event(2200, 100, 100, 300),          # 2,200-2,700
    ]
    got = setup.split(slices_of(kept), [], P0 + 4_000 * MS)
    assert got["backend_open_s"] == 1.0
    assert got["backend_compile_s"] == pytest.approx(0.1 + 0.1 + 0.3)
    assert got["trace_lower_s"] == pytest.approx(0.4 + 1.1 - 0.3 - 0.2 + 0.2)
    assert sum(got[name] or 0.0 for name in setup.TIMES) == pytest.approx(4.0)


def test_a_timeline_without_the_slices_reads_none():
    assert setup.split([], [], OPENED) is None
    # slices, but no process start to count from
    assert setup.split(slices_of(serving_by_hand()[1:]), [], OPENED) is None


class ParentTracer:
    """The process tracer of a program from before the set-up slices."""

    events = ()


@pytest.mark.parametrize("metric", [
    m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]
    if m["name"].startswith("setup.")], ids=lambda m: m["name"])
def test_every_reader_reads_its_number_through_ctx(metric, monkeypatch):
    """``metrics/setup.<name>.py`` as ``run.py`` loads it: the number of the
    hand-made timeline from a ctx that holds the harness's spans, ``None``
    from a program that writes no set-up slices; and the entry says what the
    contract wants of it."""
    from distributed_pytorch_tpu.obs import tracer as program

    assert metric["moves"] == "setup_s" and metric["better"] == "lower"
    assert metric["unit"] in ("s", "count") and metric["workloads"]
    path = os.path.join(ROOT, "benchmarks", "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    spans = Spans()
    spans.rows = [("engine.step", OPENED / 1e9 + 0.5, OPENED / 1e9 + 0.6),
                  ("engine.step", OPENED / 1e9, OPENED / 1e9 + 0.4)]
    made = program.Tracer()
    made.setup_events.extend(serving_by_hand())
    monkeypatch.setattr(program, "_process_tracer", made)
    want = setup.split(slices_of(serving_by_hand()), [], OPENED)
    value = reader.read({"spans": spans})
    key = metric["name"].removeprefix("setup.")
    assert value == want[key]
    assert (value is None) == (key == "first_epoch_run_s")

    monkeypatch.setattr(program, "_process_tracer", ParentTracer())
    assert reader.read({"spans": spans}) is None
