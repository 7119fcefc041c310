"""The two readers of the engine's prefill programs, on a recorded toy trace:
``data/prefill_trace_recorded.json`` holds the engine tracer's ``step`` and
``prefill.chunk`` slices of the same six prompts (10 to 601 tokens, 1,259 to
prefill) through the tree before PR 32 (``ladder``: a program a power-of-two
chunk) and through PR 32's (``widths``: a padded program a piece), recorded
on the CPU by ``record_prefill_trace.py``."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "prefill_trace_recorded.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_prefill_programs_per_step_counts_slices_on_either_tree(recorded):
    read = reader("engine.prefill_programs_per_step").read
    # 18 steps either way; 27 chunk programs through the ladder, 9 pieces
    assert read({"engine_events": recorded["ladder"]}) == pytest.approx(27 / 18)
    assert read({"engine_events": recorded["widths"]}) == pytest.approx(9 / 18)
    # instants and async request spans are no slices
    noise = [{"name": "prefill.chunk", "ph": "i", "args": {}},
             {"name": "state.reset", "ph": "i", "args": {}}]
    assert read({"engine_events": recorded["widths"] + noise}) == pytest.approx(0.5)
    # steps that prefilled nothing read 0; no step, nothing to read
    steps = [e for e in recorded["widths"] if e["name"] == "step"]
    assert read({"engine_events": steps}) == 0.0
    assert read({"engine_events": []}) is None
    assert read({}) is None


def test_prefill_pad_share_is_padding_over_width(recorded):
    read = reader("engine.prefill_pad_share").read
    pieces = [e["args"] for e in recorded["widths"]
              if e["name"] == "prefill.chunk"]
    assert sum(p["tokens"] for p in pieces) == 1259
    assert sum(p["width"] for p in pieces) == 1536
    assert all(p["width"] % 64 == 0 and 0 <= p["width"] - p["tokens"] < 64
               for p in pieces)
    assert read({"engine_events": recorded["widths"]}) == pytest.approx(
        100 * (1 - 1259 / 1536))
    # the parent's slices carry no width: nothing to read, and no error
    assert read({"engine_events": recorded["ladder"]}) is None
    assert read({"engine_events": []}) is None
    assert read({}) is None
    # whole pieces pad nothing
    whole = [{"name": "prefill.chunk", "ph": "X",
              "args": {"tokens": 256, "start": 0, "width": 256}}]
    assert read({"engine_events": whole}) == 0.0
