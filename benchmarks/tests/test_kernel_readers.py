"""The reader of ``kernels.paged_fetch_amplification`` on a hand-made list of
the engine's events: its arithmetic, and that a program without the counters
(this PR's parent) reads nothing."""

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def step(**args):
    return {"name": "step", "ph": "X", "ts": 0.0, "dur": 1.0, "args": args}


def test_fetch_amplification_is_fetched_over_visible_across_steps():
    read = reader("kernels.paged_fetch_amplification").read
    events = [
        step(decode_rows=2, decode_kv_tokens_fetched=384,
             decode_kv_tokens_visible=300),
        {"name": "decode_kv_tokens_fetched", "ph": "C", "args": {"value": 384}},
        step(decode_rows=0),  # a step of prefill alone: no decode dispatch
        {"name": "dispatch.stage", "ph": "X", "ts": 0.0, "dur": 1.0,
         "args": {"decode_kv_tokens_fetched": 10 ** 9}},
        step(decode_rows=1, decode_kv_tokens_fetched=128,
             decode_kv_tokens_visible=20),
    ]
    assert read({"engine_events": events}) == (384 + 128) / (300 + 20)
    # the parent's steps carry no such counter; a training cell has no events
    assert read({"engine_events": [step(decode_rows=2)]}) is None
    assert read({"engine_events": []}) is None
    assert read({}) is None
