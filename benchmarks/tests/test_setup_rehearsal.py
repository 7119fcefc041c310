"""CPU rehearsal of the ``setup.*`` metrics: the toy cells of ``tests/toy``
and ``tests/toy_hybrid`` through ``run_cell``, their specs given the
``setup.*`` entries of ``BENCHMARK.json`` (the toy specs themselves are left
as they are), every metric listed for a cell's kind reading a number. The
numbers are a CPU's and say nothing; that they are there, disjoint and add up
is what the chip run relies on."""

import json
import os
import time

import pytest

import run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TIMES = {"serve": ("trace_lower_s", "backend_compile_s", "backend_open_s",
                   "engine_init_s", "warm_run_s", "unattributed_s"),
         "train": ("trace_lower_s", "backend_compile_s", "backend_open_s",
                   "first_epoch_run_s", "unattributed_s")}


def with_setup_metrics(toy: str, cell: str, kind: str) -> dict:
    """The toy spec plus the real benchmark's ``setup.*`` entries, listed
    for ``cell`` where they are listed for the real cells of its kind."""
    with open(os.path.join(HERE, toy, "spec.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    like = {"serve": "sc2-3b-generation", "train": "resnet50-train-1chip"}[kind]
    for m in real["per_layer"]:
        if m["name"].startswith("setup.") and like in m["workloads"]:
            spec["per_layer"].append({**m, "workloads": [cell]})
    return spec


@pytest.mark.parametrize("toy, cell, kind", [
    ("toy", "toy-lm.closed", "serve"),
    ("toy", "toy-resnet.steps", "train"),
    ("toy_hybrid", "toy-hybrid.closed", "serve"),
])
def test_every_setup_metric_of_the_cell_reads_a_number(toy, cell, kind, capsys):
    spec = with_setup_metrics(toy, cell, kind)
    listed = {m["name"] for m in spec["per_layer"]
              if m["name"].startswith("setup.")}
    assert len(listed) == len(TIMES[kind]) + 2
    t_start = time.perf_counter()
    out = bench.run_cell(cell, 37, 2.0, True, spec=spec, allow_cpu=True,
                         t_start=t_start)
    assert out["correct"] is True
    got = {n: m["value"] for n, m in out["metrics"].items() if n in listed}
    assert set(got) == listed
    assert all(v >= 0 for v in got.values())
    assert got["setup.programs_compiled"] >= got["setup.cache_misses"] >= 0
    assert got["setup.programs_compiled"] > 0
    assert got["setup.backend_compile_s"] > 0 and got["setup.trace_lower_s"] > 0
    run_s = got["setup.warm_run_s" if kind == "serve"
                else "setup.first_epoch_run_s"]
    assert run_s > 0
    # The stretch is this pytest process's, from its start to this cell's
    # window: longer than the cell's own set-up, which it holds.
    stretch = sum(got[f"setup.{name}"] for name in TIMES[kind])
    assert stretch >= time.perf_counter() - t_start - 60 and stretch > 0
    said = capsys.readouterr().out
    assert "[bench] setup:" in said and "programs compiled" in said
