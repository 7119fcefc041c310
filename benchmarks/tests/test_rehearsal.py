"""CPU rehearsal of ``run.py`` at toy sizes, one cell per driver, through the
same ``run_cell`` the command line calls. The toy configuration, traffic mix,
cell and per-layer metric live in ``tests/toy`` and were added the way
``benchmarks/README.md`` says: files and entries, no edit to the harness."""

import json
import os
import time

import pytest

import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, cell, seed, trace, seconds=1.5, **kw):
    return bench.run_cell(cell, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def metrics_of(spec, cell, group):
    return {m["name"] for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", ["toy-lm.closed", "toy-resnet.steps"])
def test_untraced_run_reports_the_end_to_end_metrics(spec, cell):
    out = run(spec, cell, 2**31 + 11, False)
    assert RESULT_KEYS <= set(out) and "breakdown" not in out
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == metrics_of(spec, cell, "end_to_end")
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"  # stamped as what it is


@pytest.mark.parametrize(
    "cell", ["toy-lm.closed", "toy-resnet.steps", "toy-resnet.dp4"])
def test_traced_run_reports_the_per_layer_metrics(spec, cell):
    out = run(spec, cell, 12, True)
    assert out["correct"] is True
    want = metrics_of(spec, cell, "per_layer")
    if cell != "toy-resnet.dp4":
        want.discard("collectives.exposed_ms_per_step")  # nothing to read
    assert set(out["metrics"]) == want
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    if cell.startswith("toy-resnet"):
        assert out["metrics"]["programs.compiles_in_window.train"]["value"] == 0
    else:
        assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
        assert out["metrics"]["toy.requests_counted"]["value"] > 0


def test_whole_passes_only_and_the_seed_changes_no_length(spec):
    with open(os.path.join(TOY, "traffic/toy_closed.json")) as f:
        deck = json.load(f)["deck"]
    for seed in (5, 2**31 + 6):
        sampled = []

        def after(cell, weights, sample, check, sampled=sampled):
            sampled.extend(sample)

        out = run(spec, "toy-lm.closed", seed, False,
                  hooks={"after_check": after})
        assert out["attempted"] > 0 and out["attempted"] % len(deck) == 0
        assert sampled
        for r in sampled:  # lengths and order come from the deck alone
            assert [len(r.prompt), r.want] == deck[r.deck_index % len(deck)]


def test_a_missing_accelerator_is_refused(spec):
    with pytest.raises(SystemExit) as e:
        bench.run_cell("toy-lm.closed", 1, 1.0, False, spec=spec)
    assert e.value.code not in (0, None)
