"""CPU rehearsal of the ``serve_cca_moe`` driver at toy sizes, through the
same ``run_cell`` the command line calls. The toy configuration, traffic mix
(with the long stagger: client ``i`` first asks for ``4 i`` tokens) and cell
live in ``tests/toy_cca_moe``, beside ``tests/toy_window_moe``: files and
entries, no edit. Each run compiles the toy's programs, so this file stays
outside tier-1 and is run by hand, as ``test_window_moe_rehearsal.py`` is."""

import json
import os
import sys
import time

import pytest

import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_cca_moe")
CELL = "toy-cca-moe.reasoning"
#: What a CPU trace, which has no device plane, leaves its readers nothing of.
DEVICE_ONLY = {
    "cca.device_ms_per_step", "cca.decode_roofline_share",
    "moe_top1.device_ms_per_step", "moe_top1.expert_roofline_share",
    "device.idle_ms_per_step.keys", "device.idle_ms_per_step.dispatch_rest",
    "device.idle_ms_per_step.readback",
}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def test_untraced_run_is_correct_and_splits_the_state_pool_off(spec, capsys):
    out = run(spec, 2**31 + 41, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 6 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    # 4 slots x 4 layers x (2 x 80 + 8) float32; K and V of 4 tokens on 2
    # heads of 8, float32, 129 pages, 4 layers.
    assert out["device"]["memory_state_pool_bytes"] == 4 * 4 * 168 * 4
    assert out["device"]["memory_kv_pool_reserved_bytes"] == (
        129 * 4 * 2 * 4 * 2 * 8 * 4)
    said = capsys.readouterr().out
    assert "layer kinds cca, router mlp_carry" in said
    assert "the probe's K and V pages lie" in said


def test_traced_run_reports_the_counters_and_finds_no_device_operations(spec):
    out = run(spec, 42, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]} - DEVICE_ONLY
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["kv.preemptions"]["value"] == 0
    assert out["metrics"]["state.resets_per_step"]["value"] > 0
    # One expert a token over 8 experts and 4 rows: the busiest expert of a
    # layer holds between its share and all of them.
    assert 1.0 <= out["metrics"]["moe.tokens_per_expert_imbalance"]["value"] <= 8.0


def control(spec, monkeypatch, capsys, seed, *planted):
    import control_cca_moe

    monkeypatch.setattr(sys, "argv", [
        "control_cca_moe.py", "--workload", CELL, "--seed", str(seed),
        "--seconds", "2", *planted])
    run_cell = bench.run_cell
    monkeypatch.setattr(
        bench, "run_cell", lambda *a, **kw: run_cell(
            *a, spec=spec, allow_cpu=True, t_start=time.perf_counter(), **kw))
    assert control_cca_moe.main() == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def unplanted():
    """``control_cca_moe.plant`` rebinds names of the program's modules."""
    from distributed_pytorch_tpu.models import mamba, moe

    kept = (mamba.load_rows, moe.route, moe.CarryRouter.__call__,
            moe.ROUTER_DTYPE)
    yield
    (mamba.load_rows, moe.route, moe.CarryRouter.__call__,
     moe.ROUTER_DTYPE) = kept


@pytest.mark.parametrize("planted", [
    ["--tails-zeroed"], ["--no-value-shift"], ["--carry-dropped"],
])
def test_a_program_made_wrong_by_the_control_is_not_correct(
        spec, monkeypatch, capsys, unplanted, planted):
    """``control_cca_moe.py``'s faults of the program on the cell's
    ``correct``: everything is float32 here, so a sound run reads rounding
    and a faulty one does not."""
    out = control(spec, monkeypatch, capsys, 43, *planted)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    assert out["planted"]


def test_the_int8_reference_in_the_programs_place_reads_far_outside(
        spec, monkeypatch, capsys):
    out = control(spec, monkeypatch, capsys, 44)
    assert out["correct"] is True
    readings = out["control"]
    check = json.load(open(os.path.join(
        TOY, "traffic", "toy_reasoning_closed.json")))["check"]
    for name in ("routing_gap", "kv_gap", "kv_gap_last"):
        assert readings[f"program_{name}"] <= check[f"{name}_limit"]
        assert readings[f"control_{name}"] > 10 * check[f"{name}_limit"]
    assert readings["control_mean_gap"] > 10 * check["mean_gap_limit"]
