"""CPU rehearsal of the ``serve_window_moe`` driver at toy sizes, through the
same ``run_cell`` the command line calls. The toy configuration, traffic mix
and cell live in ``tests/toy_window_moe``, beside ``tests/toy_latent_moe``:
files and entries, no edit. Each run compiles the toy's programs, so this file
stays outside tier-1 and is run by hand, as ``test_latent_moe_rehearsal.py``
is."""

import json
import os
import sys
import time

import pytest

import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_window_moe")
CELL = "toy-window-moe.mixed"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def test_untraced_run_is_correct_and_frees_the_pages_behind_its_windows(
        spec, capsys):
    out = run(spec, 2**31 + 41, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 6 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    page = 2 * 4 * 2 * 6 * 4  # K and V, 4 tokens, 2 heads of 6, float32
    assert out["device"]["memory_kv_pool_full_bytes"] == 129 * 2 * page
    assert out["device"]["memory_kv_pool_window_bytes"] == 33 * 6 * page
    said = capsys.readouterr().out
    assert "through its WINDOW-group table" in said
    # a window of 8 on pages of 4 under pieces of 32: 11 pages and 3
    assert "(bounds 11 and 3)" in said and "within the bounds" in said
    assert "3 while it decoded" in said


def test_traced_run_reports_the_counters_and_finds_no_device_operations(spec):
    out = run(spec, 42, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]}
    # A CPU trace has no device plane: the readers of the window layers'
    # device time find nothing to read, and say so by None.
    want -= {"swa_kv.device_ms_per_step", "swa_kv.decode_roofline_share"}
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["kv.preemptions"]["value"] == 0
    # Three pages a decoding row where the full group holds its whole
    # context (prompts of 22-90): well under one table a sequence's 1.0.
    assert 0.02 < out["metrics"]["kv.window_held_share"]["value"] < 0.5
    assert out["metrics"]["kv.window_pages_freed_per_step"]["value"] > 0.5


@pytest.mark.parametrize("planted", [
    ["--window", "7"], ["--free-ahead", "1"], ["--gate-scale", "1"],
])
def test_a_program_made_wrong_by_the_control_is_not_correct(
        spec, monkeypatch, capsys, planted):
    """``control_window_moe.py``'s faults of the program on the cell's
    ``correct``: everything is float32 here, so a sound run reads rounding
    and a faulty one does not."""
    import control_window_moe

    monkeypatch.setattr(sys, "argv", [
        "control_window_moe.py", "--workload", CELL, "--seed", "43",
        "--seconds", "2", *planted])
    run_cell = bench.run_cell
    monkeypatch.setattr(
        bench, "run_cell", lambda *a, **kw: run_cell(
            *a, spec=spec, allow_cpu=True, t_start=time.perf_counter(), **kw))
    assert control_window_moe.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    assert out["planted"]


def test_the_int8_reference_in_the_programs_place_reads_far_outside(
        spec, monkeypatch, capsys):
    import control_window_moe

    monkeypatch.setattr(sys, "argv", [
        "control_window_moe.py", "--workload", CELL, "--seed", "44",
        "--seconds", "2"])
    run_cell = bench.run_cell
    monkeypatch.setattr(
        bench, "run_cell", lambda *a, **kw: run_cell(
            *a, spec=spec, allow_cpu=True, t_start=time.perf_counter(), **kw))
    assert control_window_moe.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    control = out["control"]
    check = json.load(open(os.path.join(
        TOY, "traffic", "toy_mixed_closed.json")))["check"]
    for name in ("routing_gap", "kv_gap_window", "kv_gap_full"):
        assert control[f"program_{name}"] <= check[f"{name}_limit"]
        assert control[f"control_{name}"] > 10 * check[f"{name}_limit"]
    assert control["control_mean_gap"] > 10 * check["mean_gap_limit"]
