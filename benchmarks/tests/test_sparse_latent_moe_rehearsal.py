"""CPU rehearsal of the ``serve_sparse_latent_moe`` driver at toy sizes,
through the same ``run_cell`` the command line calls. The toy configuration,
traffic mix and cell live in ``tests/toy_sparse_latent_moe``, beside
``tests/toy_latent_moe``: files and entries, no edit. Each run compiles the
toy's programs, so this file stays outside tier-1 and is run by hand, as
``test_latent_moe_rehearsal.py`` is."""

import json
import os
import time

import pytest

import run as bench

TOY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "toy_sparse_latent_moe")
CELL = "toy-sparse-latent-moe.docqa"
DEVICE_ONLY = {
    "dsa.device_ms_per_step", "dsa.index_roofline_share",
    "dsa.sparse_decode_roofline_share", "swa.device_ms_per_step",
    "swa.decode_roofline_share"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def test_untraced_run_is_correct_under_every_limit(spec, capsys):
    out = run(spec, 2**31 + 41, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 6 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    # 129 pages x 4 tokens x (2 full layers x (128 + 128) + 2 sliding x 128)
    # lanes of float32
    assert out["device"]["memory_kv_pool_reserved_bytes"] == (
        129 * 4 * (2 * 256 + 2 * 128) * 4)
    said = capsys.readouterr().out
    assert "positions served by the trie" in said
    assert "are not in the reference's own top 7" in said


def test_traced_run_reports_the_counters_and_finds_no_device_operations(spec):
    out = run(spec, 42, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]} - DEVICE_ONLY
    # A CPU trace has no device plane: the readers of device time find
    # nothing to read, and say so by None.
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["kv.prefix_hit_share"]["value"] > 75.0
    # Contexts of 30-70 tokens of which a full layer reads 7.
    assert 5.0 < out["metrics"]["dsa.selected_share"]["value"] < 40.0
    assert out["metrics"]["kernels.paged_fetch_amplification"]["value"] < 1.0


def said_number(text: str, before: str) -> float:
    """The number a ``[bench]`` line gives right before ``before``."""
    return float(text.split(before)[0].split()[-1])


@pytest.mark.parametrize("switch, reading", [
    (dict(index_bits=8), " (first full layer, index keys)"),
    (dict(top_k=3), " are not in the reference's own top"),
    (dict(window=5), " (first sliding layer)"),
])
def test_a_control_of_the_program_is_not_correct(
        spec, monkeypatch, capsys, switch, reading):
    """``control_sparse_latent_moe.py``'s faults of the program on the cell's
    ``correct``: everything is float32 here, so the sound run reads rounding
    and a faulty run does not."""
    import control_sparse_latent_moe as control
    from distributed_pytorch_tpu.models import mla

    monkeypatch.setattr(mla, "LatentAttention", mla.LatentAttention)
    monkeypatch.setattr(mla, "index_row", mla.index_row)
    control.plant(**switch)
    out = run(spec, 43, False)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    if "window" not in switch:  # a window of 5 shows in the served tokens
        assert said_number(capsys.readouterr().out, reading) > 1e-3


def test_the_int8_reference_in_the_programs_place_reads_worse(spec):
    """``control_sparse_latent_moe.py`` with no switch: every probe reading
    of the int8 reference against the float32 one, beside the program's."""
    import control_sparse_latent_moe as control

    readings = {}
    out = run(spec, 44, False, hooks={
        "after_check": control.reader({}, readings)})
    assert out["correct"] is True
    for name in ("latent_gap", "latent_gap_sliding", "latent_gap_last",
                 "index_gap", "routing_gap"):
        assert readings[f"control_{name}"] > 10 * readings[f"program_{name}"]
        assert readings[f"control_{name}"] > 1e-3
    assert readings["control_logit_gap"] >= readings["program_logit_gap"]
    assert 0 <= readings["control_selection_gap_first"] <= 1
