"""CPU rehearsal of the ``serve_latent_moe`` driver at toy sizes, through the
same ``run_cell`` the command line calls. The toy configuration, traffic mix
and cell live in ``tests/toy_latent_moe``, beside ``tests/toy_hybrid_moe``:
files and entries, no edit. Each run compiles the toy's programs, so this file
stays outside tier-1 and is run by hand, as ``test_hybrid_moe_rehearsal.py``
is."""

import json
import os
import time

import pytest

import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_latent_moe")
CELL = "toy-latent-moe.docqa"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def test_untraced_run_is_correct_and_hits_the_trie(spec, capsys):
    out = run(spec, 2**31 + 41, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 6 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    # 129 pages x 4 tokens x 3 layers x 128 lanes of float32
    assert out["device"]["memory_kv_pool_reserved_bytes"] == 129 * 4 * 3 * 128 * 4
    said = capsys.readouterr().out
    assert "512 B a token and layer as held" in said
    assert "positions served by the trie" in said


def test_traced_run_reports_the_counters_and_finds_no_device_operations(spec):
    out = run(spec, 42, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]}
    # A CPU trace has no device plane: the readers of the latent
    # attention's device time find nothing to read, and say so by None.
    want -= {"mla.device_ms_per_step", "mla.decode_roofline_share"}
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["kv.preemptions"]["value"] == 0
    # Documents of 41, 58 and 30 tokens under questions of 3-9: the whole
    # pages of every document are hits.
    assert out["metrics"]["kv.prefix_hit_share"]["value"] > 75.0
    assert out["metrics"]["kernels.paged_fetch_amplification"]["value"] >= 1.0


def said_number(text: str, before: str) -> float:
    """The number a ``[bench]`` line gives right before ``before``."""
    return float(text.split(before)[0].split()[-1])


def test_latent_pages_rounded_to_8_bits_are_not_correct(
        spec, monkeypatch, capsys):
    """``control_latent_moe.py``'s fault of the program on the cell's
    ``correct``: everything else is float32 here, so the sound run reads
    rounding and the faulty run does not."""
    import control_latent_moe
    from distributed_pytorch_tpu.models import mla

    reading = " (first layer) and"
    sound = run(spec, 43, False)
    assert sound["correct"] is True
    low = said_number(capsys.readouterr().out, reading)
    monkeypatch.setattr(mla, "latent_row", mla.latent_row)  # restored after
    control_latent_moe.round_latents_to(8)
    out = run(spec, 43, False)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    assert said_number(capsys.readouterr().out, reading) > max(10 * low, 1e-3)
