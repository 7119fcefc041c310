"""CPU rehearsal of the ``serve_hybrid_moe`` driver at toy sizes, through the
same ``run_cell`` the command line calls. The toy configuration, traffic mix
and cell live in ``tests/toy_hybrid_moe``, beside ``tests/toy_hybrid``: files
and entries, no edit. Each run compiles the toy's programs, so this file stays
outside tier-1 and is run by hand, as ``test_hybrid_rehearsal.py`` is."""

import json
import os
import time

import jax.numpy as jnp
import pytest

import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_hybrid_moe")
CELL = "toy-hybrid-moe.closed"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def test_untraced_run_is_correct_and_splits_the_memory(spec, capsys):
    out = run(spec, 2**31 + 31, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 4 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    device = out["device"]
    # 4 slots x 3 Mamba-2 layers x (8 x 16 x 16 float32 + 3 x 160 float32)
    assert device["memory_state_pool_bytes"] == 4 * 3 * (8 * 16 * 16 + 3 * 160) * 4
    # 33 pages x 16 tokens x 1 attention layer x K and V x 2 heads of 16
    assert device["memory_kv_pool_reserved_bytes"] == 33 * 16 * 2 * 2 * 16 * 4
    assert "the state pool" in capsys.readouterr().out


def test_traced_run_reports_the_counters_and_finds_no_device_operations(spec):
    out = run(spec, 32, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]}
    # A CPU trace has no device plane: the readers of the mixers' and the
    # experts' device time find nothing to read, and say so by None.
    want -= {"moe.device_ms_per_step", "ssd.device_ms_per_step"}
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["state.resets_per_step"]["value"] > 0
    # 4 held experts of 8, 3 choices a token: a token's most-loaded held
    # expert over the held experts' mean.
    assert 1.0 <= out["metrics"]["moe.tokens_per_expert_imbalance"]["value"] < 4.0


def said_number(text: str, before: str) -> float:
    """The number a ``[bench]`` line gives right before ``before``."""
    return float(text.split(before)[0].split()[-1])


@pytest.mark.parametrize("module, name, reading", [
    ("mamba", "STATE_DTYPE", " from the reference's (limit 0.0001); every"),
    ("moe", "ROUTER_DTYPE", " differ from the reference's own"),
])
def test_a_program_in_bfloat16_is_not_correct(
        spec, monkeypatch, capsys, module, name, reading):
    """``control_hybrid_moe.py``'s two faults of the program, each on the
    cell's ``correct``: the state kept in bfloat16 (``--state``), and the
    router's scores, top-k and gates in bfloat16 (``--router``). Everything
    else is float32 here, so the sound run reads rounding and the faulty run
    does not: the state fault on the state's limit, the router's on the
    routing's (the served tokens do not show it: no first place changes
    among 1,024 ids, and the first mixer lies before the first router)."""
    import importlib

    sound = run(spec, 33, False)
    assert sound["correct"] is True
    low = said_number(capsys.readouterr().out, reading)
    monkeypatch.setattr(
        importlib.import_module(f"distributed_pytorch_tpu.models.{module}"),
        name, jnp.bfloat16)
    out = run(spec, 33, False)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    assert said_number(capsys.readouterr().out, reading) > max(10 * low, 1e-3)
