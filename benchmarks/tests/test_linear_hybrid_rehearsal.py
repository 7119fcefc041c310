"""CPU rehearsal of the ``serve_linear_hybrid`` driver at toy sizes, through
the same ``run_cell`` the command line calls. The toy configuration, traffic
mix and cell live in ``tests/toy_linear_hybrid``, beside ``tests/toy_hybrid``:
files and entries, no edit. Each run compiles the toy's programs, so this file
stays outside tier-1 and is run by hand, as ``test_hybrid_rehearsal.py`` is."""

import json
import os
import time

import jax.numpy as jnp
import pytest

import control
import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_linear_hybrid")
CELL = "toy-linear-hybrid.closed"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def said_number(text: str, before: str) -> float:
    """The number a ``[bench]`` line gives right before ``before``."""
    return float(text.split(before)[0].split()[-1])


STATE_LINE = " from the reference's (limit 0.0001); every"


def test_untraced_run_is_correct_and_splits_the_memory(spec, capsys):
    out = run(spec, 2**31 + 31, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 4 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    device = out["device"]
    # 4 slots x 3 gated-delta layers x (4 x 16 x 64 float32 as [2, 16, 128]
    # + a conv tail of 3 x 384 float32)
    assert device["memory_state_pool_bytes"] == 4 * 3 * (4 * 16 * 64 + 3 * 384) * 4
    # 33 pages x 16 tokens x 1 full layer x K and V x 6 heads of 16
    assert device["memory_kv_pool_reserved_bytes"] == 33 * 16 * 2 * 6 * 16 * 4
    said = capsys.readouterr().out
    assert "the state pool" in said
    assert "a head of the first gated-delta layer's state" in said


def test_traced_run_reports_the_counters_and_finds_no_device_operations(spec):
    out = run(spec, 32, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]}
    # A CPU trace has no device plane: the readers of the mixers' device
    # time find nothing to read, and say so by None.
    want -= {"gdn.device_ms_per_step", "gdn.state_roofline_share",
             "gdn.blocks_roofline_share"}
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["state.resets_per_step"]["value"] > 0
    assert out["metrics"]["kv.preemptions"]["value"] == 0


def test_a_state_in_bfloat16_is_not_correct(spec, monkeypatch, capsys):
    """``control_linear_hybrid.py --state bfloat16`` on the cell's
    ``correct``: everything else is float32 here, so the sound run reads
    rounding and the faulty run does not."""
    from distributed_pytorch_tpu.models import mamba

    sound = run(spec, 33, False)
    assert sound["correct"] is True
    low = said_number(capsys.readouterr().out, STATE_LINE)
    monkeypatch.setattr(mamba, "STATE_DTYPE", jnp.bfloat16)
    out = run(spec, 33, False)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    assert said_number(capsys.readouterr().out, STATE_LINE) > max(10 * low, 1e-3)


def test_beta_without_its_factor_two_is_not_correct(spec, capsys):
    """``control_linear_hybrid.py --beta-scale 1``: the program's model built
    with ``linear_neg_eigval`` false, the reference as the configuration
    states. Caught by the state's limit (the first layer's state reads its
    own ``beta`` directly) and by the served tokens'."""
    def build_program(cfg, weights):
        driver = bench.load_module(
            bench.find(["benchmarks"], "drivers/serve_linear_hybrid.py"))
        return driver.build_program(cfg, weights, linear_neg_eigval=False)

    out = run(spec, 34, False, hooks={"build_program": build_program})
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False
    assert said_number(capsys.readouterr().out, STATE_LINE) > 0.05


def test_an_int8_reference_reads_over_the_limits(spec):
    """``control_linear_hybrid.py``'s first control: the reference with int8
    operands in every matmul, in the program's place, reads over the served
    tokens' limits and over the state's."""
    readings = {}

    def after_check(cell, weights, sample, check):
        import numpy as np

        readings.update(control.serve_control(cell, weights, sample, check))
        probe = {"tokens": check["probe_tokens"], "states": np.asarray(
            cell.reference.final_states(
                cell.config, weights, check["probe_tokens"],
                einsum=control.int8_einsum))}
        readings["state_gaps"] = cell.driver.state_gaps(cell, weights, probe)
        readings["limits"] = cell.traffic["check"]

    out = run(spec, 35, False, hooks={"after_check": after_check})
    assert out["correct"] is True
    limits = readings["limits"]
    assert readings["program_logit_gap"] <= limits["logit_gap_limit"]
    assert readings["control_mean_gap"] > limits["mean_gap_limit"]
    assert readings["state_gaps"][0] > 10 * limits["state_gap_limit"]
