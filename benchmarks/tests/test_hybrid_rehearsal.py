"""CPU rehearsal of the ``serve_hybrid`` driver at toy sizes, through the
same ``run_cell`` the command line calls. The toy configuration, traffic mix
and cell live in ``tests/toy_hybrid``, beside ``tests/toy``, and were added
the way ``benchmarks/README.md`` says: files and entries, no edit."""

import json
import os
import time

import jax
import pytest

import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_hybrid")
CELL = "toy-hybrid.closed"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, seed, trace, seconds=2.0, **kw):
    return bench.run_cell(CELL, seed, seconds, trace, spec=spec,
                          allow_cpu=True, t_start=time.perf_counter(), **kw)


def test_untraced_run_is_correct_and_splits_the_memory(spec, capsys):
    out = run(spec, 2**31 + 21, False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 4 == 0
    assert set(out["metrics"]) == {"tpot_ms_p50", "setup_s"}
    device = out["device"]
    # 4 slots x 3 Mamba layers x (8 x 128 float32 + 3 x 128 float32)
    assert device["memory_state_pool_bytes"] == 4 * 3 * (8 * 128 + 3 * 128) * 4
    # 33 pages x 16 tokens x 1 attention layer x K and V x 16 float32
    assert device["memory_kv_pool_reserved_bytes"] == 33 * 16 * 2 * 16 * 4
    assert 0 < device["memory_kv_pool_used_peak_bytes"] <= (
        device["memory_kv_pool_reserved_bytes"])
    assert "the state pool" in capsys.readouterr().out


def test_traced_run_reports_the_per_layer_metrics(spec):
    out = run(spec, 22, True)
    assert out["correct"] is True
    want = {m["name"] for m in spec["per_layer"]}
    # The toy cell lists no share of a roofline (the CPU has no row in the
    # table of peaks). A CPU trace names its operations otherwise than a
    # TPU's: the reader of the mixers' device time finds nothing to read,
    # and says so by None.
    want.discard("ssm.device_ms_per_step")
    assert set(out["metrics"]) == want
    assert out["metrics"]["programs.compiles_in_window.serve"]["value"] == 0
    assert out["metrics"]["state.resets_per_step"]["value"] > 0


def test_a_state_zeroed_mid_request_is_not_correct(spec):
    """The planted fault: every slot's recurrent state is wiped once, in
    the middle of the window, and nothing else changes. Tokens keep coming
    and every request finishes; only the comparison with the reference can
    tell."""
    from distributed_pytorch_tpu.models.mamba import STATE_KEYS

    def build_engine(cfg, model, params, tracer=None):
        from distributed_pytorch_tpu.serving import InferenceEngine

        engine = InferenceEngine(
            model, params, tracer=tracer, **cfg["assumed"]["engine"])
        step, seen = engine.step, [0]

        def faulty_step():
            seen[0] += 1
            if seen[0] % 40 == 0 and engine.scheduler.running:
                engine.cache = jax.tree_util.tree_map_with_path(
                    lambda path, leaf: leaf * 0
                    if path[-1].key in STATE_KEYS else leaf, engine.cache)
            return step()

        engine.step = faulty_step
        return engine

    out = run(spec, 23, False, hooks={"build_engine": build_engine})
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] is False


def test_a_bfloat16_scan_state_is_not_correct(spec, monkeypatch, capsys):
    """``control_hybrid.py --state bfloat16``'s fault: the same cell with the
    program's scan state kept in bfloat16. The served greedy tokens do not
    show it (float32 everywhere else, logits of order 3 over 1,024 ids: no
    first place changes); the driver's own comparison of the scan states
    after its probe does."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models import mamba

    sound = run(spec, 24, False)
    assert sound["correct"] is True
    said = capsys.readouterr().out
    assert "scan state after the probe's 96 tokens" in said
    monkeypatch.setattr(mamba, "STATE_DTYPE", jnp.bfloat16)
    out = run(spec, 24, False)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["memory_state_pool_bytes"] == (
        4 * 3 * (8 * 128 * 2 + 3 * 128 * 4))
    assert out["correct"] is False
    assert "widest gap 0.000000" in capsys.readouterr().out
