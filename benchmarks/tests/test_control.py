"""The comparison that decides ``correct`` has to fail what it is there to
catch. Two kinds of test, at a size a test run can hold:

* the lower-precision control: the plain reference computed with int8
  operands, put in the program's place, must read well outside what the sound
  program reads (``control.py`` makes the same readings on the chip at the
  cells' own sizes; PERF.md records them);
* the broken timed path: a whole run, minus the look for a chip, with a token
  altered where it is produced, with a step that returns its state unchanged,
  or with a step that trains on half of its batch, must come out
  ``correct: false``.
"""

import json
import os
import time

import pytest

import control
import run as bench

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(TOY, "spec.json")) as f:
        return json.load(f)


def run(spec, cell, hooks, seed=31):
    return bench.run_cell(cell, seed, 1.0, False, spec=spec, allow_cpu=True,
                          t_start=time.perf_counter(), hooks=hooks)


def test_int8_reference_in_the_engines_place_reads_far_outside(spec):
    readings = {}

    def after(cell, *rest):
        readings.update(control.serve_control(cell, *rest))

    out = run(spec, "toy-lm.closed", {"after_check": after})
    assert out["correct"] is True
    with open(os.path.join(TOY, "traffic/toy_closed.json")) as f:
        limits = json.load(f)["check"]
    assert readings["program_mean_gap"] <= limits["mean_gap_limit"] / 3
    assert readings["control_mean_gap"] >= 3 * limits["mean_gap_limit"]


def test_int8_reference_in_the_trainers_place_reads_far_outside(spec):
    readings = {}

    def after(cell, *rest):
        readings.update(control.train_control(cell, *rest))

    out = run(spec, "toy-resnet.steps", {"after_check": after})
    assert out["correct"] is True
    with open(os.path.join(TOY, "traffic/toy_steps.json")) as f:
        limits = json.load(f)["check"]
    # The lower precision has to fail one of the cell's numbers, not each.
    assert (readings["control_grad_gap"] >= 3 * limits["grad_gap_limit"]
            or readings["control_loss_gap"] >= 3 * limits["loss_gap_limit"]
            or readings["control_change_gap"] >= 3 * limits["change_gap_limit"])


def test_a_token_altered_where_it_is_produced_is_not_correct(spec):
    serve = bench.load_module(bench.find(spec["paths"], "drivers/serve.py"))

    def build_broken_engine(cfg, model, params, tracer=None):
        engine = serve.build_engine(cfg, model, params, tracer)
        decode = engine._decode_step

        def altered(*args):
            tokens, cache = decode(*args)
            return (tokens + 1) % cfg["vocab_size"], cache

        engine.__dict__["_decode_step"] = altered  # shadows the cached one
        return engine

    out = run(spec, "toy-lm.closed", {"build_engine": build_broken_engine})
    assert out["correct"] is False
    assert out["attempted"] > 0  # the run itself went through


def unchanged(step):
    import jax

    def fault(state, batch):
        # The real step donates its state: give it a copy, keep the old.
        _new, loss = step(
            jax.tree_util.tree_map(lambda x: x.copy(), state), batch)
        return state, loss

    return fault


def half_batch(step):
    """On one chip nothing but the comparison with the reference sees it."""
    import jax.numpy as jnp

    def fault(state, batch):
        keep = batch[0].shape[0] // 2
        return step(state, tuple(
            jnp.concatenate([x[:keep], x[:keep]]) for x in batch))

    return fault


@pytest.mark.parametrize("cell, fault", [
    ("toy-resnet.steps", unchanged), ("toy-resnet.dp4", unchanged),
    ("toy-resnet.steps", half_batch)])
def test_a_step_that_keeps_its_state_or_drops_half_its_batch_is_not_correct(
        spec, cell, fault):
    out = run(spec, cell, {"wrap_step": fault})
    assert out["correct"] is False
    assert out["attempted"] > 0 and out["failed"] == 0
