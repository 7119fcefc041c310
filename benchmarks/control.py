#!/usr/bin/env python3
"""Read a cell's `correct` numbers and its lower-precision control's, on one
seed, at the cell's own size.

    python3 benchmarks/control.py --workload <name> --seed <n> --seconds <s>

Runs the cell exactly as ``run.py`` does (same driver, same window, only
shorter if ``--seconds`` says so), then puts the plain reference, computed with
int8 operands (the nearest precision below the bfloat16 the configurations
state), in the program's place and reads the same numbers from it. A limit has
to lie above what sound runs read and below what this control reads: PERF.md
records both. The benchmark's own runs never run this.

The int8 arithmetic lives here, not in the references: they take the
``einsum`` or ``conv`` every layer runs, and this file hands them these.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

import run as bench


def int8_round(a, axis=None):
    """Symmetric absmax int8, dequantised: one scale over ``axis`` (the
    whole tensor when None)."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(a / scale) * scale


def int8_einsum(spec: str, a, b):
    """What a matmul sees when both operands are int8 with one scale per
    row or column: each is rounded along the axes the product contracts."""
    (la, lb), out = spec.split("->")[0].split(","), spec.split("->")[1]
    contracted = [c for c in la if c in lb and c not in out]
    return jnp.einsum(
        spec, int8_round(a, tuple(la.index(c) for c in contracted)),
        int8_round(b, tuple(lb.index(c) for c in contracted)))


def _conv(x, kernel, stride, padding):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def int8_conv(x, kernel, stride, padding):
    """What an int8 convolution computes. Forward with int8 images (one
    scale) and int8 filters (one scale a filter); backward, both of its
    convolutions with an int8 gradient (one scale) as well."""
    return _conv(int8_round(x), int8_round(kernel, (0, 1, 2)), stride, padding)


def _int8_conv_fwd(x, kernel, stride, padding):
    return int8_conv(x, kernel, stride, padding), (x, kernel)


def _int8_conv_bwd(stride, padding, saved, g):
    x, kernel = saved
    _, vjp = jax.vjp(
        lambda a, b: _conv(a, b, stride, padding),
        int8_round(x), int8_round(kernel, (0, 1, 2)))
    return vjp(int8_round(g))


int8_conv.defvjp(_int8_conv_fwd, _int8_conv_bwd)


def serve_control(cell, weights, sample, check) -> dict:
    """At each served position of the same prompts and tokens: how far the
    token that int8 puts first lies below the reference's best."""
    reference_logits = cell.driver.reference_logits
    worst, total, compared = 0.0, 0.0, 0
    for r in sample:
        n = len(r.generated)
        exact = np.asarray(reference_logits(cell, weights, r, sample))
        low = np.asarray(
            reference_logits(cell, weights, r, sample, einsum=int8_einsum))
        gaps = exact.max(axis=-1) - exact[np.arange(n), low.argmax(axis=-1)]
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        compared += n
    return {"program_logit_gap": check["logit_gap"],
            "program_mean_gap": check["mean_gap"],
            "control_logit_gap": worst, "control_mean_gap": total / compared}


def train_control(cell, start, batches, want) -> dict:
    from harness.stats import worst_leaf_gap

    opt = cell.config["assumed"]["optimizer"]
    low = cell.reference.train_steps(
        cell.config, start, batches, learning_rate=opt["learning_rate"],
        momentum=opt["momentum"], devices=cell.devices,
        loss=functools.partial(cell.reference.loss_fn, conv=int8_conv))
    return {
        "control_loss_gap": max(
            abs(a - b) / abs(b) for a, b in zip(low["losses"], want["losses"])),
        "control_grad_gap": worst_leaf_gap(
            low["first_grad_norms"], want["first_grad_norms"])[0],
        "control_change_gap": worst_leaf_gap(
            low["param_change_norms"], want["param_change_norms"])[0],
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args()
    readings = {}

    def after_check(cell, *rest):
        fn = serve_control if cell.config["kind"] == "serve" else train_control
        readings.update(fn(cell, *rest))

    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False,
        hooks={"after_check": after_check})
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": result["correct"], "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
