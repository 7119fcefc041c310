#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_linear_hybrid`` (``control.py``
tells kinds apart as ``serve`` and everything else and compares no state;
neither it nor the other kinds' controls may be edited here), with the two
controls more that such a configuration needs.

    python3 benchmarks/control_linear_hybrid.py --workload <name> --seed <n> \\
        --seconds <s> [--state bfloat16 | --beta-scale 1]

With neither: runs the cell exactly as ``run.py`` does, then puts the plain
reference computed with int8 operands in every matmul in the program's place
and reads the same numbers from it (``control.serve_control``, unchanged), and
the reference's own states with int8 operands against the reference's in
float32.

With ``--state bfloat16``: runs the same cell with the PROGRAM's recurrent
state kept in that type (``models/mamba.py``'s ``STATE_DTYPE``, rebound here,
which ``models/gated_delta.py`` reads; neither the model nor the engine has a
switch for it). With ``--beta-scale 1``: with the program's ``beta`` without
its factor 2 (``linear_allow_neg_eigval`` false in the program's model alone:
the one line that is this family's own). The configuration states a float32
state and the factor, so such a run has to come out not correct by at least
one of the cell's limits. Beside the program's numbers the reference's own
states under the same fault are read against the reference's as stated. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import control
import run as bench


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--state", default="")
    parser.add_argument("--beta-scale", type=float, default=0.0)
    args = parser.parse_args()
    readings = {}

    def after_check(cell, weights, sample, check):
        import numpy as np

        lower = {}
        if not (args.state or args.beta_scale):
            readings.update(control.serve_control(cell, weights, sample, check))
            lower["einsum"] = control.int8_einsum
        if args.state:
            lower["state_dtype"] = args.state
        if args.beta_scale:
            lower["beta_scale"] = args.beta_scale
        tokens = check["probe_tokens"]
        probe = {"tokens": tokens, "states": np.asarray(
            cell.reference.final_states(cell.config, weights, tokens, **lower))}
        readings.update(
            program_logit_gap=check["logit_gap"],
            program_mean_gap=check["mean_gap"],
            program_state_gap=check["state_gap"],
            reference_state_gaps=cell.driver.state_gaps(cell, weights, probe),
            reference_state_gap_memory=probe["state_gap_memory"])

    def after_probe(probe):
        readings.update(program_state_gap_memory=probe["state_gap_memory"])

    hooks = {"after_check": after_check, "after_probe": after_probe}
    if args.state:
        import jax.numpy as jnp

        from distributed_pytorch_tpu.models import mamba

        mamba.STATE_DTYPE = jnp.dtype(args.state)
    if args.beta_scale:
        if args.beta_scale != 1.0:
            raise SystemExit("the program's beta is scaled by 2 or by 1")

        def build_program(cfg, weights):
            driver = bench.load_module(
                bench.find(["benchmarks"], "drivers/serve_linear_hybrid.py"))
            return driver.build_program(cfg, weights, linear_neg_eigval=False)

        hooks["build_program"] = build_program
    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False, hooks=hooks)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "state": args.state or "float32",
        "beta_scale": args.beta_scale or 2.0,
        "correct": result["correct"], "failed": result["failed"],
        "attempted": result["attempted"], "metrics": result["metrics"],
        "device": result["device"], "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
