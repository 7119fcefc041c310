#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_hybrid`` (``control.py`` tells
kinds apart as ``serve`` and everything else, and may not be edited here),
with the one control more that such a configuration needs.

    python3 benchmarks/control_hybrid.py --workload <name> --seed <n> \
        --seconds <s> [--state bfloat16]

Without ``--state``: runs the cell exactly as ``run.py`` does, then puts the
plain reference computed with int8 operands in the program's place and reads
the same numbers from it (``control.serve_control``, unchanged). A limit has to
lie above what sound runs read and below what this control reads.

With ``--state bfloat16``: runs the same cell with the PROGRAM's scan state
kept in that type (``models/mamba.py``'s ``STATE_DTYPE``, rebound here; neither
the model nor the engine has a switch for it). The configuration states a
float32 state, so this run has to come out not correct by at least one limit:
the driver's ``state_gap_limit``. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import control
import run as bench


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--state", default="")
    args = parser.parse_args()
    readings = {}

    def after_check(cell, weights, sample, check):
        if args.state:
            # ... and the reference's own states, carried in that type,
            # against the reference's in float32.
            probe = {"tokens": check["probe_tokens"], "states": np.asarray(
                cell.reference.final_states(
                    cell.config, weights, check["probe_tokens"],
                    state_dtype=args.state)).transpose(0, 2, 1)}
            readings.update(
                program_logit_gap=check["logit_gap"],
                program_mean_gap=check["mean_gap"],
                program_state_gap=check["state_gap"],
                reference_state_gaps=cell.driver.state_gaps(cell, weights, probe))
        else:
            readings.update(control.serve_control(cell, weights, sample, check))

    if args.state:
        import jax.numpy as jnp

        from distributed_pytorch_tpu.models import mamba

        mamba.STATE_DTYPE = jnp.dtype(args.state)
    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False,
        hooks={"after_check": after_check})
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "state": args.state or "float32", "correct": result["correct"],
        "failed": result["failed"], "attempted": result["attempted"],
        "metrics": result["metrics"], "device": result["device"],
        "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
