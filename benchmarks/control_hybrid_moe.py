#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_hybrid_moe`` (``control.py`` tells
kinds apart as ``serve`` and everything else, ``control_hybrid.py`` compares
``[N, d_inner]`` states; neither may be edited here), with the two controls
more that such a configuration needs.

    python3 benchmarks/control_hybrid_moe.py --workload <name> --seed <n> \\
        --seconds <s> [--state bfloat16 | --router bfloat16]

With neither: runs the cell exactly as ``run.py`` does, then puts the plain
reference computed with int8 operands in every matmul in the program's place
and reads the same numbers from it (``control.serve_control``, unchanged), and the
reference's own states and routing with int8 operands against the reference's
in float32.

With ``--state bfloat16``: runs the same cell with the PROGRAM's recurrent
state kept in that type (``models/mamba.py``'s ``STATE_DTYPE``, rebound here,
which ``models/mamba2.py`` reads). With ``--router bfloat16``: with the
program's router scores, top-k and gates in that type (``models/moe.py``'s
``ROUTER_DTYPE``). Neither the model nor the engine has a switch for either.
The configuration states float32 for both, so such a run has to come out not
correct by at least one of the cell's limits. Beside the program's numbers
the reference's own states and routing in the lower type are read against
the reference's in float32. The benchmark's own runs never run this.
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
    parser.add_argument("--router", default="")
    args = parser.parse_args()
    readings = {}

    def after_check(cell, weights, sample, check):
        import numpy as np

        lower = {}
        if not (args.state or args.router):
            readings.update(control.serve_control(cell, weights, sample, check))
            lower["einsum"] = control.int8_einsum
        if args.state:
            lower["state_dtype"] = args.state
        if args.router:
            lower["router_dtype"] = args.router
        tokens = check["probe_tokens"]
        routed = np.asarray(cell.reference.routing_at(
            cell.config, weights, tokens, **lower))  # [layers, T, E]
        probe = {"tokens": tokens, "states": np.asarray(
            cell.reference.final_states(cell.config, weights, tokens, **lower)),
            # every token a program of its own
            "routing": list(routed.astype(np.int64).transpose(1, 0, 2))}
        readings.update(
            program_logit_gap=check["logit_gap"],
            program_mean_gap=check["mean_gap"],
            program_state_gap=check["state_gap"],
            reference_state_gaps=cell.driver.state_gaps(cell, weights, probe),
            reference_state_gap_memory=probe["state_gap_memory"],
            reference_routing_gap=probe["routing_gap"])

    def after_probe(probe):
        readings.update(program_state_gap_memory=probe["state_gap_memory"],
                        program_routing_gap=probe["routing_gap"])

    import jax.numpy as jnp

    if args.state:
        from distributed_pytorch_tpu.models import mamba

        mamba.STATE_DTYPE = jnp.dtype(args.state)
    if args.router:
        from distributed_pytorch_tpu.models import moe

        moe.ROUTER_DTYPE = jnp.dtype(args.router)
    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False,
        hooks={"after_check": after_check, "after_probe": after_probe})
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "state": args.state or "float32", "router": args.router or "float32",
        "correct": result["correct"], "failed": result["failed"],
        "attempted": result["attempted"], "metrics": result["metrics"],
        "device": result["device"], "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
