#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_cca_moe`` (``control.py`` tells
kinds apart as ``serve`` and everything else; it may not be edited here), with
the controls more that such a configuration needs.

    python3 benchmarks/control_cca_moe.py --workload <name> --seed <n> \\
        --seconds <s> [--tails-zeroed | --no-value-shift | --rope-full |
                       --carry-dropped | --no-choice-bias | --router bfloat16]

With no option: runs the cell exactly as ``run.py`` does, then puts the plain
reference computed with int8 operands in every matmul in the program's place
and reads the same numbers from it (``control.serve_control``, unchanged), and
the reference's own routing and K/V with int8 operands against the
reference's in float32, as the probe's are read.

With an option the same cell runs with the PROGRAM made wrong in one way, and
has to come out not correct by at least one of the cell's limits:
``--tails-zeroed`` the convolutions' tails read as zeros wherever a program
starts from the slot state (every piece border, every decode step);
``--no-value-shift`` both halves of ``v`` this token's; ``--rope-full`` the
rotation over the whole head; ``--carry-dropped`` every router without the
previous layer's ``r``; ``--no-choice-bias`` the expert chosen by its
probability alone; ``--router bfloat16`` the router network in bf16 (rebinds
``models/moe.py`` ``ROUTER_DTYPE``). The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import control
import run as bench

GAPS = ("logit_gap", "mean_gap", "routing_gap", "kv_gap", "kv_gap_last")


def plant(args) -> dict:
    """Make the program wrong as ``args`` say; returns the fields of
    ``TransformerLM`` to change."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models import mamba, moe

    cca = {}
    if args.tails_zeroed:
        load_rows = mamba.load_rows

        def zeroed(state, state_slots, seq_lens):
            rows = load_rows(state, state_slots, seq_lens)
            # The conv tails are the rank-3 leaf; the shifted value stays.
            return jnp.zeros_like(rows) if state.ndim == 3 else rows

        mamba.load_rows = zeroed
    if args.no_value_shift:
        cca["value_shift"] = False
    if args.rope_full:
        cca["rotary_dim"] = 0
    if args.carry_dropped:
        call = moe.CarryRouter.__call__
        moe.CarryRouter.__call__ = lambda self, n, carry=None: call(self, n)
    if args.no_choice_bias:
        route = moe.route
        moe.route = lambda scores, top_k, gating=moe.GATINGS[0], bias=None: (
            route(scores, top_k, gating, None))
    if args.router:
        moe.ROUTER_DTYPE = jnp.dtype(args.router)
    return cca


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    for flag in ("--tails-zeroed", "--no-value-shift", "--rope-full",
                 "--carry-dropped", "--no-choice-bias"):
        parser.add_argument(flag, action="store_true")
    parser.add_argument("--router", default="")
    args = parser.parse_args()
    planted = {k: v for k, v in vars(args).items()
               if k not in ("workload", "seed", "seconds") and v}
    readings = {}

    def after_check(cell, weights, sample, check):
        readings.update({f"program_{name}": check[name] for name in GAPS})
        if planted:
            return
        readings.update(control.serve_control(cell, weights, sample, check))
        # The reference's own probe numbers with int8 operands: its routing
        # and its k and v stand in the program's place.
        import numpy as np

        probe = check["probe"]
        n = len(probe["tokens"])
        tokens = list(probe["tokens"]) + [0] * (
            cell.traffic["check"]["pad_tokens_to"] - n)
        kv, routed = cell.reference.probe_at(
            cell.config, weights, tokens, probe["layers"],
            einsum=control.int8_einsum)
        stand_in = dict(
            probe, kv=np.asarray(kv, np.float32)[:, :, :n],
            # every token a program of its own
            routing=list(np.asarray(routed)[:, :n].astype(
                np.int64).transpose(1, 0, 2)))
        gaps = cell.driver.probe_gaps(cell, weights, stand_in)
        readings.update(
            **{f"control_{name}": gap for name, gap in gaps.items()})

    def build_program(cfg, weights):
        module = bench.load_module(os.path.join(
            bench.HERE, "drivers", f"{cfg['kind']}.py"))
        changed = plant(args)
        options = dict(module.model_options(cfg)["cca_options"], **changed)
        return module.build_program(
            cfg, weights, cca_options=tuple(sorted(options.items())))

    hooks = {"after_check": after_check}
    if planted:
        hooks["build_program"] = build_program
    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False, hooks=hooks)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "planted": planted or "int8 reference",
        "correct": result["correct"], "failed": result["failed"],
        "attempted": result["attempted"], "metrics": result["metrics"],
        "device": result["device"], "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
