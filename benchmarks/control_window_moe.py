#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_window_moe`` (``control.py`` tells
kinds apart as ``serve`` and everything else; it may not be edited here), with
the controls more that such a configuration needs.

    python3 benchmarks/control_window_moe.py --workload <name> --seed <n> \\
        --seconds <s> [--window 127 | --free-ahead 1 | --rope-full |
                       --gate-scale 1 | --router bfloat16]

With no option: runs the cell exactly as ``run.py`` does, then puts the plain
reference computed with int8 operands in every matmul in the program's place
and reads the same numbers from it (``control.serve_control``, unchanged), and
the reference's own routing and K/V with int8 operands against the
reference's in float32, as the probe's are read.

With an option the same cell runs with the PROGRAM made wrong in one way, and
has to come out not correct by at least one of the cell's limits:
``--window N`` the sliding layers' window (127: off by one; 112: a page
short); ``--free-ahead 1`` the window group gives a page back one step early
(``WindowGroup.free_ahead``: the table then lacks a page the kernel still
needs); ``--rope-full`` the rotation left on in the full layers;
``--gate-scale 1`` the routed gates without their 2.5; ``--router bfloat16``
the router's scores in bf16 (rebinds ``models/moe.py`` ``ROUTER_DTYPE``). The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import control
import run as bench

GAPS = ("logit_gap", "mean_gap", "routing_gap", "kv_gap_window", "kv_gap_full")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--window", type=int, default=0)
    parser.add_argument("--free-ahead", type=int, default=0)
    parser.add_argument("--rope-full", action="store_true")
    parser.add_argument("--gate-scale", type=float, default=0.0)
    parser.add_argument("--router", default="")
    args = parser.parse_args()
    planted = {k: v for k, v in vars(args).items()
               if k not in ("workload", "seed", "seconds") and v}
    readings = {}

    def after_check(cell, weights, sample, check):
        readings.update(
            {f"program_{name}": check[name] for name in GAPS},
            program_within_bounds=check["within"])
        if planted:
            return
        readings.update(control.serve_control(cell, weights, sample, check))
        # The reference's own probe numbers with int8 operands: its routing
        # and its k and v stand in the program's place.
        import numpy as np

        probe = check["probe"]
        pad_to = cell.traffic["check"]["pad_tokens_to"]
        n = len(probe["tokens"])
        tokens = list(probe["tokens"]) + [0] * (pad_to - n)
        kv, routed = cell.reference.probe_at(
            cell.config, weights, tokens, probe["layers"],
            einsum=control.int8_einsum)
        kv = np.asarray(kv)[:, :, :n]
        stand_in = dict(
            probe, cached=0,
            # every token a program of its own
            routing=list(np.asarray(routed)[:, :n].astype(
                np.int64).transpose(1, 0, 2)),
            window_kv=kv[0][:, probe["window_start"]:], full_kv=kv[1])
        gaps = cell.driver.probe_gaps(cell, weights, stand_in)
        readings.update(
            **{f"control_{name}": gap for name, gap in gaps.items()})

    def driver_of(cfg):
        return bench.load_module(os.path.join(
            bench.HERE, "drivers", f"{cfg['kind']}.py"))

    def build_program(cfg, weights):
        import jax.numpy as jnp

        from distributed_pytorch_tpu.models import moe

        changed = {}
        module = driver_of(cfg)
        if args.window:
            options = module.model_options(cfg)
            (name, sizes), = options["attention_variants"]
            changed["attention_variants"] = ((name, tuple(
                (k, args.window if k == "window" else v) for k, v in sizes)),)
        if args.rope_full:
            changed["rope"] = True
        if args.gate_scale:
            changed["routed_scale"] = args.gate_scale
        if args.router:
            moe.ROUTER_DTYPE = jnp.dtype(args.router)
        return module.build_program(cfg, weights, **changed)

    def build_engine(cfg, model, params, tracer=None):
        engine = driver_of(cfg).build_engine(cfg, model, params, tracer)
        engine.scheduler.window_group.free_ahead = args.free_ahead
        return engine

    hooks = {"after_check": after_check}
    if planted:
        hooks.update(build_program=build_program, build_engine=build_engine)
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
