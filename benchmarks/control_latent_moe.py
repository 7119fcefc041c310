#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_latent_moe`` (``control.py`` tells
kinds apart as ``serve`` and everything else; it may not be edited here), with
the control more that such a configuration needs.

    python3 benchmarks/control_latent_moe.py --workload <name> --seed <n> \\
        --seconds <s> [--latent-bits 8]

Without ``--latent-bits``: runs the cell exactly as ``run.py`` does, then puts
the plain reference computed with int8 operands in every matmul in the
program's place and reads the same numbers from it (``control.serve_control``,
unchanged), and the reference's own routing and latents with int8 operands
against the reference's in float32, as the probe's are read.

With ``--latent-bits 8``: runs the same cell with the PROGRAM's latent pages
rounded to that many bits a number at every write (symmetric absmax a token,
over the token's ``[c | k_pe]``; ``models/mla.py``'s ``latent_row`` is
rebound here to round what it is about to cache: neither the model nor the
engine has a switch for it). The configuration states bf16 pages, so such
a run has to come out not correct by at least one of the cell's limits. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import control
import run as bench


def round_latents_to(bits: int) -> None:
    """Make every latent row the program caches hold ``bits``-bit numbers:
    ``models/mla.py``'s ``latent_row`` (what a token's row of the pool
    holds) is rebound to round ``[c | k_pe]`` first, one scale a token."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models import mla

    levels = 2 ** (bits - 1) - 1
    latent_row = mla.latent_row

    def rounded_row(c, k_pe, width):
        row = jnp.concatenate([c, k_pe], axis=-1)
        scale = jnp.max(jnp.abs(row), axis=-1, keepdims=True) / levels
        scale = jnp.where(scale > 0, scale, 1.0)
        row = (jnp.round(row / scale) * scale).astype(row.dtype)
        return latent_row(row[..., : c.shape[-1]], row[..., c.shape[-1]:], width)

    mla.latent_row = rounded_row


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--latent-bits", type=int, default=0)
    args = parser.parse_args()
    readings = {}

    def after_check(cell, weights, sample, check):
        readings.update(
            program_logit_gap=check["logit_gap"],
            program_mean_gap=check["mean_gap"],
            program_routing_gap=check["routing_gap"],
            program_latent_gap=check["latent_gap"],
            program_latent_gap_last=check["latent_gap_last"])
        if args.latent_bits:
            return
        readings.update(control.serve_control(cell, weights, sample, check))
        # The reference's own probe numbers with int8 operands: its routing
        # and latents stand in the program's place.
        import numpy as np

        probe = check["probe"]
        pad_to = cell.traffic["check"]["pad_tokens_to"]
        n = len(probe["tokens"])
        tokens = list(probe["tokens"]) + [0] * (pad_to - n)
        latents, routed = cell.reference.probe_at(
            cell.config, weights, tokens, (0, -1), einsum=control.int8_einsum)
        stand_in = {
            "tokens": probe["tokens"], "cached": 0,
            # every token a program of its own
            "routing": list(np.asarray(routed)[:, :n].astype(
                np.int64).transpose(1, 0, 2)),
            "latents": np.asarray(latents)[:, :n]}
        gaps = cell.driver.probe_gaps(cell, weights, stand_in)
        readings.update(
            **{f"control_{name}": gap for name, gap in gaps.items()})

    if args.latent_bits:
        round_latents_to(args.latent_bits)
    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False,
        hooks={"after_check": after_check})
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "latent_bits": args.latent_bits or "as configured",
        "correct": result["correct"], "failed": result["failed"],
        "attempted": result["attempted"], "metrics": result["metrics"],
        "device": result["device"], "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
