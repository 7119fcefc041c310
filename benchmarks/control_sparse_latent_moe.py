#!/usr/bin/env python3
"""``control.py`` for a cell of kind ``serve_sparse_latent_moe`` (``control.py``
tells kinds apart as ``serve`` and everything else; it may not be edited
here), with the controls more that such a configuration needs.

    python3 benchmarks/control_sparse_latent_moe.py --workload <name> \\
        --seed <n> --seconds <s> [--index-bits 8 | --top-k 1024 | --window 257]

Without a switch: runs the cell exactly as ``run.py`` does, then puts the plain
reference computed with int8 operands in every matmul in the program's place
and reads the same numbers from it (``control.serve_control``, unchanged), and
the reference's own routing, cache rows and selections with int8 operands
against the reference's in float32, as the probe's are read.

With a switch the PROGRAM is made wrong in one way and the cell run as it is;
the configuration states bf16 index keys, the 2,048 best positions and a
window of 513, so each such run has to come out not correct by at least one
of the cell's limits (neither the model nor the engine has a switch for any of
them; ``plant`` rebinds what ``models/mla.py`` reads):

* ``--index-bits 8``: every index key rounded to that many bits at its write
  (symmetric absmax a token; ``mla.index_row``);
* ``--top-k 1024``: the full layers select that many positions;
* ``--window 257``: the sliding layers see that many.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import control
import run as bench


def plant(index_bits: int = 0, top_k: int = 0, window: int = 0) -> None:
    """Make the program wrong (module docstring)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models import mla

    if index_bits:
        levels = 2 ** (index_bits - 1) - 1
        index_row = mla.index_row

        def rounded_row(k_idx, width):
            scale = jnp.max(jnp.abs(k_idx), axis=-1, keepdims=True) / levels
            scale = jnp.where(scale > 0, scale, 1.0)
            return index_row(
                (jnp.round(k_idx / scale) * scale).astype(k_idx.dtype), width)

        mla.index_row = rounded_row
    if top_k or window:
        layer = mla.LatentAttention

        def planted(*args, **kw):
            if top_k and kw.get("index_top_k"):
                kw["index_top_k"] = top_k
            if window and kw.get("window"):
                kw["window"] = window
            return layer(*args, **kw)

        mla.LatentAttention = planted


def reader(planted: dict, readings: dict):
    """The ``after_check`` hook that fills ``readings``: the program's
    numbers and, where nothing is planted, the int8 reference's in the
    program's place."""

    def after_check(cell, weights, sample, check):
        gaps = cell.driver.GAPS
        readings.update(
            program_logit_gap=check["logit_gap"],
            program_mean_gap=check["mean_gap"],
            **{f"program_{name}": check[name] for name in gaps})
        if any(planted.values()):
            return
        readings.update(control.serve_control(cell, weights, sample, check))
        # The reference's own probe numbers with int8 operands: its routing,
        # cache rows and selections stand in the program's place.
        import numpy as np

        probe = check["probe"]
        limits = cell.traffic["check"]
        rows = cell.driver.decode_rows(probe)
        low = cell.reference.probe_at(
            cell.config, weights, probe["tokens"], rows,
            pad_tokens_to=limits["pad_tokens_to"],
            pad_rows_to=limits["probe_output"], einsum=control.int8_einsum)
        chosen = np.asarray(low["selected"])
        top = cell.config["index_topk"]

        def positions(mask):
            found = np.flatnonzero(mask)
            return np.pad(found, (0, top - len(found)), constant_values=-1)

        stand_in = {
            "tokens": probe["tokens"], "prompt": probe["prompt"],
            "cached": probe["prompt"] - 1, "layers": probe["layers"],
            # every decoded token a program of its own
            "routing": list(np.asarray(low["routing"]).astype(
                np.int64).transpose(1, 0, 2)),
            "latents": [np.asarray(x) for x in low["latents"]],
            "index_keys": np.asarray(low["index_keys"][0]),
            "selected": {
                row: np.stack([positions(chosen[layer, i])
                               for layer in range(chosen.shape[0])])
                for i, row in enumerate(rows)}}
        found = cell.driver.probe_gaps(cell, weights, stand_in)
        readings.update(
            **{f"control_{name}": gap for name, gap in found.items()})

    return after_check


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--index-bits", type=int, default=0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--window", type=int, default=0)
    args = parser.parse_args()
    planted = dict(index_bits=args.index_bits, top_k=args.top_k,
                   window=args.window)
    readings = {}
    if any(planted.values()):
        plant(**planted)
    result = bench.run_cell(
        args.workload, args.seed, args.seconds, False,
        hooks={"after_check": reader(planted, readings)})
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "planted": {k: v for k, v in planted.items() if v} or "nothing",
        "correct": result["correct"], "failed": result["failed"],
        "attempted": result["attempted"], "metrics": result["metrics"],
        "device": result["device"], "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
