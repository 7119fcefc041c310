#!/usr/bin/env python3
"""Draw a replay deck once and print it, to be pasted into a traffic file.

    python3 benchmarks/make_deck.py --entries 64 --prompt 200 0.55 64 512 \
        --output 192 0.45 64 384

Prompt and output lengths are each a log-normal (median, sigma) read at evenly
spaced quantiles and clipped to [low, high]; the two lists are then paired
and ordered by fixed permutations (numpy ``default_rng(0)``), so the deck is
the same whoever draws it. The traffic file holds the result: a run never
draws lengths.
"""

from __future__ import annotations

import argparse
import json
from statistics import NormalDist

import numpy as np


def quantile_lengths(n, median, sigma, low, high):
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        out.append(int(min(high, max(low, round(median * np.exp(sigma * z))))))
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--entries", type=int, required=True)
    parser.add_argument("--prompt", type=float, nargs=4, required=True,
                        metavar=("MEDIAN", "SIGMA", "LOW", "HIGH"))
    parser.add_argument("--output", type=float, nargs=4, required=True,
                        metavar=("MEDIAN", "SIGMA", "LOW", "HIGH"))
    args = parser.parse_args()
    rng = np.random.default_rng(0)
    prompts = quantile_lengths(args.entries, *args.prompt)
    outputs = quantile_lengths(args.entries, *args.output)
    outputs = [outputs[i] for i in rng.permutation(args.entries)]
    order = rng.permutation(args.entries)
    deck = [[prompts[i], outputs[i]] for i in order]
    print(json.dumps(deck))
    print(f"prompt tokens {sum(prompts)}, median {int(np.median(prompts))}; "
          f"output tokens {sum(outputs)}, median {int(np.median(outputs))}")


if __name__ == "__main__":
    main()
