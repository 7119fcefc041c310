#!/usr/bin/env python3
"""Record the engine tracer's ``step`` and ``prefill.chunk`` slices of a toy
engine's run, for ``test_prefill_readers.py``.

    JAX_PLATFORMS=cpu python3 benchmarks/tests/record_prefill_trace.py <out.json> <key>

Run from the root of a checkout: it records THAT checkout's engine (a
2-layer model of width 32, ``max_prefill_chunk`` 256, budget 300, two slots)
serving six prompts of 10 to 601 tokens, three decoded tokens each, and
writes the slices under ``key`` in ``out.json`` beside what the file holds.
``data/prefill_trace_recorded.json`` holds two keys: ``ladder`` from the tree
before PR 32 (a program a power-of-two chunk, no ``width``) and ``widths``
from PR 32's (a padded program a piece).
"""

import json
import os
import sys

sys.path.insert(0, os.getcwd())

PROMPTS = (10, 66, 130, 201, 257, 601)


def record():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.models.transformer import TransformerLM
    from distributed_pytorch_tpu.obs.tracer import Tracer
    from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    tracer = Tracer()
    engine = InferenceEngine(
        model, params, max_slots=2, max_seq_len=1024, page_size=16,
        max_prefill_chunk=256, token_budget=300, prefix_cache=False,
        tracer=tracer)
    for n in PROMPTS:
        prompt = np.random.default_rng(n).integers(1, 64, size=n).tolist()
        engine.submit(prompt, SamplingParams(max_new_tokens=3))
    engine.run()
    kept = []
    for e in tracer.events:
        if e.get("ph") == "X" and e["name"] in ("step", "prefill.chunk"):
            args = {k: v for k, v in e["args"].items()
                    if k in ("tokens", "start", "width", "decode_rows")}
            kept.append({"name": e["name"], "ph": "X", "args": args})
    engine.close()
    return kept


def main() -> None:
    out, key = sys.argv[1], sys.argv[2]
    held = {}
    if os.path.exists(out):
        with open(out) as f:
            held = json.load(f)
    held[key] = record()
    with open(out, "w") as f:
        json.dump(held, f, indent=0, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main()
