"""The StableHLO of the serving programs of the three language models the
benchmark has served since before latent attention (a StarCoder2-shaped
default block with grouped KV heads, the Jamba-shaped hybrid, the
Granite-shaped hybrid with routed experts), at toy widths: the decode program
and one prefill program of each, lowered through the engine's own builders.

``tests/test_lowered_defaults.py`` pins their digests. A PR that adds a kind
of layer has to leave every default as it was, and "as it was" is these
bytes: run this file against the parent commit's package and against the
tree (``PYTHONPATH=<checkout> python tests/lowered_defaults.py``) and compare.
A digest that moves with the tree alone is a default that changed; one that
moves on both sides is the installation's (a new jax): record it again.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

ENGINE = dict(max_slots=2, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=10, prefix_cache=False)


def models():
    import granite_toy
    import hybrid_toy
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    default = TransformerLM(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2)
    params = default.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    return {
        "default_block_gqa": (default, params),
        "hybrid_s6": hybrid_toy.toy_program()[1:],
        "hybrid_mamba2_routed": granite_toy.toy_program()[1:],
    }


def lowered() -> dict:
    """name -> StableHLO text of the engine's decode program and of its
    prefill program of width 8, kernel off and on (``"xla"``: the mode the
    CPU resolves ``"auto"`` to)."""
    from distributed_pytorch_tpu.serving import InferenceEngine

    out = {}
    for name, (model, params) in models().items():
        for kernel in (False, "xla"):
            engine = InferenceEngine(
                model, params, paged_kernel=kernel, **ENGINE)
            stage = lambda a: jnp.asarray(np.array(a))  # noqa: E731
            decode = engine._decode_step.lower(
                engine.params, engine.cache, stage(engine._stage_tokens),
                engine._zero_prev, stage(engine._stage_use_prev),
                stage(engine._stage_tables), stage(engine._stage_lens),
                stage(engine._stage_temps), stage(engine._stage_keys),
                engine._zero_bias)
            zero = jnp.asarray([0], jnp.int32)
            slot = (jnp.asarray([0], jnp.int32),) if engine.state_layers else ()
            prefill = engine._prefill_step(8).lower(
                engine.params, engine.cache, jnp.zeros((1, 8), jnp.int32),
                jnp.zeros((1, engine.pages_per_seq), jnp.int32), zero,
                jnp.asarray([5], jnp.int32), *slot)
            tag = f"{name}.{'kernel' if kernel else 'gather'}"
            out[f"{tag}.decode"] = decode.as_text()
            out[f"{tag}.prefill8"] = prefill.as_text()
    return out


def digests() -> dict:
    return {name: hashlib.sha256(text.encode()).hexdigest()[:16]
            for name, text in lowered().items()}


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1, sort_keys=True))
