"""The StableHLO of the serving programs of the language models the benchmark
serves, at toy widths: the three it has served since before latent attention
(a StarCoder2-shaped default block with grouped KV heads, the Jamba-shaped
hybrid, the Granite-shaped hybrid with routed experts) and the three newer
families (DeepSeek-shaped latent attention with routed experts, the
dots3-shaped sparse and windowed latent layers, the Olmo-shaped gated-delta
hybrid): the decode program and one prefill program of each, lowered through
the engine's own builders.

``tests/test_lowered_defaults.py`` pins their digests. A PR that adds a kind
of layer has to leave every default as it was, and "as it was" is these
bytes: run this file against the parent commit's package and against the
tree (``PYTHONPATH=<checkout> python tests/lowered_defaults.py``) and compare.
A digest that moves with the tree alone is a default that changed; one that
moves on both sides is the installation's (a new jax): record it again.

``"xla"`` leaves the decode kernels' operands (the rows' grouping, the runs of
neighbouring pages) out of the text, so run as a script the file also prints
the two latent toys' decode digests in ``"interpret"`` mode. Those are NOT
pinned (every kernel PR would have to record them again); a PR that moves who
works the operands out prints them at the parent and on its tree, equal.
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


def gated_delta_toy():
    """The toy gated-delta hybrid, as ``tests/test_gated_delta.py`` builds
    it: ``(model, params)``."""
    from hybrid_toy import ROOT, load_by_path

    with open(os.path.join(
            ROOT, "benchmarks", "tests", "toy_linear_hybrid", "configs",
            "toy-linear-hybrid.json")) as f:
        cfg = json.load(f)
    reference = load_by_path("benchmarks/reference/olmo_hybrid.py")
    driver = load_by_path("benchmarks/drivers/serve_linear_hybrid.py")
    return driver.build_program(cfg, reference.make_weights(cfg, 11))


def default_block():
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2)
    params = model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def toy(module: str):
    """``(model, params)`` of the toy program of ``tests/<module>.py``."""
    import importlib

    return importlib.import_module(module).toy_program()[1:]


#: name -> what builds its ``(model, params)``.
MODELS = {
    "default_block_gqa": default_block,
    "hybrid_s6": lambda: toy("hybrid_toy"),
    "hybrid_mamba2_routed": lambda: toy("granite_toy"),
    "latent_routed": lambda: toy("deepseek_toy"),
    "sparse_window_latent": lambda: toy("dots3_toy"),
    "hybrid_gated_delta": gated_delta_toy,
}


def engine_for(model, params, kernel):
    from distributed_pytorch_tpu.serving import InferenceEngine

    return InferenceEngine(model, params, paged_kernel=kernel, **ENGINE)


def lower_decode(engine):
    """The engine's decode program, lowered on its own staged operands."""
    stage = lambda a: jnp.asarray(np.array(a))  # noqa: E731
    return engine._decode_step.lower(
        engine.params, engine.cache, stage(engine._stage_tokens),
        engine._zero_prev, stage(engine._stage_use_prev),
        stage(engine._stage_tables), stage(engine._stage_lens),
        stage(engine._stage_temps), stage(engine._stage_keys),
        engine._zero_bias)


def programs(model, params, kernel) -> tuple:
    """The StableHLO text of the decode program and of the prefill program of
    width 8 of an engine over ``model`` with ``paged_kernel=kernel``."""
    engine = engine_for(model, params, kernel)
    zero = jnp.asarray([0], jnp.int32)
    slot = (jnp.asarray([0], jnp.int32),) if engine.state_layers else ()
    prefill = engine._prefill_step(8).lower(
        engine.params, engine.cache, jnp.zeros((1, 8), jnp.int32),
        jnp.zeros((1, engine.pages_per_seq), jnp.int32), zero,
        jnp.asarray([5], jnp.int32), *slot)
    return lower_decode(engine).as_text(), prefill.as_text()


def lowered() -> dict:
    """name -> StableHLO text of the engine's decode program and of its
    prefill program of width 8, kernel off and on (``"xla"``: the mode the
    CPU resolves ``"auto"`` to)."""
    out = {}
    for name, build in MODELS.items():
        model, params = build()
        for kernel in (False, "xla"):
            tag = f"{name}.{'kernel' if kernel else 'gather'}"
            out[f"{tag}.decode"], out[f"{tag}.prefill8"] = programs(
                model, params, kernel)
    return out


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def interpreted() -> dict:
    """name -> digest of the two latent toys' decode programs with the
    kernels in ``"interpret"`` mode (module docstring: printed, not pinned)."""
    return {
        f"{name}.interpret.decode": digest(
            programs(*MODELS[name](), "interpret")[0])
        for name in ("latent_routed", "sparse_window_latent")
    }


def digests() -> dict:
    return {name: digest(text) for name, text in lowered().items()}


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1, sort_keys=True))
    print(json.dumps(interpreted(), indent=1, sort_keys=True))
