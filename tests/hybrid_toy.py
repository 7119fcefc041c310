"""What the hybrid-model tests share: the plain reference and the benchmark
driver's ``build_program``, loaded by path as ``benchmarks/run.py`` loads them
(there is no second copy of either), and one toy configuration."""

import importlib.util
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_by_path(relative: str):
    name = "hybrid_" + "".join(c if c.isalnum() else "_" for c in relative)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relative))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


reference = load_by_path("benchmarks/reference/jamba.py")
driver = load_by_path("benchmarks/drivers/serve_hybrid.py")

#: Layers: mamba, mamba, attention, mamba. float32 throughout, so that what
#: is compared is the arithmetic's order and nothing else.
TOY = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=1, vocab_size=128,
    attn_layer_period=4, attn_layer_offset=2, mamba_d_state=8,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8, rms_norm_eps=1e-6,
    tie_word_embeddings=True, torch_dtype="float32", initializer_range=0.1,
)
SEED = 2**31 + 7

# Logits here are of order 3. The program and the reference run the same
# float32 arithmetic in another order (the program's [N, d_inner] state
# against the reference's [d_inner, N], fused projections): differences are
# float32 rounding carried through 4 layers, measured at 3e-6. 5e-5 leaves
# an order of magnitude and is still 1/40 of what a bfloat16 state does.
LOGIT_TOL = 5e-5


def toy_program():
    weights = reference.make_weights(TOY, SEED)
    model, params = driver.build_program(TOY, weights)
    return weights, model, params


def tokens(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], size=n).tolist()


def served_gap(weights, prompt, generated):
    """How far below the reference's best logit each served token's
    reference logit lies (0 where the served token IS the reference's
    first), at the positions that predicted them."""
    rows = [len(prompt) - 1 + i for i in range(len(generated))]
    logits = np.asarray(reference.logits_at(
        TOY, weights, list(prompt) + list(generated), rows))
    return logits.max(-1) - logits[np.arange(len(generated)), generated]
