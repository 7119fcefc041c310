"""What the Mamba-2 / routed-experts tests share: the plain reference and the
benchmark driver's ``build_program``, loaded by path as ``benchmarks/run.py``
loads them (there is no second copy of either), and one toy configuration."""

import numpy as np

from hybrid_toy import ROOT, load_by_path  # noqa: F401

reference = load_by_path("benchmarks/reference/granite.py")
driver = load_by_path("benchmarks/drivers/serve_hybrid_moe.py")

#: Layers: mamba2, attention, mamba2; every feed-forward 8 routed
#: experts (top 3) plus a shared one. float32 throughout, so that what is
#: compared is the arithmetic's order and nothing else. The multipliers are
#: the published kind (none of them 1), the widths toys.
TOY = dict(
    hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
    num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba", "mamba", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, vocab_size=128,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_chunk_size=8, num_local_experts=8,
    num_experts_per_tok=3, rms_norm_eps=1e-5, attention_multiplier=0.1,
    embedding_multiplier=6.0, residual_multiplier=0.5, logits_scaling=4.0,
    tie_word_embeddings=True, torch_dtype="float32", initializer_range=0.1,
)
SEED = 2**31 + 11

# Logits here are of order 0.1 (the reference draws the tied embedding at
# initializer_range / embedding_multiplier, so they are flat and a greedy
# token is decided by the layers, not by its input token's own row). The
# program and the reference run the same float32 arithmetic in another order
# (blocks of 64 against blocks of 8, a grouped product against every expert
# on every token, fused projections): float32 rounding carried through 3
# layers, measured at 6e-8 to 9e-8. 1e-6 leaves an order of magnitude. What a
# bfloat16 state does to the logits here is no more than that (1.1e-6: the
# gated RMSNorm hides it), which is why its test reads the state itself.
LOGIT_TOL = 1e-6


def share(held):
    """The toy with only experts ``held = (lo, hi)`` on this chip."""
    lo, hi = held
    return dict(TOY, num_local_experts=hi - lo, experts_held=[lo, hi],
                num_local_experts_published=TOY["num_local_experts"])


def slice_experts(weights, held):
    """``weights`` (all experts held) cut to the share ``held``."""
    lo, hi = held
    layers = [dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
              for w in weights["layers"]]
    return dict(weights, layers=layers)


def toy_program(cfg=None, weights=None):
    cfg = cfg or TOY
    weights = weights or reference.make_weights(cfg, SEED)
    model, params = driver.build_program(cfg, weights)
    return weights, model, params


def tokens(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], size=n).tolist()
