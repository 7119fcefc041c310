"""What the latent-attention tests share: the plain reference and the
benchmark driver's ``build_program``, loaded by path as ``benchmarks/run.py``
loads them (there is no second copy of either), and one toy configuration."""

import numpy as np

from hybrid_toy import ROOT, load_by_path  # noqa: F401

reference = load_by_path("benchmarks/reference/deepseek_v2.py")
driver = load_by_path("benchmarks/drivers/serve_latent_moe.py")

#: Three layers of latent attention (4 heads; keys of 8 + 4, values of 6, a
#: latent of 12: every width differs from every other, so that a slice taken
#: at the wrong place shows); a dense feed-forward, then two layers of 8
#: routed experts (top 3, gates NOT renormalised) beside two shared ones.
#: YaRN at a trained length of 16, so that the toy's positions pass it.
#: float32 throughout, so that what is compared is the arithmetic's order and
#: nothing else.
TOY = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=3, first_k_dense_replace=1, moe_layer_freq=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None,
    kv_lora_rank=12, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
    n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=3,
    norm_topk_prob=False, scoring_func="softmax", routed_scaling_factor=1,
    n_group=1, topk_group=1, topk_method="greedy", vocab_size=96,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=4, mscale=0.707,
                      mscale_all_dim=0.707,
                      original_max_position_embeddings=16, type="yarn"),
    tie_word_embeddings=False, torch_dtype="float32", initializer_range=0.3,
)
SEED = 2**31 + 13

# Logits here are of order 1. The program and the reference run the same
# float32 arithmetic in another order (an online softmax over blocks of pages
# against one softmax a block of queries, the absorbed products against the
# expanded ones, a grouped product against every expert on every token):
# float32 rounding carried through 3 layers, measured at 2e-7 to 6e-7. 5e-6
# leaves an order of magnitude; the planted faults read 1e-3 and more.
LOGIT_TOL = 5e-6


def share(held):
    """The toy with only experts ``held = (lo, hi)`` on this chip."""
    lo, hi = held
    return dict(TOY, n_routed_experts=hi - lo, experts_held=[lo, hi],
                n_routed_experts_published=TOY["n_routed_experts"])


def slice_experts(weights, held):
    """``weights`` (all experts held) cut to the share ``held``."""
    lo, hi = held
    layers = [dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
              if "we_in" in w else w for w in weights["layers"]]
    return dict(weights, layers=layers)


def toy_program(cfg=None, weights=None):
    cfg = cfg or TOY
    weights = weights or reference.make_weights(cfg, SEED)
    model, params = driver.build_program(cfg, weights)
    return weights, model, params


def tokens(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], size=n).tolist()
